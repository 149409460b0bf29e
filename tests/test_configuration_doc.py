"""docs/configuration.md lists the ``GORDO_*`` environment variables.
Held in both directions: a variable the package names is documented, and
a documented one is still named by the package, so the list neither
lags behind the code nor outlives it."""

import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIABLE = re.compile(r"GORDO_[A-Z0-9_]+")


def _package_variables():
    found = set()
    for dirpath, _dirnames, filenames in os.walk(os.path.join(ROOT, "gordo_tpu")):
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as fh:
                    found |= set(VARIABLE.findall(fh.read()))
    return found


def _documented_variables():
    with open(os.path.join(ROOT, "docs", "configuration.md")) as fh:
        return set(VARIABLE.findall(fh.read()))


def test_every_package_variable_is_documented():
    missing = _package_variables() - _documented_variables()
    assert not missing, (
        f"named in gordo_tpu/, absent from docs/configuration.md: "
        f"{sorted(missing)}"
    )


def test_every_documented_variable_is_in_the_package():
    stale = _documented_variables() - _package_variables()
    assert not stale, (
        f"listed in docs/configuration.md, named nowhere in gordo_tpu/: "
        f"{sorted(stale)}"
    )
