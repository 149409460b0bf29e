"""scripts/lint.py names functions in tables (the scopes a rule applies
to, by file basename); a rename in the package would silently empty a
rule.  Every name a table lists has to be a function of a module the
table's rule looks at."""

import ast
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint():
    spec = importlib.util.spec_from_file_location(
        "gordo_lint", os.path.join(ROOT, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LINT = _lint()

#: table → the directories its rule walks (scripts/lint.py: the D2H and
#: faults rules match a basename anywhere, the others inside their dirs)
TABLES = {
    "D2H_FORBIDDEN_SCOPES": (LINT.D2H_FORBIDDEN_SCOPES, ("gordo_tpu",)),
    "FAULTS_FORBIDDEN_SCOPES": (LINT.FAULTS_FORBIDDEN_SCOPES, ("gordo_tpu",)),
    "HOST_MATH_FORBIDDEN_SCOPES": (
        LINT.HOST_MATH_FORBIDDEN_SCOPES, (LINT.SERVE_DIR,)
    ),
    "BULK_FRAME_FORBIDDEN_SCOPES": (
        LINT.BULK_FRAME_FORBIDDEN_SCOPES, LINT.BULK_FRAME_DIRS
    ),
    "INGEST_SANCTIONED_SCOPES": (
        {os.path.basename(LINT.INGEST_PLANE_FILE):
            LINT.INGEST_SANCTIONED_SCOPES},
        (os.path.dirname(LINT.INGEST_PLANE_FILE),),
    ),
}


def _functions_defined(basename, dirs):
    """Names of every function (nested ones too: the build drive's are
    closures) in the files called ``basename`` under ``dirs``."""
    names, files = set(), []
    for top in dirs:
        for dirpath, _dirnames, filenames in os.walk(os.path.join(ROOT, top)):
            if basename in filenames:
                files.append(os.path.join(dirpath, basename))
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read())
        names |= {
            node.name for node in ast.walk(tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
    return names, files


@pytest.mark.parametrize("table", sorted(TABLES))
def test_scope_tables_name_functions_that_exist(table):
    scopes, dirs = TABLES[table]
    assert scopes
    for basename, wanted in scopes.items():
        defined, files = _functions_defined(basename, dirs)
        assert files, f"{table}: no {basename} under {dirs}"
        assert not set(wanted) - defined, (
            f"{table}[{basename!r}] names functions that {files} do not "
            f"define: {sorted(set(wanted) - defined)}"
        )
