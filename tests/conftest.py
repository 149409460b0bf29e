"""Test harness config.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(`("models", "data")` meshes, collectives) is exercised without TPU hardware
— the same simulation strategy the driver's `dryrun_multichip` uses.
"""

import os

# Must be set before jax backend initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests are hermetic CPU-only; the chip is reached only through
# chip_smoke.py (see .claude/skills/verify/SKILL.md).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_compile_state():
    """Clear jax's compiled-program caches after every test module.

    jax 0.9.0's XLA:CPU backend segfaults inside ``backend_compile_and_
    load`` when a fresh program compiles late in a long single-process
    run (~150+ tests of accumulated compile state; the same compile
    passes in isolation — reproduced repeatedly in this container, crash
    point moving with the suite's total compile pressure).  Dropping the
    caches per module bounds that state; modules that share program
    shapes pay one extra compile each, which is noise next to a crashed
    suite.  TPU is unaffected — this is purely a test-harness guard.
    """
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def sine_tags():
    """Synthetic multi-tag sine matrix (the RandomDataProvider-style backbone
    of integration tests, per SURVEY.md §5)."""
    rng = np.random.default_rng(42)
    n, f, latents = 600, 6, 2
    t = np.arange(n)[:, None]
    phases = rng.uniform(0, 2 * np.pi, size=(1, latents))
    freqs = rng.uniform(0.01, 0.1, size=(1, latents))
    Z = np.sin(freqs * t + phases)  # shared latent signals
    mix = rng.uniform(-1, 1, size=(latents, f))
    X = Z @ mix + 0.05 * rng.standard_normal((n, f))
    return X.astype(np.float32)
