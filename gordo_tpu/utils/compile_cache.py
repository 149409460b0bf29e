"""Persistent XLA compilation cache for cross-process compile reuse.

Reference equivalent: none — the reference's Keras/TF models had no
ahead-of-time compile cost to amortize.  Here every fleet program (CV +
multi-epoch fit, LSTM scans) is an XLA executable that can take tens of
seconds to compile cold; a builder pod that restarts, or a project built
across several CLI invocations, would re-pay every compile.  jax's
persistent compilation cache writes executables to disk keyed by program
fingerprint, so a process-cold build of an already-seen program shape
loads in milliseconds instead.

Where the cache lives is decided OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets no directory in code (the generated manifests stamp it to the shared
``/compile-cache`` volume).  Unset, the cache sits at one fixed,
git-ignored path inside the checkout (:data:`DEFAULT_CACHE_DIR`) — the
directory is part of every entry's key, so a path that moved (``$HOME``,
a temp dir, a pid) would never hit.

Enabled by default at the CLI/builder/server entry points — on TPU (and
GPU) backends only.  **XLA:CPU is excluded**: its cached AOT executables
embed the compiling process's detected machine features, and loading an
entry whose feature set disagrees with the current detection crashed the
process in this container (SIGILL-class segfault inside
``compilation_cache.get_executable_and_time`` — the loader itself warns
"could lead to execution errors such as SIGILL").  On CPU the cold
compiles are also far cheaper, so the trade is not worth the risk;
``GORDO_COMPILE_CACHE=force`` overrides for a trusted single-machine
setup and ``GORDO_COMPILE_CACHE=0`` opts out entirely.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

#: the program's own default: ``<checkout>/.jax_cache`` (listed in
#: ``.gitignore``), derived from the package location
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_ENABLED = False


def enable_persistent_compile_cache() -> bool:
    """Turn on jax's on-disk compilation cache (idempotent; TPU/GPU only
    unless forced — see module docstring for the XLA:CPU hazard).

    Returns True when the cache is active.  Call it before the process's
    first compile: jax tolerates late enabling, but whatever compiled
    earlier is never written.  A cache directory that cannot be created
    raises — an uncached process re-pays every compile on every start,
    which must not happen silently.
    """
    global _ENABLED
    if _ENABLED:
        return True
    import jax

    from gordo_tpu.compile import install_compile_listeners

    # persistent hit/miss events and per-stage compile seconds land on the
    # compile plane's gordo_compile_* series on every backend, cached or
    # not, so a /metrics scrape or build snapshot says what compiling cost
    install_compile_listeners()
    flag = os.environ.get("GORDO_COMPILE_CACHE", "1")
    opted_out = flag in ("0", "false", "no")
    if opted_out or (flag != "force" and jax.default_backend() == "cpu"):
        if jax.config.jax_compilation_cache_dir:
            # JAX_COMPILATION_CACHE_DIR is set, so jax would cache on its
            # own: the exclusion has to switch the cache itself off
            jax.config.update("jax_enable_compilation_cache", False)
        if not opted_out:
            logger.debug(
                "Persistent compile cache skipped on CPU backend "
                "(AOT feature-mismatch hazard; GORDO_COMPILE_CACHE=force "
                "overrides)"
            )
        return False
    jax.config.update("jax_enable_compilation_cache", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # default min-compile-time (1s) keeps tiny programs out of the
    # cache; the fleet fit/CV programs are seconds-to-minutes.
    # GORDO_COMPILE_CACHE_MIN_SECONDS overrides (the cold-start bench
    # sets 0 so its deliberately small programs exercise the disk
    # round-trip; a serving fleet of sub-second programs may too).
    min_secs = os.environ.get("GORDO_COMPILE_CACHE_MIN_SECONDS")
    if min_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_secs)
        )
    _ENABLED = True
    logger.debug(
        "Persistent compile cache at %s", jax.config.jax_compilation_cache_dir
    )
    return True
