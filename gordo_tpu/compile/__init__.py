"""Compile plane: AOT program registry, warmup manifest, cold-start tools.

See :mod:`gordo_tpu.compile.registry` for the design.  Every jitted
program in the stack registers here (``scripts/lint.py`` rejects bare
``jax.jit`` outside this package); the serving dispatch family
additionally compiles ahead-of-time through :func:`program` so startup
warmup — driven by the manifest ``builder/fleet_build.py`` writes — can
pre-compile before the first request arrives.
"""

from gordo_tpu.compile.registry import (  # noqa: F401
    REGISTRY,
    ClosureProgram,
    CompileRegistry,
    Program,
    aot_fallbacks,
    cached_closure,
    closure_program,
    install_compile_listeners,
    jit,
    program,
    set_warming,
    warming,
)
from gordo_tpu.compile.warmup import (  # noqa: F401
    WARMUP_DIR,
    filter_manifest,
    load_warmup_manifest,
    warmup_collection,
    write_warmup_manifest,
)

__all__ = [
    "REGISTRY",
    "ClosureProgram",
    "CompileRegistry",
    "Program",
    "WARMUP_DIR",
    "aot_fallbacks",
    "cached_closure",
    "closure_program",
    "filter_manifest",
    "install_compile_listeners",
    "jit",
    "load_warmup_manifest",
    "program",
    "set_warming",
    "warming",
    "warmup_collection",
    "write_warmup_manifest",
]
