"""The compile plane: one registry owning every jitted program in the stack.

Reference status: absent upstream — the reference's Keras models had no
compile step to manage.  Here every serving request and every fleet build
runs through an XLA executable, and before this module each call site
managed its own compilation implicitly: ``jax.jit`` traced-and-compiled on
the first unlucky call (ambushing the request path with a multi-second
stall), ``parallel/anomaly.py`` kept its own closure LRU, and nothing
counted compiles or cache reuse.  Both pjit-era training systems and the
AOT-compilation line of work treat compile-once-run-many as a first-class
system concern; this registry makes it one:

- :class:`Program` — an explicitly registered jitted program whose
  compiled executables are cached HERE, keyed by
  ``(program, static args, input signature, sharding)``.  Compilation goes
  through ``jit(...).lower(shapes).compile()`` (the jax AOT API), so it is
  schedulable: :meth:`Program.warm` compiles from shape structs alone —
  no input data, no execution — which is what the server's startup warmup
  and the ``gordo warmup`` init-container hook run off the serving thread.
  A call that misses compiles inline (counted + timed); a call that hits
  dispatches the cached executable (~15µs over jit's C++ fast path,
  noise next to a device dispatch).  Anything the AOT path cannot express
  (tracer inputs, exotic shardings) falls back to the plain jitted
  function — behavior, results, and numerics are identical either way.
- :func:`cached_closure` — the ONE LRU for per-configuration jitted
  closures (the fleet CV+fit programs of ``parallel/anomaly.py``), so the
  builder and the serving plane share a single eviction policy and one
  ``gordo_compiled_programs`` gauge instead of ad-hoc caches.
- :func:`jit` — a registered passthrough to ``jax.jit`` for programs that
  run inside other traced code (where AOT signature capture is
  meaningless).  Keeps ``scripts/lint.py``'s "no bare jax.jit outside
  gordo_tpu/compile/" gate honest: every program in the stack is at least
  *known* to the plane.
- warming state — the server's startup warmup flips
  :func:`set_warming`; ``/healthz`` reports ``warming`` vs ``ready`` and
  the coalescer queues new riders behind the warmup instead of letting
  each executor thread block on its own cold compile.
- jax's own compile monitoring — ``jax.monitoring`` listeners
  (:func:`install_compile_listeners`) map the on-disk compilation cache's
  hit/miss events onto ``gordo_compile_cache_hits_total`` /
  ``misses_total{cache="persistent"}`` so cross-process reuse (server
  restarts, forked multi-host workers) is attestable in a scrape, and sum
  every jit's trace/lower/backend seconds into
  ``gordo_compile_jax_seconds_total{stage}``.

Kill switch: ``GORDO_COMPILE_PLANE=off`` routes every :class:`Program`
call straight through the plain jitted function (today's pre-plane
behavior, bit for bit); the registry then only counts.
"""

from __future__ import annotations

import functools
import inspect
import logging
import os
import re
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from gordo_tpu import telemetry

logger = logging.getLogger(__name__)

# -- telemetry instruments (docs/observability.md "Compile plane") ----------
_COMPILE_SECONDS = telemetry.histogram(
    "gordo_compile_seconds",
    "Wall seconds spent lowering+compiling one program signature",
    labels=("program",),
)
_CACHE_HITS = telemetry.counter(
    "gordo_compile_cache_hits_total",
    "Compile-cache hits by cache layer "
    "(programs: in-process executable registry; persistent: jax's "
    "on-disk compilation cache)",
    labels=("cache",),
)
_CACHE_MISSES = telemetry.counter(
    "gordo_compile_cache_misses_total",
    "Compile-cache misses by cache layer",
    labels=("cache",),
)
_PROGRAMS_GAUGE = telemetry.gauge(
    "gordo_compiled_programs",
    "Programs resident in the compile-plane caches, by kind "
    "(aot: compiled executables; closure: jitted builder closures)",
    labels=("kind",),
)
_WARMING_GAUGE = telemetry.gauge(
    "gordo_compile_warming",
    "1 while a startup warmup is pre-compiling serving programs",
)
_JAX_COMPILE_SECONDS = telemetry.counter(
    "gordo_compile_jax_seconds_total",
    "Seconds jax spent compiling in this process, by stage "
    "(trace | lower | backend; backend includes persistent-cache loads)",
    labels=("stage",),
)
_AOT_FALLBACKS = telemetry.counter(
    "gordo_compile_aot_fallbacks_total",
    "AOT lower/compile/execute failures that degraded a program to plain "
    "jit dispatch, by program",
    labels=("program",),
)

#: executable-cache bound: power-of-two request buckets keep distinct
#: serving signatures log-few, so 256 covers a large project's full
#: program family with room for transient shapes
MAX_EXECUTABLES = int(os.environ.get("GORDO_COMPILE_PROGRAMS_MAX", "256"))
#: closure-cache bound — matches the historical _EXACT_PROGRAMS LRU of
#: parallel/anomaly.py it replaces
MAX_CLOSURES = 128


def _plane_enabled() -> bool:
    return os.environ.get("GORDO_COMPILE_PLANE", "on").strip().lower() not in (
        "off", "0", "false",
    )


def _sharding_token(leaf: Any) -> Any:
    """Cache-key component for a leaf's placement: only a committed
    mesh sharding distinguishes executables — numpy inputs, shape
    structs, and uncommitted single-device arrays all lower to the same
    program, so they share a token (None)."""
    from gordo_tpu.mesh import NamedSharding

    sharding = getattr(leaf, "sharding", None)
    return sharding if isinstance(sharding, NamedSharding) else None


def _leaf_sig(leaf: Any) -> Tuple:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return ("py", type(leaf).__name__)
    return (tuple(shape), str(getattr(leaf, "dtype", "?")),
            _sharding_token(leaf))


class BoundProgram:
    """A program pre-resolved to one compiled executable (see
    :meth:`Program.bind`): call with the FULL positional argument list
    (statics included, in signature order) — the statics are already
    baked into the executable and are dropped here by position."""

    __slots__ = ("_exe", "_dyn_idx")

    def __init__(self, exe: Any, dyn_idx: Tuple[int, ...]):
        self._exe = exe
        self._dyn_idx = dyn_idx

    def __call__(self, *ordered):
        return self._exe(*[ordered[i] for i in self._dyn_idx])


class Program:
    """One explicitly registered jitted program with an AOT executable
    cache.

    Call it exactly like the jitted function it wraps — same arguments,
    same results.  The difference is WHERE compilation happens: through
    the shared registry (counted, timed, evictable, pre-compilable via
    :meth:`warm`) instead of inside jit's opaque first-call path.
    """

    def __init__(
        self,
        name: str,
        fn: Callable,
        static_argnames: Tuple[str, ...] = (),
        registry: Optional["CompileRegistry"] = None,
    ):
        self.name = name
        self._fn = fn
        self._static = frozenset(static_argnames)
        import jax

        self._jitted = jax.jit(fn, static_argnames=tuple(static_argnames))
        self._signature = inspect.signature(fn)
        self._registry = registry or REGISTRY
        self._aot_broken = False  # one loud failure, then jit-only
        self._registry._register_program(self)

    # -- signature machinery -------------------------------------------------
    def _normalize(self, args: Tuple, kwargs: Dict) -> List[Any]:
        """Every call form → the full positional argument list (defaults
        applied), so cache keys and lowered calling conventions agree no
        matter how the caller spelled the invocation."""
        bound = self._signature.bind(*args, **kwargs)
        bound.apply_defaults()
        return [bound.arguments[p] for p in self._signature.parameters]

    def _split(self, ordered: List[Any]) -> Tuple[Tuple, List[Any]]:
        statics, dynamics = [], []
        for pname, value in zip(self._signature.parameters, ordered):
            if pname in self._static:
                statics.append((pname, value))
            else:
                dynamics.append(value)
        return tuple(statics), dynamics

    def _key(self, statics: Tuple, dynamics: List[Any]):
        import jax

        flat, treedef = jax.tree.flatten(dynamics)
        if any(isinstance(leaf, jax.core.Tracer) for leaf in flat):
            return None, None  # inside another trace: jit path only
        sig = tuple(_leaf_sig(leaf) for leaf in flat)
        return (self.name, statics, treedef, sig), flat

    # -- dispatch ------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if self._aot_broken or not _plane_enabled():
            return self._jitted(*args, **kwargs)
        try:
            ordered = self._normalize(args, kwargs)
            statics, dynamics = self._split(ordered)
            key, _ = self._key(statics, dynamics)
        except Exception:  # unbindable/unhashable: jit can still judge it
            return self._jitted(*args, **kwargs)
        if key is None:
            return self._jitted(*args, **kwargs)
        exe = self._registry._get_executable(key)
        if exe is None:
            _CACHE_MISSES.inc(1.0, "programs")
            exe = self._compile(key, ordered)
            if exe is None:  # AOT couldn't express it — jit fallback
                return self._jitted(*args, **kwargs)
        else:
            _CACHE_HITS.inc(1.0, "programs")
        try:
            return exe(*dynamics)
        except Exception:
            # a cached executable that stopped matching (device change,
            # sharding drift) must degrade, not 500 the request
            logger.exception(
                "compiled executable for %s failed; falling back to jit",
                self.name,
            )
            self._registry.note_aot_fallback(self.name)
            self._registry._drop_executable(key)
            return self._jitted(*args, **kwargs)

    def _compile(self, key, ordered: List[Any]):
        """Lower+compile one signature through the registry (timed)."""
        t0 = time.perf_counter()
        try:
            exe = self._jitted.lower(*ordered).compile()
        except Exception as exc:
            if not self._aot_broken:
                self._aot_broken = True
                self._registry.note_aot_fallback(self.name)
                logger.warning(
                    "AOT compile unavailable for program %s (%s); "
                    "dispatching through jit for this process",
                    self.name, exc,
                )
            return None
        _COMPILE_SECONDS.observe(time.perf_counter() - t0, self.name)
        self._registry._put_executable(key, exe)
        return exe

    def bind(self, *args, **kwargs) -> Optional["BoundProgram"]:
        """Resolve THIS call signature to its compiled executable once
        and return a :class:`BoundProgram` — the fixed-shape hot-loop
        fast path (the streaming step dispatches through one of these
        per arrival), skipping the per-call normalize/split/key work
        that dominates sub-millisecond dispatches.

        The binding is only valid while every subsequent call repeats
        the SAME static values and dynamic shapes/dtypes; callers must
        re-bind when either changes (a mismatched call raises from the
        executable rather than miscomputing).  Returns None when the
        AOT plane is off or cannot express the call — fall back to
        normal ``__call__`` dispatch then.
        """
        if self._aot_broken or not _plane_enabled():
            return None
        try:
            ordered = self._normalize(args, kwargs)
            statics, dynamics = self._split(ordered)
            key, _ = self._key(statics, dynamics)
        except Exception:
            return None
        if key is None:
            return None
        exe = self._registry._get_executable(key)
        if exe is None:
            _CACHE_MISSES.inc(1.0, "programs")
            exe = self._compile(key, ordered)
            if exe is None:
                return None
        dyn_idx = tuple(
            i for i, pname in enumerate(self._signature.parameters)
            if pname not in self._static
        )
        return BoundProgram(exe, dyn_idx)

    def warm(self, *args, **kwargs) -> float:
        """Pre-compile this program for the given argument shapes without
        executing it.  Dynamic arguments may be real arrays OR
        ``jax.ShapeDtypeStruct``s — warmup needs no input data.  Returns
        the compile seconds (0.0 when the signature was already cached).
        Raises on compile failure so warmup gates (CLI exit codes, k8s
        init containers) can fail loudly.
        """
        ordered = self._normalize(args, kwargs)
        statics, dynamics = self._split(ordered)
        key, _ = self._key(statics, dynamics)
        if key is None:
            raise ValueError(f"cannot warm {self.name} with tracer inputs")
        if self._registry._get_executable(key) is not None:
            return 0.0
        _CACHE_MISSES.inc(1.0, "programs")
        t0 = time.perf_counter()
        exe = self._jitted.lower(*ordered).compile()
        dt = time.perf_counter() - t0
        _COMPILE_SECONDS.observe(dt, self.name)
        self._registry._put_executable(key, exe)
        return dt


def _named_for(fn: Callable, name: str) -> Callable:
    """``fn`` under its registry name.  jax names a jit's XLA module for the
    function (``jit_<name>``), and every fleet closure is a local
    ``program``: as ``fleet.exact`` -> ``jit_fleet_exact`` the profiler's
    ``XLA Modules`` line, an ``--xla_dump_to`` directory and the largest
    loaded program say which program they hold.  The module's name is part
    of the persistent compile cache's key (debug information is not: a
    program that differs from a cached one by ``jax.named_scope``s alone
    loads the cached executable, with the cached names)."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = re.sub(r"\W", "_", name)
    return program


class ClosureProgram:
    """A per-configuration jitted CLOSURE with the :class:`Program`
    warm/bind surface.

    The fleet build programs of ``parallel/anomaly.py`` are closures over
    their configuration (module, fold layout, scaler options), built on
    demand and cached in the registry's closure LRU — they cannot be
    top-level :class:`Program`\\ s because the closure itself is part of
    the identity.  Wrapping each closure in a ``ClosureProgram`` gives the
    build plane the same two properties the serve plane gets from
    ``Program``: :meth:`warm` pre-compiles a signature from
    ``jax.ShapeDtypeStruct``\\ s alone (no data, no execution — schedulable
    before the first chunk's arrays exist), and a call whose signature was
    warmed dispatches the AOT executable directly instead of re-entering
    jit's trace-cache path.  A call whose signature was never warmed (the
    common cold-build case) falls through to the plain jitted closure —
    behavior and numerics identical either way, and near-zero overhead:
    the fallthrough is one attribute check while the executable dict is
    empty.

    Executables live on the instance, so they are evicted together with
    the closure when the registry's closure LRU drops it.
    """

    __slots__ = ("name", "_jitted", "_exes", "_lock", "_aot_broken")

    def __init__(self, fn: Callable, name: str = "closure", **jit_kwargs):
        import jax

        self.name = name
        self._jitted = jax.jit(_named_for(fn, name), **jit_kwargs)
        with REGISTRY._lock:
            REGISTRY._jits[name] = self._jitted
        self._exes: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self._aot_broken = False

    def _sig(self, args: Tuple):
        import jax

        flat, treedef = jax.tree.flatten(args)
        if any(isinstance(leaf, jax.core.Tracer) for leaf in flat):
            return None
        return (treedef, tuple(_leaf_sig(leaf) for leaf in flat))

    def warm(self, *args) -> float:
        """Pre-compile this closure for the given argument shapes without
        executing it (arguments may be real arrays or
        ``jax.ShapeDtypeStruct``\\ s, shardings included).  Returns compile
        seconds, 0.0 on a cache hit.  Raises on tracer inputs or compile
        failure so warmup gates fail loudly."""
        key = self._sig(args)
        if key is None:
            raise ValueError(f"cannot warm {self.name} with tracer inputs")
        with self._lock:
            if key in self._exes:
                return 0.0
        if not _plane_enabled():
            return 0.0
        _CACHE_MISSES.inc(1.0, "programs")
        t0 = time.perf_counter()
        exe = self._jitted.lower(*args).compile()
        dt = time.perf_counter() - t0
        _COMPILE_SECONDS.observe(dt, self.name)
        with self._lock:
            self._exes[key] = exe
        return dt

    def bind(self, *args):
        """Resolve this signature to its compiled executable (compiling if
        needed) and return it, or None when the AOT path is off or cannot
        express the call.  The executable is only valid while calls repeat
        the same shapes/dtypes/shardings."""
        if self._aot_broken or not _plane_enabled():
            return None
        key = self._sig(args)
        if key is None:
            return None
        with self._lock:
            exe = self._exes.get(key)
        if exe is not None:
            return exe
        try:
            self.warm(*args)
        except Exception as exc:
            self._aot_broken = True
            REGISTRY.note_aot_fallback(self.name)
            logger.warning(
                "AOT compile unavailable for closure %s (%s); "
                "dispatching through jit",
                self.name, exc,
            )
            return None
        with self._lock:
            return self._exes.get(key)

    def __call__(self, *args):
        # empty-dict check first: a never-warmed closure (the common cold
        # build) pays one truthiness test, not a tree flatten
        if self._aot_broken or not self._exes or not _plane_enabled():
            return self._jitted(*args)
        key = self._sig(args)
        if key is None:
            return self._jitted(*args)
        with self._lock:
            exe = self._exes.get(key)
        if exe is None:
            return self._jitted(*args)
        _CACHE_HITS.inc(1.0, "programs")
        try:
            return exe(*args)
        except Exception:
            logger.exception(
                "compiled executable for closure %s failed; "
                "falling back to jit", self.name,
            )
            REGISTRY.note_aot_fallback(self.name)
            with self._lock:
                self._exes.pop(key, None)
            return self._jitted(*args)


class CompileRegistry:
    """Process-wide compile-plane state: the AOT executable cache, the
    builder closure cache, the registered-program index, and the warming
    flag.  One instance (:data:`REGISTRY`) serves the whole process."""

    def __init__(
        self,
        max_executables: int = MAX_EXECUTABLES,
        max_closures: int = MAX_CLOSURES,
    ):
        self._lock = threading.Lock()
        self._executables: "OrderedDict[Any, Any]" = OrderedDict()
        self._closures: "OrderedDict[Any, Any]" = OrderedDict()
        self._programs: Dict[str, Program] = {}
        self._jits: Dict[str, Any] = {}
        self.max_executables = max_executables
        self.max_closures = max_closures
        self._warming = False
        #: AOT→jit degradations (lower/compile/execute failures)
        self._aot_fallbacks = 0

    # -- program index -------------------------------------------------------
    def _register_program(self, program: Program) -> None:
        with self._lock:
            self._programs[program.name] = program

    def programs(self) -> Dict[str, Program]:
        with self._lock:
            return dict(self._programs)

    # -- AOT executable cache ------------------------------------------------
    def _get_executable(self, key):
        with self._lock:
            exe = self._executables.get(key)
            if exe is not None:
                self._executables.move_to_end(key)
            return exe

    def _put_executable(self, key, exe) -> None:
        with self._lock:
            self._executables[key] = exe
            self._executables.move_to_end(key)
            while len(self._executables) > self.max_executables:
                self._executables.popitem(last=False)
            _PROGRAMS_GAUGE.set(float(len(self._executables)), "aot")

    def _drop_executable(self, key) -> None:
        with self._lock:
            self._executables.pop(key, None)
            _PROGRAMS_GAUGE.set(float(len(self._executables)), "aot")

    def n_executables(self) -> int:
        with self._lock:
            return len(self._executables)

    # -- closure cache (the unified _EXACT_PROGRAMS successor) --------------
    def cached_closure(self, key, factory: Callable[[], Any]):
        """Get-or-build a jitted closure under the shared LRU.  ``key``
        must capture everything the closure's trace depends on — the same
        contract the builder's old private cache had, now with ONE
        eviction policy and a gauge for the whole plane."""
        with self._lock:
            cached = self._closures.get(key)
            if cached is not None:
                self._closures.move_to_end(key)
                _CACHE_HITS.inc(1.0, "closures")
                return cached
        _CACHE_MISSES.inc(1.0, "closures")
        built = factory()
        with self._lock:
            self._closures[key] = built
            self._closures.move_to_end(key)
            while len(self._closures) > self.max_closures:
                self._closures.popitem(last=False)
            _PROGRAMS_GAUGE.set(float(len(self._closures)), "closure")
        return built

    def clear(self) -> None:
        """Drop every cached executable and closure (tests; device swaps)."""
        with self._lock:
            self._executables.clear()
            self._closures.clear()
            _PROGRAMS_GAUGE.set(0.0, "aot")
            _PROGRAMS_GAUGE.set(0.0, "closure")

    # -- AOT→jit degradations -------------------------------------------------
    def note_aot_fallback(self, program_name: str) -> None:
        with self._lock:
            self._aot_fallbacks += 1
        _AOT_FALLBACKS.inc(1.0, program_name)

    def aot_fallbacks(self) -> int:
        with self._lock:
            return self._aot_fallbacks

    # -- warming state -------------------------------------------------------
    def set_warming(self, warming: bool) -> None:
        with self._lock:
            self._warming = bool(warming)
        _WARMING_GAUGE.set(1.0 if warming else 0.0)

    def warming(self) -> bool:
        with self._lock:
            return self._warming


#: the process's compile plane
REGISTRY = CompileRegistry()


def program(
    name: str, fn: Callable, static_argnames: Tuple[str, ...] = ()
) -> Program:
    """Register ``fn`` as a compile-plane :class:`Program` (the AOT path).
    Use for top-level programs called with concrete inputs — the serving
    dispatch family."""
    return Program(name, fn, static_argnames=static_argnames)


def jit(fn: Optional[Callable] = None, *, name: Optional[str] = None, **kwargs):
    """Registered passthrough to ``jax.jit`` for programs that run inside
    other traces or need jit-only features (donation, shardings) — the
    compile plane knows them by name; dispatch is jax's unchanged.
    Usable bare (``compile.jit(fn)``) or parameterized
    (``compile.jit(static_argnames=...)(fn)``)."""
    import jax

    def wrap(f: Callable):
        jitted = jax.jit(f, **kwargs)
        label = name or getattr(f, "__qualname__", getattr(f, "__name__", "jit"))
        with REGISTRY._lock:
            REGISTRY._jits[label] = jitted
        return jitted

    if fn is not None:
        return wrap(fn)
    return wrap


def cached_closure(key, factory: Callable[[], Any]):
    """Module-level convenience for :meth:`CompileRegistry.cached_closure`
    on the process registry."""
    return REGISTRY.cached_closure(key, factory)


def closure_program(
    fn: Callable, *, name: str = "closure", **jit_kwargs
) -> ClosureProgram:
    """Wrap a per-configuration closure as a :class:`ClosureProgram`
    (warm/bind-capable jitted closure).  Pair with :func:`cached_closure`
    so the wrapper shares the closure LRU's eviction."""
    return ClosureProgram(fn, name=name, **jit_kwargs)


def aot_fallbacks() -> int:
    """How many times this process degraded a program from its AOT
    executable to plain ``jit`` (lower/compile refused, or a cached
    executable stopped matching).  Serving keeps answering either way;
    the build summary and ``/healthz`` publish the count so the
    degradation is visible from outside the process."""
    return REGISTRY.aot_fallbacks()


def warming() -> bool:
    return REGISTRY.warming()


def set_warming(value: bool) -> None:
    REGISTRY.set_warming(value)


# ---------------------------------------------------------------------------
# jax.monitoring bridge: persistent-cache hits/misses + compile seconds
# ---------------------------------------------------------------------------

_MONITORING_INSTALLED = False
_PERSISTENT_EVENTS = {
    "/jax/compilation_cache/cache_hits": _CACHE_HITS,
    "/jax/compilation_cache/cache_misses": _CACHE_MISSES,
}
_COMPILE_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


def install_compile_listeners() -> None:
    """Bridge jax's own compile monitoring onto the compile plane's
    series (idempotent; the entry points call it through
    ``enable_persistent_compile_cache``):

    - on-disk compilation-cache hit/miss events →
      ``gordo_compile_cache_*_total{cache="persistent"}``, so a scrape
      attests cross-process compile reuse.  jax records a "miss" when it
      WRITES an entry, so misses count programs written;
    - trace / lower / backend-compile durations of EVERY jit in the
      process (the fleet build programs compile through plain jit, which
      ``gordo_compile_seconds`` never sees) →
      ``gordo_compile_jax_seconds_total{stage}``: what a run paid to
      compile, apart from what it paid to compute.  ``backend`` includes
      the load time of persistent-cache hits.
    """
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return
    from jax import monitoring

    def _on_event(event: str, **kw) -> None:
        counter = _PERSISTENT_EVENTS.get(event)
        if counter is not None:
            counter.inc(1.0, "persistent")

    def _on_duration(event: str, duration_secs: float, **kw) -> None:
        stage = _COMPILE_STAGE_EVENTS.get(event)
        if stage is not None:
            _JAX_COMPILE_SECONDS.inc(duration_secs, stage)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _MONITORING_INSTALLED = True
