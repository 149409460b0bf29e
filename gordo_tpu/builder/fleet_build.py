"""Project-scale builds: the whole machine list through the fleet engine.

Reference equivalent: the Argo workflow's fan-out — N independent
``gordo build`` pods, one per machine, each running
``builder/build_model.py::provide_saved_model`` (SURVEY.md §4.4).

TPU-native replacement: machines are bucketed by model-signature +
data-shape; each bucket trains as ONE stacked XLA program
(``gordo_tpu.parallel.anomaly.FleetDiffBuilder``) sharded over the device
mesh.  Per-machine contracts are preserved exactly: every machine still
gets its own artifact directory, metadata JSON, and config-hash cache entry
(``provide_saved_model`` cache parity) — a re-run project build skips
already-built machines, and a machine whose config the fleet engine can't
express falls back to the single-machine builder transparently.

Data loading stays host-side, streaming, and memory-bounded: machines are
bucketed by CONFIG alone (model signature + tag widths — no data needed),
then built chunk by chunk with the loader pool prefetching exactly ONE
chunk ahead while the device trains the current one.  Peak host memory is
two chunks of arrays (2 x ``max_bucket_size`` machines), not the whole
project — the reference held one machine per pod; a 10k-machine
load-everything pass here would be tens of GB.  Arrays free as soon as a
machine's artifact is dumped; ``ProjectBuildResult.peak_loaded`` records
the high-water mark so tests can hold the bound.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import hashlib
import logging
import math
import os
import shutil
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from gordo_tpu import artifacts, serializer, telemetry
from gordo_tpu.mesh import Mesh
from gordo_tpu.builder.timeline import (
    STAGE_SECONDS as _PIPE_STAGE_SECONDS,
    BuildTimeline,
)
from gordo_tpu.builder.build_model import (
    assemble_metadata,
    build_model,
    calculate_model_key,
    lookup_cached_artifact,
)
from gordo_tpu.ingest import plane as ingest_plane
from gordo_tpu.parallel.anomaly import (
    FleetDiffBuilder,
    _model_axis_pad,
    analyze_definition,
)
from gordo_tpu.utils import disk_registry
from gordo_tpu.workflow.config import Machine

logger = logging.getLogger(__name__)

# -- telemetry instruments (docs/observability.md) --------------------------
_BUILD_MACHINES_TOTAL = telemetry.counter(
    "gordo_build_machines_total",
    "Machines resolved by project builds, by path taken",
    labels=("path",),  # cached | fleet | single | failed
)
_BUILD_MACHINE_SECONDS = telemetry.histogram(
    "gordo_build_machine_seconds",
    "Per-machine build seconds (fleet machines: bucket seconds / size)",
    labels=("path",),
)
_BUILD_BUCKET_SECONDS = telemetry.histogram(
    "gordo_build_bucket_seconds",
    "Stacked CV+fit seconds per fleet chunk",
)
_DATA_LOAD_SECONDS = telemetry.histogram(
    "gordo_build_data_load_seconds",
    "Per-machine dataset load+assembly seconds (loader pool)",
)

# -- build-pipeline instruments (docs/perf.md "Build pipeline"); the stage
#    histogram and the device-idle counter live with the chunk timeline
#    (builder/timeline.py) ------------------------------------------------
_PIPE_STALL_SECONDS = telemetry.counter(
    "gordo_build_pipeline_stall_seconds",
    "Seconds the pipeline drive loop stalled on a stage "
    "(load: waiting for the loader pool, write: writer queue full)",
    labels=("stage",),
)
_PIPE_WRITER_QUEUE_DEPTH = telemetry.gauge(
    "gordo_build_pipeline_writer_queue_depth",
    "Artifact writes queued or in flight in the background writer pool",
)
_PIPE_CHUNKS_TOTAL = telemetry.counter(
    "gordo_build_pipeline_chunks_total",
    "Fleet chunks driven to completion, by execution path",
    labels=("path",),  # pipelined
)

# -- incremental refresh knobs (docs/configuration.md) ----------------------
#: fraction of the configured epochs a warm-start rebuild trains for —
#: the previous generation's weights are most of the way there already
ENV_REFRESH_EPOCH_FRACTION = "GORDO_REFRESH_EPOCH_FRACTION"
DEFAULT_REFRESH_EPOCH_FRACTION = 0.25
#: parity gate: the warm rebuild's final training loss must stay within
#: this factor of the previous artifact's recorded final loss, or the
#: machine rebuilds cold (full epochs, fresh init) with the reason attested
#: in its metadata
ENV_REFRESH_PARITY_FACTOR = "GORDO_REFRESH_PARITY_FACTOR"
DEFAULT_REFRESH_PARITY_FACTOR = 1.5


def _refresh_epoch_fraction() -> float:
    try:
        frac = float(os.environ.get(
            ENV_REFRESH_EPOCH_FRACTION, DEFAULT_REFRESH_EPOCH_FRACTION
        ))
    except ValueError:
        return DEFAULT_REFRESH_EPOCH_FRACTION
    return min(max(frac, 0.0), 1.0)


def _refresh_parity_factor() -> float:
    try:
        return float(os.environ.get(
            ENV_REFRESH_PARITY_FACTOR, DEFAULT_REFRESH_PARITY_FACTOR
        ))
    except ValueError:
        return DEFAULT_REFRESH_PARITY_FACTOR


def _warm_epochs(cfg) -> int:
    """Reduced-epoch budget for a warm-start fit (never below 1)."""
    return max(1, math.ceil(cfg.epochs * _refresh_epoch_fraction()))


def _detector_estimator(detector):
    """The trained JAX estimator inside a detector/pipeline artifact."""
    from gordo_tpu.pipeline import Pipeline

    base = getattr(detector, "base_estimator", detector)
    return base._final if isinstance(base, Pipeline) else base


def _resolve_warm_params(
    output_dir: str, names: Sequence[str]
) -> Dict[str, Tuple[Any, Optional[float]]]:
    """Previous-generation warm-start material via zero-copy
    :class:`~gordo_tpu.artifacts.PackStore` reads:
    ``{name: (params pytree, previous final training loss)}``.

    Machines the pack index doesn't know (first build, v1-only artifact)
    are simply absent — the caller rebuilds them cold and attests why.
    The arrays stay memory-mapped until the fleet program stacks them, so
    resolving a subset never reads the rest of the fleet's bytes."""
    try:
        store = artifacts.open_store(output_dir)
    except Exception:
        logger.exception(
            "warm-start: pack store open failed under %s", output_dir
        )
        return {}
    if store is None:
        return {}
    resolved: Dict[str, Tuple[Any, Optional[float]]] = {}
    for name in names:
        if name not in store:
            continue
        try:
            est = _detector_estimator(store.load_model(name))
            params = getattr(est, "params_", None)
            if params is None:
                continue
            hist = getattr(est, "history_", None)
            prev_loss = (
                float(np.asarray(hist).ravel()[-1])
                if hist is not None and np.size(hist) else None
            )
        except Exception:
            logger.exception(
                "warm-start: could not resolve previous params for %s", name
            )
            continue
        resolved[name] = (params, prev_loss)
    return resolved


class _ArtifactWriter:
    """Background artifact-writer pool — stage C of the build pipeline.

    ``serializer.dump`` (pickle + YAML + JSON per machine) runs off the
    device critical path on a small thread pool behind a BOUNDED queue:
    :meth:`submit` blocks once ``max_queued`` writes are outstanding, so
    a slow disk backpressures the drive loop instead of buffering
    unbounded pickled fleets.  The write function is expected to place
    each artifact atomically (scratch dir + rename — see
    :func:`_write_artifact`) and to do its own failure recording;
    ``drain()`` blocks until every queued write finished.  The resumable
    exit-75 path drains BEFORE the shard state transitions, so recorded
    progress never references a half-written artifact.
    """

    def __init__(
        self,
        write_fn: Callable[..., None],
        timeline: BuildTimeline,
        max_workers: int = 1,
        max_queued: int = 512,
    ):
        # one worker by default: artifact pickling is GIL-bound, so extra
        # writer threads buy no parallelism and cost switch churn on
        # small hosts (the bench container is 1-core)
        self._write_fn = write_fn
        self._timeline = timeline
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="gordo-artifact-writer"
        )
        self._slots = threading.BoundedSemaphore(max_queued)
        self._lock = threading.Lock()
        self._depth = 0
        self._futures: List[Any] = []

    def submit(self, items: Sequence[Tuple], chunk: int) -> None:
        """Queue chunk number ``chunk``'s artifact writes as a single pool
        task (one handoff per chunk, not per machine).  Blocks for queue
        slots — one per artifact — when the writer is ``max_queued``
        behind."""
        t0 = time.time()
        for _ in items:
            self._slots.acquire()
        stall = time.time() - t0
        if stall > 0.001:
            _PIPE_STALL_SECONDS.inc(stall, "write")
        with self._lock:
            self._depth += len(items)
            _PIPE_WRITER_QUEUE_DEPTH.set(float(self._depth))
        # in a copy of this context: the write spans keep the build's
        # trace id and parent span on the pool's thread
        self._futures.append(self._pool.submit(
            contextvars.copy_context().run, self._run, list(items), chunk
        ))

    def _run(self, items: List[Tuple], chunk: int) -> None:
        for args in items:
            try:
                with self._timeline.phase("write", chunk):
                    self._write_fn(*args)
            finally:
                self._slots.release()
                with self._lock:
                    self._depth -= 1
                    _PIPE_WRITER_QUEUE_DEPTH.set(float(self._depth))

    def drain(self) -> None:
        """Block until every queued write has completed, then shut the
        pool down.  Write errors are recorded by the write function, not
        raised here — a failed dump must fail ONE machine, not the drain."""
        futures, self._futures = self._futures, []
        for fut in futures:
            fut.result()
        self._pool.shutdown(wait=True)


#: fleet programs are chunked so a bucket's stacked arrays stay well inside
#: device memory (tiny models: the data, not the params, is the footprint).
#: Not re-measured on an attached chip.
DEFAULT_MAX_BUCKET = 512

#: recurrent (lookback-windowed) signatures chunk smaller: the r6
#: machines-per-bucket sweep (`scripts/sweep_constants.py lstmbucket`,
#: CPU jax, docs/perf.md) measured the warm CV+fit rate DECLINING with
#: bucket size (5,019 models/h at 64 → 3,895 at 512 — wider vmap, more
#: cache pressure) while the cold rate peaks mid-table (compile
#: amortization).  128 sits within 10% of the best warm rate, builds
#: cold 18% faster than 64, and keeps 4x headroom vs 512 on the windows
#: tensors (∝ machines × rows × lookback × tags) that bound LSTM
#: dispatches.  Not re-measured on an attached chip.
DEFAULT_MAX_BUCKET_LSTM = 128


#: auto-pad (VERDICT weak #4): when neither ragged strategy is chosen and
#: the config-level estimate predicts more than this many seconds of
#: per-distinct-length XLA compiles, ``build_project`` turns on
#: ``pad_lengths`` itself rather than only warning.  300s ≈ 22 distinct
#: lengths at the measured ~13.7s/compile — small ragged dev projects
#: (a handful of lengths) stay in exact-parity mode, while the
#: 1000-machine filtered project that forgot the flag no longer pays the
#: hour of compiles the feature was built to kill.
DEFAULT_AUTO_PAD_BUDGET_SECONDS = 300.0

#: the alignment auto-pad selects.  128 collapses any ragged bucket to
#: ~(length range)/128 programs at a bounded cost of < 128 weight-masked
#: rows per machine, and is large enough that the row counts row
#: filtering produces in practice (thousands) land in few groups.  An
#: explicit ``pad_lengths`` always wins over this default.
DEFAULT_AUTO_PAD_LENGTHS = 128


def estimate_ragged_compile_seconds(machines: Sequence[Machine]) -> float:
    """Config-level estimate of the EXTRA XLA compile seconds an exact-mode
    build of ``machines`` would pay for ragged train lengths (one program
    per distinct row count beyond the one-per-bucket floor).  The same
    estimator ``workflow plan`` prints its warning from."""
    # lazy import: workflow.generator imports gordo_tpu.builder at module
    # scope, so a top-level import here would cycle
    from gordo_tpu.workflow.generator import (
        COMPILE_SECONDS_PER_LENGTH,
        _fleet_signature,
        _ragged_length_estimate,
    )

    buckets: Dict[str, List[Machine]] = {}
    for m in machines:
        buckets.setdefault(_fleet_signature(m), []).append(m)
    if not buckets:
        return 0.0
    est_lengths = sum(
        _ragged_length_estimate(members) for members in buckets.values()
    )
    extra = est_lengths - len(buckets)  # 1 compile per bucket is the floor
    return max(0.0, extra * COMPILE_SECONDS_PER_LENGTH)


#: what the exact fleet program holds per float32 parameter and machine:
#: ``params0``, the running fit's weights, their gradient, Adam's two moments
#: (``parallel.anomaly._exact_fleet_program``)
FIT_BYTES_PER_PARAMETER = 20

#: a chunk's parameters and optimiser state may take this share of a device;
#: the rest is for the data, the activations and the next chunk's inputs
PARAMETER_MEMORY_SHARE = 0.25

#: where the backend reports no limit (XLA:CPU): one v5e chip
ASSUMED_DEVICE_BYTES = 16 * 2 ** 30


def _parameter_count(spec, widths) -> Optional[int]:
    """Parameters of one machine's model, from shapes alone
    (``jax.eval_shape`` of the module's init); None where the spec or the
    widths do not say.  A factory that cannot be sized raises: a chunk
    planned without the count may be five hundred models that do not fit."""
    est = getattr(spec, "estimator_proto", None)
    if est is None or widths is None:
        return None
    import jax
    import jax.numpy as jnp

    from gordo_tpu.registry import lookup_factory

    module = lookup_factory(est.model_type, est.kind)(
        n_features=int(widths[0]), n_features_out=int(widths[1]),
        **spec.factory_kwargs,
    )
    # the estimator's own windowing gives the input's rank
    rows = jnp.zeros(
        (int(getattr(est, "lookback_window", 1)) + 1, int(widths[0])),
        jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), est._make_inputs(rows)[:1]))
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def _device_bytes() -> int:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or ASSUMED_DEVICE_BYTES)


def default_bucket_size(spec, widths: Optional[Tuple[int, int]] = None) -> int:
    """Per-signature ``max_bucket_size`` default: recurrent estimators
    (``lookback_window > 1`` — LSTM family) chunk at
    ``DEFAULT_MAX_BUCKET_LSTM``, everything else at
    ``DEFAULT_MAX_BUCKET`` — and never more machines than whose parameters
    and optimiser state, at ``FIT_BYTES_PER_PARAMETER``, fit a quarter of a
    device: a model of half a billion parameters is a chunk of one."""
    est = getattr(spec, "estimator_proto", None)
    size = DEFAULT_MAX_BUCKET
    if getattr(est, "lookback_window", 1) > 1:
        size = DEFAULT_MAX_BUCKET_LSTM
    params = _parameter_count(spec, widths)
    if params:
        budget = PARAMETER_MEMORY_SHARE * _device_bytes()
        size = max(1, min(size, int(budget // (FIT_BYTES_PER_PARAMETER * params))))
    return size


class ProjectBuildResult:
    """Per-machine artifact dirs + build accounting for one project build."""

    def __init__(self):
        self.artifacts: Dict[str, str] = {}
        self.cached: List[str] = []
        self.fleet_built: List[str] = []
        self.single_built: List[str] = []
        self.failed: Dict[str, str] = {}
        #: machines a fleet program was meant to build but that fell to
        #: the single-machine builder, with the reason (the program failed
        #: to trace, compile, run or fetch; loaded widths disagreed with
        #: the config) — the build carries on, the summary says so
        self.demoted: Dict[str, str] = {}
        #: devices that held the fleet programs' result arrays
        self.devices: set = set()
        self.seconds: float = 0.0
        #: high-water mark of machines whose (X, y) arrays were resident at
        #: once — the streaming pipeline bounds this at two chunks
        self.peak_loaded: int = 0
        #: the pad_lengths value auto-selected by the ragged-strategy
        #: heuristic (None when off, explicit, or not triggered)
        self.auto_pad: Optional[int] = None
        #: (process_id, num_processes) when this was one shard of a
        #: multi-host build
        self.shard: Optional[Tuple[int, int]] = None
        #: seconds between one fleet program's end and the next one's
        #: start, by the host's stamps (``timeline.DeviceOccupancy``; the
        #: first gap runs from build start).  0.0 with telemetry off: no
        #: watcher thread stamps the programs' ends then
        self.device_idle_seconds: float = 0.0
        #: one dict per fleet chunk — every phase's intervals, programs,
        #: device gaps and their split (``builder/timeline.py``); also
        #: written to ``.gordo-telemetry/timeline-*.json``
        self.timeline: List[Dict[str, Any]] = []
        #: artifact format this build wrote ("v1" per-machine dirs, "v2"
        #: memory-mapped bucket packs — see gordo_tpu/artifacts/)
        self.artifact_format: str = "v1"
        #: machines rebuilt from the previous generation's params under
        #: the parity gate (warm_start=True builds only)
        self.warm_started: List[str] = []
        #: machines a warm_start build rebuilt COLD, with the attested
        #: reason (no previous params / parity gate / single path / ...)
        self.warm_fallbacks: Dict[str, str] = {}
        #: the published artifact generation after this build's stamp
        #: (v2 only; None for v1 builds)
        self.generation: Optional[int] = None
        #: resolved loader-pool thread count (adaptive when the caller
        #: passed data_workers=None — see build_project)
        self.loader_workers: int = 0
        #: build-ingest plane accounting: machines / fetches / dedup_hits /
        #: vectorized / fallback counts accumulated across chunks by
        #: ingest.plane.load_chunk
        self.ingest: Dict[str, Any] = {}

    def summary(self) -> Dict[str, Any]:
        from gordo_tpu import compile as compile_plane
        from gordo_tpu.mesh import device_doc

        out = {
            "device": device_doc(self.devices),
            "n_machines": len(self.artifacts) + len(self.failed),
            "cached": len(self.cached),
            "fleet_built": len(self.fleet_built),
            "single_built": len(self.single_built),
            "failed": dict(self.failed),
            "demoted": {
                "machines": len(self.demoted),
                "reasons": sorted(set(self.demoted.values())),
            },
            "aot_fallbacks": compile_plane.aot_fallbacks(),
            "build_seconds": self.seconds,
            "peak_loaded_machines": self.peak_loaded,
            "device_idle_seconds": self.device_idle_seconds,
            "artifact_format": self.artifact_format,
        }
        if self.loader_workers:
            out["loader_workers"] = self.loader_workers
        out["ingest"] = dict(self.ingest)
        if self.warm_started or self.warm_fallbacks:
            out["warm_started"] = len(self.warm_started)
            out["warm_fallbacks"] = dict(self.warm_fallbacks)
        if self.generation is not None:
            out["generation"] = self.generation
        if self.auto_pad:
            out["auto_pad_lengths"] = self.auto_pad
        if self.shard:
            out["shard"] = {
                "process_id": self.shard[0],
                "num_processes": self.shard[1],
                "machines": sorted(self.artifacts) + sorted(self.failed),
            }
        return out


class _LoadTracker:
    """Counts machines with live arrays; records the high-water mark."""

    def __init__(self):
        self._lock = threading.Lock()
        self.current = 0
        self.peak = 0

    def acquire(self) -> None:
        with self._lock:
            self.current += 1
            self.peak = max(self.peak, self.current)

    def release(self, n: int = 1) -> None:
        with self._lock:
            self.current -= n


@dataclasses.dataclass
class _PendingChunk:
    """One chunk between its dispatch and its collect: the in-flight
    :class:`~gordo_tpu.parallel.anomaly.PendingFleetBuild` plus everything
    the finish side needs (the loaded arrays stay referenced here so a
    collect-time failure can still demote to singles and free them).
    Warm-start chunks build synchronously inside dispatch (the parity
    gate must read results before deciding on in-chunk cold rebuilds), so
    they arrive with ``detectors`` already set and ``pending`` None."""

    index: int
    key: Tuple
    ok_chunk: List[Machine]
    loaded: Dict[str, Tuple]
    t0: float
    pending: Optional[Any] = None
    detectors: Optional[List[Any]] = None


def _as_machine(m: Union[Machine, Dict[str, Any]]) -> Machine:
    if isinstance(m, Machine):
        return m
    return Machine.from_config(m)


def _demote_to_single(
    m: Machine,
    singles: List[Machine],
    machine_keys: Dict[str, str],
    key_extra: Optional[Dict[str, Any]],
    demoted: set,
    result: "ProjectBuildResult",
    reason: str,
) -> None:
    """Route a fleet-intended machine to the single builder, recording
    ``reason`` in ``result.demoted``.  The single
    path trains on FULL untruncated data, so if an aligned build keyed this
    machine with the alignment component, the key must drop it — otherwise
    a later aligned run would cache-hit an artifact that never truncated.
    ``demoted`` marks the machine so the singles pass re-checks the cache
    under the rewritten key (a deterministic demotion — e.g. a provider
    whose widths never match config — would otherwise retrain every run)."""
    if key_extra:
        machine_keys[m.name] = calculate_model_key(
            m.name, m.model, m.dataset, m.metadata, extra=None
        )
        demoted.add(m.name)
    result.demoted[m.name] = reason
    singles.append(m)


def _config_widths(dataset_cfg: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """(n_features, n_outputs) derivable from the dataset CONFIG alone, or
    None — the streaming pipeline buckets machines before any data loads."""
    tags = dataset_cfg.get("tag_list") or dataset_cfg.get("tags")
    if not tags:
        return None
    targets = dataset_cfg.get("target_tag_list") or tags
    return len(tags), len(targets)


def _traced_build(fn):
    """Run a build under one trace id (the caller's, else a new one) and
    one root span, ``gordo.build.project``: every phase span of the build,
    on whichever thread, has it as its parent."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        minted = telemetry.current_trace_id() is None
        if minted:
            telemetry.set_trace_id(telemetry.new_trace_id())
        try:
            with telemetry.span("gordo.build.project"):
                return fn(*args, **kwargs)
        finally:
            if minted:
                telemetry.set_trace_id(None)

    return traced


@_traced_build
def build_project(
    machines: Sequence[Union[Machine, Dict[str, Any]]],
    output_dir: str,
    model_register_dir: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    replace_cache: bool = False,
    max_bucket_size: Optional[int] = None,
    data_workers: Optional[int] = None,
    align_lengths: Optional[int] = None,
    pad_lengths: Optional[int] = None,
    auto_pad: bool = True,
    auto_pad_budget_seconds: Optional[float] = None,
    shard: Optional[Any] = None,
    artifact_format: Optional[str] = None,
    warm_start: bool = False,
) -> ProjectBuildResult:
    """Build every machine; fleet-bucket the homogeneous ones.

    ``warm_start=True`` is the incremental-refresh mode (v2 only —
    requires an existing pack index): pass the SUBSET of machines to
    rebuild, and each one's previous-generation params resolve via
    zero-copy :class:`~gordo_tpu.artifacts.PackStore` reads to seed a
    reduced-epoch warm fit (``GORDO_REFRESH_EPOCH_FRACTION`` of the
    configured epochs).  A per-machine parity gate — the warm final
    training loss must stay within ``GORDO_REFRESH_PARITY_FACTOR`` of
    the previous artifact's — demotes failing machines to a full cold
    rebuild, attested in ``result.warm_fallbacks`` and the machine's
    metadata.  Rebuilt machines already in the index publish through
    ``artifacts.delta_write`` (in-place slot rewrites + one atomic
    index swap that stamps its own generation), so live servers
    delta-reload exactly the touched packs; the config-hash cache is
    bypassed (the configs haven't changed — the data has).

    ``artifact_format``: ``"v1"`` writes the historical one-directory-
    per-machine layout; ``"v2"`` writes one memory-mapped parameter pack
    per fleet chunk (``gordo_tpu/artifacts/``) — the writer stage emits
    ONE pack + index update per (signature, bucket) chunk instead of
    per-machine pickles, the registry records pack refs, and the server
    loads each pack with a single whole-pack device transfer.  Machines
    on the single-machine fallback path still write v1 dirs (the mixed
    layout every reader handles).  Default: ``GORDO_ARTIFACT_FORMAT``,
    else v2 (``GORDO_ARTIFACT_FORMAT=v1`` is the per-machine-dirs escape
    hatch).

    Streaming and memory-bounded: at most TWO chunks of machines
    (2 x the effective bucket size) have arrays resident — the one
    training on device and the one the loader pool is prefetching behind
    it.

    The chunks run as a three-stage pipeline — loader pool (prefetch) ∥
    device (this thread) ∥ background artifact-writer pool — so dataset
    loads and artifact writes both overlap device compute instead of
    sitting on the critical path.  Artifacts are written to a scratch dir
    and atomically renamed into place; completion records (registry,
    shard state) follow the rename, and the writer queue drains before
    the resumable exit-75 path transitions the shard state.  A chunk's
    artifact bytes and registry entries do not depend on what was
    dispatched before it was collected (tests/test_build_pipeline.py).

    ``max_bucket_size=None`` (the default) picks a per-signature chunk
    size: ``DEFAULT_MAX_BUCKET`` (512) for dense signatures,
    ``DEFAULT_MAX_BUCKET_LSTM`` for recurrent ones (see
    :func:`default_bucket_size`); an explicit value applies to every
    bucket.

    ``align_lengths``: truncate each fleet-bucketed machine's train rows
    DOWN to a multiple of this (dropping the oldest rows) before training.
    Exact CV parity holds per distinct row count, so a ragged project —
    the normal case once row filtering bites — pays one full XLA compile
    per distinct length (~14s each measured); alignment collapses
    ~``align_lengths`` lengths into one.  The cost is explicit and
    bounded: up to ``align_lengths - 1`` of the OLDEST rows per machine.
    Off (None) by default — results then match the single-machine build
    of the unmodified data exactly.

    ``pad_lengths``: the zero-data-loss alternative — pad each machine's
    rows UP to a multiple of this with weight-masked rows instead of
    truncating (``parallel.anomaly._padded_fleet_program``).  Every real
    row trains and a ragged bucket compiles one program per ALIGNED
    length, but CV fold boundaries and minibatch geometry derive from the
    padded length, so results for not-already-aligned machines differ
    slightly from their single-machine builds (see ``docs/fleet.md``).
    Mutually exclusive with ``align_lengths``.

    ``auto_pad`` (default on): when NEITHER ragged strategy is chosen and
    the config-level estimator (the one behind ``workflow plan``'s
    warning) predicts more than ``auto_pad_budget_seconds`` (default
    :data:`DEFAULT_AUTO_PAD_BUDGET_SECONDS`) of per-distinct-length
    compiles, enable ``pad_lengths=DEFAULT_AUTO_PAD_LENGTHS`` — loudly
    logged, recorded in ``result.auto_pad``, disabled with
    ``auto_pad=False`` (CLI ``--no-auto-pad``).  The selected value flows
    into cache keys exactly as an explicit ``pad_lengths`` would, so the
    decision is stable across re-runs of the same config set.

    Each fleet chunk loads through the build-ingest plane
    (:func:`gordo_tpu.ingest.plane.load_chunk`): one fingerprint-deduped,
    fleet-vectorized columnar assembly per chunk, written straight into
    the stacked ``(m_pad, n, tags)`` buffer the dispatch path adopts;
    datasets the columnar pass cannot express take its per-machine
    ``get_data()`` fallback, with the same arrays and metadata
    (tests/test_ingest.py).

    ``data_workers`` (default None → 2): loader-pool threads.  The
    plane's unit of work is a whole chunk, so the prefetch depth (the
    current chunk and the next) is all the parallelism the drive can
    use.  The resolved value lands in ``result.loader_workers``.

    ``shard``: a :class:`gordo_tpu.distributed.partition.ProcessShard` —
    build only this process's slice of ``machines`` (multi-host builds;
    artifact/metadata layout is identical to the single-host path).  The
    shard's state file tracks per-machine completion so a killed worker's
    shard is resumable.

    Returns a :class:`ProjectBuildResult` with one artifact dir per machine
    (identical layout to ``provide_saved_model``).
    """
    t_start = time.time()
    from gordo_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    if align_lengths is not None and align_lengths < 2:
        raise ValueError(
            f"align_lengths must be >= 2 (got {align_lengths}); it is a "
            "row-count multiple, and 0/1/negative would change cache "
            "identity without changing any training data"
        )
    if pad_lengths is not None and pad_lengths < 2:
        raise ValueError(
            f"pad_lengths must be >= 2 (got {pad_lengths}); it is a "
            "row-count multiple"
        )
    if align_lengths and pad_lengths:
        raise ValueError(
            "align_lengths (truncate down) and pad_lengths (pad up) are "
            "mutually exclusive — pick one ragged-fleet strategy"
        )
    machines = [_as_machine(m) for m in machines]
    result = ProjectBuildResult()
    artifact_fmt = artifacts.resolve_format(artifact_format)
    result.artifact_format = artifact_fmt
    if data_workers is None:
        # the ingest plane loads a whole chunk per task, so prefetch depth
        # (2: current + next) is all the parallelism the drive can use
        data_workers = 2
    result.loader_workers = int(data_workers)
    tracker = _LoadTracker()
    timeline = BuildTimeline(t_start)
    warm_resolved: Dict[str, Tuple[Any, Optional[float]]] = {}
    #: per-machine warm-start attestation, stamped into artifact metadata
    warm_info_by_name: Dict[str, Dict[str, Any]] = {}
    if warm_start:
        if artifact_fmt != "v2":
            raise ValueError(
                "warm_start=True needs the v2 pack layout (previous "
                "params resolve through the pack index) — rebuild with "
                "artifact_format='v2' or drop warm_start"
            )
        # a drifted machine's CONFIG is unchanged — its data drifted — so
        # the config-hash cache would skip the very rebuild we were asked
        # for; warm builds always retrain
        replace_cache = True
        warm_resolved = _resolve_warm_params(
            output_dir, [m.name for m in machines]
        )
        if not warm_resolved:
            logger.warning(
                "warm_start=True but no previous params resolved under "
                "%s — every machine rebuilds cold", output_dir,
            )
    # the auto-pad decision runs over the FULL machine list, before any
    # shard filtering: every process of a multi-host build (and a later
    # single-host re-run of the same config) must reach the same ragged
    # strategy, or cache keys would diverge across shards
    if auto_pad and align_lengths is None and pad_lengths is None:
        budget = (
            DEFAULT_AUTO_PAD_BUDGET_SECONDS
            if auto_pad_budget_seconds is None
            else auto_pad_budget_seconds
        )
        bill = estimate_ragged_compile_seconds(machines)
        if bill > budget:
            pad_lengths = DEFAULT_AUTO_PAD_LENGTHS
            result.auto_pad = pad_lengths
            logger.warning(
                "AUTO-PAD: configs predict ~%.0fs of per-distinct-length "
                "XLA compiles (> %.0fs budget) — enabling "
                "pad_lengths=%d (zero data loss; CV fold/batch geometry "
                "derives from the padded length, see docs/fleet.md). "
                "Pass --no-auto-pad (auto_pad=False) for exact-parity "
                "mode, or choose --align-lengths/--pad-lengths "
                "explicitly.",
                bill, budget, pad_lengths,
            )

    shard_state = None
    if shard is not None:
        # multi-host: restrict to this process's slice (order preserved);
        # the partition is machine-name based so the same project config
        # yields the same shard in every process
        wanted = set(shard.names)
        machines = [m for m in machines if m.name in wanted]
        result.shard = (shard.process_id, shard.num_processes)
        shard_state = getattr(shard, "state", None)
        if shard_state is not None:
            shard_state.start([m.name for m in machines])

    _done_lock = threading.Lock()

    def _done(name: str) -> None:
        """A machine needs no further work (artifact on disk or cached).
        Serialized: the writer pool and the drive loop both record."""
        if shard_state is not None:
            with _done_lock:
                shard_state.record(name)
    # alignment/padding changes what data trains (or how it is batched and
    # folded), so it must be part of the cache identity — otherwise an
    # aligned build silently reuses full-parity artifacts (and vice
    # versa).  Only FLEET-built machines align/pad; config-determined
    # singles train on full data and therefore key WITHOUT the component.
    key_extra = None
    if align_lengths:
        key_extra = {"align_lengths": align_lengths}
    elif pad_lengths:
        key_extra = {"pad_lengths": pad_lengths}

    # 1. Fleetability from CONFIG alone (no data loaded yet) + the
    #    config-hash cache check (reference: provide_saved_model) with the
    #    key matching what each machine's path will actually train on.
    #    When no alignment is in play the key can't depend on fleetability,
    #    so the (near-free) registry lookup runs FIRST and cache-hit
    #    machines skip model analysis entirely — a fully-cached project
    #    re-run must not instantiate 10k pipelines.
    def _analyze(m: Machine):
        cv_mode = m.evaluation.get("cv_mode", "full_build")
        widths = _config_widths(m.dataset)
        spec = None
        if cv_mode == "full_build" and widths is not None:
            try:
                spec = analyze_definition(
                    serializer.from_definition(dict(m.model))
                )
            except Exception:
                spec = None
        if spec is None and widths is None and cv_mode == "full_build":
            # this machine may be paying for its config: without an
            # explicit tag_list the stream can't bucket it pre-load, so it
            # loses the stacked-XLA path — say so
            logger.warning(
                "Machine %s has no tag_list/tags in its dataset config; "
                "building single (fleet bucketing needs config-derivable "
                "widths)", m.name,
            )
        return spec, widths

    def _lookup(key: str, m: Machine) -> bool:
        if model_register_dir and not replace_cache:
            cached = lookup_cached_artifact(model_register_dir, key, m.name)
            if cached is not None:
                result.artifacts[m.name] = cached
                result.cached.append(m.name)
                _BUILD_MACHINES_TOTAL.inc(1.0, "cached")
                _done(m.name)
                return True
        return False

    buckets: Dict[Tuple, List[Machine]] = {}
    singles: List[Machine] = []
    specs: Dict[Tuple, Any] = {}
    machine_keys: Dict[str, str] = {}
    demoted: set = set()
    for m in machines:
        if key_extra is None:
            key = calculate_model_key(m.name, m.model, m.dataset, m.metadata)
            machine_keys[m.name] = key
            if _lookup(key, m):
                continue
            spec, widths = _analyze(m)
        else:
            # alignment: try the aligned key FIRST — fleetability is a
            # deterministic function of the configs already hashed into
            # the key, so an aligned-key hit can only be a fleet-aligned
            # artifact, and cache-hit machines skip model analysis here
            # too.  Only on miss do we analyze and, for non-fleetable
            # machines, retry under the unaligned key they build with.
            key = calculate_model_key(
                m.name, m.model, m.dataset, m.metadata, extra=key_extra
            )
            machine_keys[m.name] = key
            if _lookup(key, m):
                continue
            spec, widths = _analyze(m)
            if spec is None:
                key = calculate_model_key(
                    m.name, m.model, m.dataset, m.metadata
                )
                machine_keys[m.name] = key
                if _lookup(key, m):
                    continue
        if spec is None:
            singles.append(m)
            continue
        bkey = (spec.signature, widths, str(m.evaluation.get("cv")))
        buckets.setdefault(bkey, []).append(m)
        specs[bkey] = spec

    # 3. Chunk plan across all buckets, then stream: load chunk k+1 in the
    #    pool while chunk k trains; free arrays as artifacts dump.
    chunks: List[Tuple[Tuple, List[Machine]]] = []
    for key, bucket in buckets.items():
        size = max_bucket_size or default_bucket_size(specs[key], key[1])
        for start in range(0, len(bucket), size):
            chunks.append((key, bucket[start : start + size]))

    def _load_chunk_ingest(i: int, chunk: List[Machine]) -> Dict[str, Any]:
        """One loader-pool task per CHUNK: the build-ingest plane's
        fingerprint-deduped, fleet-vectorized assembly
        (gordo_tpu/ingest/plane.py).  The capacity callable hands the
        dispatch plane's model-axis padding down so the stacked buffer
        the plane fills IS the ``(m_pad, n, tags)`` array the fleet
        program stages — no re-stack, no pad copy."""
        with timeline.phase("load", i, machines=len(chunk)):
            return ingest_plane.load_chunk(
                chunk,
                align_lengths=align_lengths,
                capacity=(lambda mm: _model_axis_pad(mm, mesh)),
                stats=result.ingest,
            )

    def _submit(pool, i: int):
        """Chunk ``i``'s load on the loader pool, each task in a copy of
        this context so its span keeps the build's trace id and parent."""
        chunk = chunks[i][1]
        return pool.submit(
            contextvars.copy_context().run, _load_chunk_ingest, i, chunk
        )

    def _collect(chunk: List[Machine], future) -> Dict[str, Tuple]:
        loaded: Dict[str, Tuple] = {}
        try:
            entries = future.result()
        except Exception as exc:  # plane crash: fail the whole chunk
            logger.exception("Ingest load failed for %d machine(s)",
                             len(chunk))
            for m in chunk:
                result.failed[m.name] = f"data: {exc}"
                _BUILD_MACHINES_TOTAL.inc(1.0, "failed")
            return loaded
        for m in chunk:
            entry = entries.get(m.name)
            if entry is None or isinstance(entry, Exception):
                exc = entry if entry is not None else RuntimeError(
                    "ingest plane produced no entry"
                )
                logger.error("Data load failed for %s: %s", m.name, exc)
                result.failed[m.name] = f"data: {exc}"
                _BUILD_MACHINES_TOTAL.inc(1.0, "failed")
                continue
            _DATA_LOAD_SECONDS.observe(entry[3])
            tracker.acquire()  # arrays are live from here until freed
            loaded[m.name] = entry
        return loaded

    def _free(loaded: Dict[str, Tuple], names: Sequence[str]) -> None:
        n = 0
        for name in list(names):
            if loaded.pop(name, None) is not None:
                n += 1
        if n:
            tracker.release(n)

    #: warmup-manifest entries, one per successfully fleet-built chunk —
    #: the (signature, bucket) record the serve plane pre-compiles from
    manifest_entries: List[Dict[str, Any]] = []

    def _record_manifest(key: Tuple, ok_chunk: List[Machine]) -> None:
        spec = specs[key]
        widths = key[1]
        manifest_entries.append(
            {
                "signature": hashlib.md5(
                    repr(spec.signature).encode()
                ).hexdigest()[:16],
                "machines": [m.name for m in ok_chunk],
                "n_machines": len(ok_chunk),
                "n_features": int(widths[0]),
                "n_outputs": int(widths[1]),
                "lookback": int(
                    getattr(spec.estimator_proto, "lookback_window", 1) or 1
                ),
                # sizes the streaming plane's carried ring
                # (offset + max(smooth_window, 1) rows)
                "smooth_window": int(
                    getattr(spec.detector_proto, "window", 0) or 0
                ),
            }
        )

    def _note_fallback(name: str, reason: str) -> None:
        """A warm_start machine rebuilding cold: attest why (result +
        metadata) — the bench parity gate accepts an attested fallback."""
        result.warm_fallbacks[name] = reason
        warm_info_by_name[name] = {"warm": False, "fallback": reason}
        logger.warning("warm-start fallback for %s: %s", name, reason)

    def _dispatch_chunk(i, spec_obj, cv, ok_chunk, loaded, warm_list=None):
        """Launch chunk ``i``'s fleet program(s) and return the pending
        handle without blocking.  With telemetry on the builder gets the
        chunk's clock, so its phase spans land on the chunk's timeline
        row and each program's end is stamped by a watcher thread; off,
        no thread starts.  Part of the lint-enforced D2H-free dispatch
        window."""
        builder = FleetDiffBuilder(
            spec_obj, cv=cv, mesh=mesh, pad_lengths=pad_lengths,
            clock=timeline.clock(i) if telemetry.enabled() else None,
        )
        return builder.dispatch(
            [loaded[m.name][0] for m in ok_chunk],
            [loaded[m.name][1] for m in ok_chunk],
            warm_params=warm_list,
        )

    def _collect_chunk(i, pending):
        """Blocking half: fetch + assemble chunk ``i``'s dispatched
        programs.  They have ended whether the collect returns or raises,
        so their ready stamps are in when this is left."""
        try:
            detectors = pending.collect()
        finally:
            if not pending.settle():
                logger.warning("chunk %d: a program's ready stamp is "
                               "missing from the timeline", i)
        result.devices |= pending.devices
        return detectors

    def _train_chunk(i, spec_obj, cv, ok_chunk, loaded, warm_list=None):
        return _collect_chunk(
            i, _dispatch_chunk(i, spec_obj, cv, ok_chunk, loaded, warm_list)
        )

    def _build_chunk_warm(i, spec, cv, ok_chunk, loaded):
        """One chunk in warm_start mode: machines with resolved previous
        params run the warm program under a reduced-epoch config, the
        parity gate demotes stragglers, and everything else (plus gate
        failures) rebuilds cold — all within the chunk, so the caller
        still sees detectors in ``ok_chunk`` order."""
        warm_ms = [m for m in ok_chunk if m.name in warm_resolved]
        cold_names = set()
        for m in ok_chunk:
            if m.name not in warm_resolved:
                _note_fallback(m.name, "no-previous-params")
                cold_names.add(m.name)
        dets: Dict[str, Any] = {}
        if warm_ms:
            parity_factor = _refresh_parity_factor()
            warm_cfg = dataclasses.replace(
                spec.train_cfg, epochs=_warm_epochs(spec.train_cfg)
            )
            warm_spec = dataclasses.replace(spec, train_cfg=warm_cfg)
            try:
                warm_dets = _train_chunk(
                    i, warm_spec, cv, warm_ms, loaded,
                    warm_list=[warm_resolved[m.name][0] for m in warm_ms],
                )
            except Exception:
                logger.exception(
                    "warm-start chunk build failed; rebuilding %d "
                    "machine(s) cold", len(warm_ms),
                )
                for m in warm_ms:
                    _note_fallback(m.name, "warm-build-failed")
                    cold_names.add(m.name)
                warm_ms, warm_dets = [], []
            for m, det in zip(warm_ms, warm_dets):
                prev_loss = warm_resolved[m.name][1]
                hist = np.asarray(
                    getattr(_detector_estimator(det), "history_", ())
                ).ravel()
                warm_loss = float(hist[-1]) if hist.size else float("nan")
                passed = np.isfinite(warm_loss) and (
                    prev_loss is None
                    or warm_loss
                    <= parity_factor * max(prev_loss, 1e-12) + 1e-12
                )
                if passed:
                    dets[m.name] = det
                    result.warm_started.append(m.name)
                    warm_info_by_name[m.name] = {
                        "warm": True,
                        "epochs": int(warm_cfg.epochs),
                        "final_loss": warm_loss,
                        "previous_final_loss": prev_loss,
                    }
                else:
                    _note_fallback(
                        m.name,
                        f"parity: warm final loss {warm_loss:.6g} vs "
                        f"previous {prev_loss} "
                        f"(factor {parity_factor:g})",
                    )
                    cold_names.add(m.name)
        cold_ms = [m for m in ok_chunk if m.name in cold_names]
        if cold_ms:
            for m, det in zip(cold_ms, _train_chunk(i, spec, cv, cold_ms,
                                                    loaded)):
                dets[m.name] = det
        return [dets[m.name] for m in ok_chunk]

    def _demote_chunk(
        ok_chunk: List[Machine], loaded: Dict[str, Tuple], stage: str,
        exc: Exception,
    ) -> None:
        """A chunk's fleet program failed at ``stage`` (trace, compile,
        run or fetch): every machine in it falls to the single-machine
        builder and frees its arrays.  The build carries on; the
        summary's ``demoted`` carries the reason, because on an
        accelerator this turns one wide program into hundreds of small
        ones."""
        logger.exception("Fleet %s failed; falling back to singles", stage)
        first_line = (str(exc).splitlines() or [""])[0][:200]
        reason = f"{stage}: {type(exc).__name__}: {first_line}"
        for m in ok_chunk:
            _demote_to_single(
                m, singles, machine_keys, key_extra, demoted, result, reason
            )
        _free(loaded, [m.name for m in ok_chunk])

    def _dispatch_bucket(
        i: int, key: Tuple, chunk: List[Machine], loaded: Dict[str, Tuple]
    ) -> Optional[_PendingChunk]:
        """Width-validate + DISPATCH one chunk's fleet program(s); returns
        a pending record (or None when every machine demoted).  Cold
        chunks return with device futures only — the blocking fetch lives
        in ``_finish_bucket`` — so the caller can dispatch chunk k+1
        before finishing chunk k.  Warm-start chunks run synchronously
        here (see :class:`_PendingChunk`).  Lint-enforced D2H-free zone
        alongside ``_drive_pipeline`` (scripts/lint.py)."""
        spec = specs[key]
        widths = key[1]
        # config said these widths; data disagreeing (exotic provider)
        # reroutes the machine through the single builder
        ok_chunk = []
        for m in chunk:
            if m.name not in loaded:
                continue
            X, y = loaded[m.name][0], loaded[m.name][1]
            if (X.shape[1], y.shape[1]) != widths:
                logger.warning(
                    "Machine %s loaded widths %s != config %s; "
                    "building single", m.name, (X.shape[1], y.shape[1]),
                    widths,
                )
                _demote_to_single(
                    m, singles, machine_keys, key_extra, demoted, result,
                    "widths: loaded data disagrees with the config",
                )
                _free(loaded, [m.name])
            else:
                ok_chunk.append(m)
        if not ok_chunk:
            return None
        cv = ok_chunk[0].evaluation.get("cv")
        t0 = time.time()
        if warm_start:
            try:
                detectors = _build_chunk_warm(i, spec, cv, ok_chunk, loaded)
            except Exception as exc:
                _demote_chunk(ok_chunk, loaded, "warm build", exc)
                return None
            return _PendingChunk(
                index=i, key=key, ok_chunk=ok_chunk, loaded=loaded, t0=t0,
                detectors=detectors,
            )
        try:
            pending = _dispatch_chunk(i, spec, cv, ok_chunk, loaded)
        except Exception as exc:
            # host-side failure (trace/compile/stacking) — async XLA
            # failures surface at collect and demote in _finish_bucket
            _demote_chunk(ok_chunk, loaded, "dispatch", exc)
            return None
        # width checks, group context, staging and enqueue together: not
        # one span's phase, and the start of the chunk's fleet seconds
        _PIPE_STAGE_SECONDS.observe(time.time() - t0, "dispatch")
        return _PendingChunk(
            index=i, key=key, ok_chunk=ok_chunk, loaded=loaded, t0=t0,
            pending=pending,
        )

    def _finish_bucket(rec: _PendingChunk):
        """Collect one dispatched chunk: blocking D2H fetch + per-machine
        assembly.  An async failure from dispatch surfaces here and
        demotes the chunk to singles.  Returns ``(ok_chunk, detectors,
        fleet_seconds)`` or None."""
        ok_chunk, loaded = rec.ok_chunk, rec.loaded
        detectors = rec.detectors
        try:
            if rec.pending is not None:
                detectors = _collect_chunk(rec.index, rec.pending)
        except Exception as exc:
            _demote_chunk(ok_chunk, loaded, "collect", exc)
            return None
        finally:
            # once per chunk, whatever its groups and however it ended:
            # the per-group phases summed, and the device side of its row
            timeline.observe(
                rec.index, "stage", "enqueue", "fetch", "assemble"
            )
            timeline.chunk_collected(rec.index)
        # dispatch to collected: the chunk's fleet seconds, which artifact
        # metadata records; on the chip it spans the next program too
        fleet_seconds = time.time() - rec.t0
        _BUILD_BUCKET_SECONDS.observe(fleet_seconds)
        _PIPE_STAGE_SECONDS.observe(fleet_seconds, "device")
        return ok_chunk, detectors, fleet_seconds

    def _finish_chunk(rec: _PendingChunk, writer: _ArtifactWriter):
        """Finish one chunk end-to-end: collect, manifest, and hand the
        artifacts to the writer pool."""
        out = _finish_bucket(rec)
        if out is None:
            return
        ok_chunk, detectors, fleet_seconds = out
        with timeline.phase("handoff", rec.index):
            _hand_off(rec, ok_chunk, detectors, fleet_seconds, writer)

    def _hand_off(rec: _PendingChunk, ok_chunk, detectors, fleet_seconds,
                  writer: _ArtifactWriter) -> None:
        """What follows a chunk's collect on the drive thread: manifest
        row, fleet-health baselines, metadata, and the writes handed to
        the writer pool."""
        key, loaded = rec.key, rec.loaded
        _record_manifest(key, ok_chunk)
        _PIPE_CHUNKS_TOTAL.inc(1.0, "pipelined")
        payload = _chunk_payload(ok_chunk, detectors, fleet_seconds,
                                 loaded, rec.pending)
        if artifact_fmt == "v2":
            # v2: the chunk IS the write unit — one pack per chunk
            # rides the writer queue as a single item
            writer.submit([payload], rec.index)
            return
        names, dets, metadatas, per_machine, chunk_definition = payload
        writer.submit(  # v1: an artifact a machine, one handoff per chunk
            [(name, det, metadata, per_machine, chunk_definition)
             for name, det, metadata in zip(names, dets, metadatas)],
            rec.index,
        )

    def _drive_pipeline(pool, writer: _ArtifactWriter) -> None:
        """The pipelined drive loop: loader pool (stage A, prefetching) ∥
        device stage B split into DISPATCH and COLLECT halves on this
        thread ∥ artifact-writer pool (stage C).

        Stage B's split is the r23 overlap: chunk k+1's program
        dispatches (async H2D staging through the placement seam + jax
        async dispatch) BEFORE chunk k's blocking fetch/assembly runs, so
        the host-side collect work of chunk k hides behind chunk k+1's
        device compute instead of starving the device between chunks.
        Loads for chunk k+2 submit only after chunk k's arrays free,
        preserving the 2-chunk peak_loaded bound.  Metadata assembles at
        enqueue time so the chunk's arrays free BEFORE the write queues
        (the bound holds regardless of writer backlog).  This function is
        a D2H-free zone — ``scripts/lint.py`` rejects blocking
        device→host calls (jax.device_get / np.asarray / to_host /
        block_until_ready) in its body; the D2H lives in
        ``_finish_bucket`` via ``PendingFleetBuild.collect``."""
        futures = _submit(pool, 0)
        prev: Optional[_PendingChunk] = None
        for i, (key, chunk) in enumerate(chunks):
            with timeline.phase("load_wait", i) as waited:
                loaded = _collect(chunk, futures)
            _PIPE_STALL_SECONDS.inc(waited.get("seconds", 0.0), "load")
            rec = _dispatch_bucket(i, key, chunk, loaded)
            if prev is not None:
                _finish_chunk(prev, writer)  # overlaps chunk i's compute
            prev = rec
            futures = (
                _submit(pool, i + 1) if i + 1 < len(chunks) else None
            )
        if prev is not None:
            _finish_chunk(prev, writer)

    # one per build, not per output dir: the processes of a multi-host
    # build share output_dir, and the first to finish removes its scratch
    # while a peer's writer pool is still renaming out of its own
    tmp_root = os.path.join(output_dir, f".gordo-tmp-{uuid.uuid4().hex[:8]}")
    writer: Optional[_ArtifactWriter] = None

    def _write_one(name: str, det, metadata: Dict[str, Any],
                   per_machine: float,
                   definition: Optional[str] = None) -> None:
        """Writer-pool task: atomic artifact write + completion records.
        Failures fail ONE machine (recorded loudly), never the drain."""
        try:
            dest = os.path.join(output_dir, name)
            _write_artifact(
                det, metadata, dest, model_register_dir,
                metadata.get("cache_key"), tmp_root=tmp_root,
                definition=definition,
            )
        except Exception as exc:
            logger.exception("Artifact write failed for %s", name)
            result.failed[name] = f"write: {exc}"
            _BUILD_MACHINES_TOTAL.inc(1.0, "failed")
            return
        result.artifacts[name] = dest
        result.fleet_built.append(name)
        _BUILD_MACHINES_TOTAL.inc(1.0, "fleet")
        _BUILD_MACHINE_SECONDS.observe(per_machine, "fleet")
        _done(name)

    def _chunk_payload(ok_chunk, detectors, fleet_seconds, loaded,
                       pending=None) -> Tuple:
        """Assemble a chunk's write payload, either format's (metadata
        closes over the training arrays, so they free HERE — at enqueue —
        keeping the 2-chunk peak_loaded bound independent of writer
        backlog).  Machines in a chunk share ONE model config, so their
        definition.yaml renders once per chunk, not per machine.
        Fleet-health baselines sketch FIRST, while the chunk's training
        arrays are still resident — one stacked scoring dispatch for the
        whole chunk (telemetry.fleet_health.training_baselines), fed the
        collect side's stacked arrays so nothing restacks."""
        per_machine = fleet_seconds / len(ok_chunk)
        chunk_definition = serializer.render_definition(detectors[0])
        baselines = _chunk_baselines(ok_chunk, detectors, loaded, pending)
        metadatas = []
        for m, det in zip(ok_chunk, detectors):
            metadatas.append(_machine_metadata(
                m, det, loaded[m.name], per_machine, fleet=True,
                align_lengths=align_lengths, pad_lengths=pad_lengths,
                cache_key=machine_keys[m.name],
                baseline=baselines.get(m.name),
                warm_info=warm_info_by_name.get(m.name),
            ))
            _free(loaded, [m.name])
        names = [m.name for m in ok_chunk]
        return names, list(detectors), metadatas, per_machine, chunk_definition

    def _record_packed(names, per_machine) -> None:
        """Bookkeeping shared by the pack and delta publish paths."""
        for name in names:
            result.artifacts[name] = artifacts.machine_ref(output_dir, name)
            result.fleet_built.append(name)
            _BUILD_MACHINES_TOTAL.inc(1.0, "fleet")
            _BUILD_MACHINE_SECONDS.observe(per_machine, "fleet")
            _register(
                artifacts.machine_ref(output_dir, name),
                model_register_dir, machine_keys.get(name),
            )
            _done(name)

    def _write_chunk_delta(names, detectors, metadatas, per_machine,
                           definition: Optional[str] = None) -> None:
        """Incremental publish (warm_start builds): machines the pack
        index already knows rewrite their slots in place via
        ``delta_write`` — whose single atomic index swap stamps its own
        generation, so live servers delta-reload exactly the touched
        packs — and machines the index doesn't know yet land as a fresh
        pack row published by the build's final stamp.  A structural
        mismatch (leaf signature changed since the previous generation)
        demotes the whole chunk to a fresh pack; any other write failure
        fails THESE machines loudly and leaves the store on its previous
        healthy generation — no partial-delta limbo, the next refresh
        cycle retries."""
        store = artifacts.open_store(output_dir)
        known = set(store.names()) if store is not None else set()
        delta_names = [n for n in names if n in known]
        fresh_names = [n for n in names if n not in known]
        by_name = dict(zip(names, detectors))
        meta_by_name = dict(zip(names, metadatas))
        try:
            if delta_names:
                try:
                    artifacts.delta_write(
                        output_dir,
                        {n: by_name[n] for n in delta_names},
                        metadatas={n: meta_by_name[n] for n in delta_names},
                    )
                except artifacts.PackError:
                    # structural change since the previous generation —
                    # a delta can't express it; write a fresh pack row
                    logger.warning(
                        "delta publish: leaf signature changed for chunk "
                        "%s...; writing a fresh pack instead", names[:3],
                    )
                    fresh_names = list(names)
                    delta_names = []
            if fresh_names:
                artifacts.write_pack(
                    output_dir, fresh_names,
                    [by_name[n] for n in fresh_names],
                    [meta_by_name[n] for n in fresh_names],
                    definition=definition,
                    cache_keys={
                        n: machine_keys[n]
                        for n in fresh_names if n in machine_keys
                    },
                )
        except Exception as exc:
            logger.exception(
                "Incremental publish failed for chunk %s...", names[:3],
            )
            for name in names:
                result.failed[name] = f"write: {exc}"
                _BUILD_MACHINES_TOTAL.inc(1.0, "failed")
            return
        _record_packed(names, per_machine)

    def _write_chunk_pack(names, detectors, metadatas, per_machine,
                          definition: Optional[str] = None) -> None:
        """v2 writer task: ONE pack + index update per fleet chunk.  A
        pack-level failure falls back to per-machine v1 artifacts — the
        chunk must not lose machines to a packing edge case."""
        try:
            artifacts.write_pack(
                output_dir, names, detectors, metadatas,
                definition=definition,
                cache_keys={
                    n: machine_keys[n] for n in names if n in machine_keys
                },
            )
        except Exception:
            logger.exception(
                "Pack write failed for chunk %s...; falling back to "
                "per-machine artifacts", names[:3],
            )
            for name, det, metadata in zip(names, detectors, metadatas):
                _write_one(name, det, metadata, per_machine, definition)
            return
        _record_packed(names, per_machine)

    # warm_start publishes incrementally (delta_write for known machines)
    # so live servers reload ONLY the touched packs; full builds write
    # whole chunk packs as always
    _write_chunk = _write_chunk_delta if warm_start else _write_chunk_pack

    with ThreadPoolExecutor(max_workers=data_workers) as pool:
        if chunks:
            writer = _ArtifactWriter(
                _write_chunk if artifact_fmt == "v2" else _write_one,
                timeline,
            )
            try:
                _drive_pipeline(pool, writer)
            except BaseException:
                writer.drain()
                raise

    # 4. Single-machine fallback (non-fleetable configs) — one at a time,
    #    each build loading and freeing its own data.
    if singles and (align_lengths or pad_lengths):
        which = (
            f"align_lengths={align_lengths}" if align_lengths
            else f"pad_lengths={pad_lengths}"
        )
        logger.warning(
            "%s does not apply to the %d machine(s) building "
            "through the single-machine path (%s%s): they train on their "
            "full unmodified data",
            which, len(singles),
            ", ".join(m.name for m in singles[:5]),
            "..." if len(singles) > 5 else "",
        )
    for m in singles:
        # a runtime-demoted machine's key was rewritten to the unaligned
        # form; a prior run's single artifact may already satisfy it
        if m.name in demoted and _lookup(machine_keys[m.name], m):
            continue
        if warm_start and m.name not in result.warm_fallbacks:
            # single-path builds have no fleet program to warm-start
            _note_fallback(m.name, "single-path")
        t_single = time.time()
        try:
            model, metadata = build_model(
                m.name, m.model, m.dataset, m.metadata, m.evaluation
            )
        except Exception as exc:
            logger.exception("Single build failed for %s", m.name)
            result.failed[m.name] = f"build: {exc}"
            _BUILD_MACHINES_TOTAL.inc(1.0, "failed")
            continue
        metadata["cache_key"] = machine_keys[m.name]
        dest = os.path.join(output_dir, m.name)
        serializer.dump(model, dest, metadata=metadata)
        _register(dest, model_register_dir, machine_keys[m.name])
        result.artifacts[m.name] = dest
        result.single_built.append(m.name)
        _BUILD_MACHINES_TOTAL.inc(1.0, "single")
        _BUILD_MACHINE_SECONDS.observe(time.time() - t_single, "single")
        _done(m.name)

    if writer is not None:
        # exit-75 / resumable contract: every queued artifact is fully on
        # disk (or its failure recorded) BEFORE the shard state
        # transitions and before this function returns — the singles pass
        # above ran concurrently with the tail of the write queue
        writer.drain()
        shutil.rmtree(tmp_root, ignore_errors=True)

    if artifact_fmt == "v2":
        # ONE atomic generation flip publishes every pending pack row
        # this build wrote — the only reload signal serving replicas act
        # on, so a mid-build index is never mistaken for a new fleet.
        # No-op (returns the current id) when the run was fully cached.
        try:
            generation = artifacts.stamp_generation(output_dir)
            result.generation = generation
            if generation:
                logger.info(
                    "published artifact generation %d", generation
                )
        except Exception:
            logger.exception("generation stamp failed — serving "
                             "replicas will not hot-reload this build")

    if shard_state is not None:
        if result.failed:
            shard_state.mark_resumable(
                f"{len(result.failed)} machine(s) failed"
            )
        else:
            shard_state.finish()
    result.seconds = time.time() - t_start
    result.peak_loaded = tracker.peak
    result.device_idle_seconds = timeline.occupancy.idle_seconds
    result.timeline = timeline.rows()
    _write_telemetry_snapshot(output_dir, result.shard, timeline)
    try:
        # the (signature, bucket) set this build materialized — what the
        # server (or `gordo warmup`) pre-compiles before going ready.  A
        # fully-cached re-run records nothing and keeps the existing
        # manifest; a partial rebuild merges into it, pruned against the
        # machines that actually exist on disk so a shrunk bucket can't
        # leave stale (signature, bucket) rows behind.
        from gordo_tpu.compile import write_warmup_manifest
        from gordo_tpu.serve.precision import serve_dtype

        write_warmup_manifest(
            output_dir, manifest_entries, shard=result.shard,
            live_machines=(
                artifacts.machines_on_disk(output_dir)
                | set(result.artifacts)
            ),
            # resolved HERE, at build time: the manifest carries the
            # precision this deployment is configured for, so a server
            # started without GORDO_SERVE_DTYPE set still warms and
            # serves what the build intended
            serve_dtype=serve_dtype(),
            # the device mesh the fleet programs compiled over — lets
            # the serve plane (and `gordo mesh info`) see what placement
            # this build warmed for
            mesh=mesh,
        )
    except Exception:  # the manifest is a hint, never a build failure
        logger.exception("warmup manifest write failed")
    return result


def _write_telemetry_snapshot(
    output_dir: str, shard: Optional[Tuple[int, int]],
    timeline: BuildTimeline,
) -> None:
    """Shard-local metric snapshot under ``<output_dir>/.gordo-telemetry/``
    — one file per process of a (multi-host) build, merged later by
    ``gordo telemetry dump --dir`` / watchman — and beside it the build's
    chunk timeline (``timeline-*.json``, which the merge passes over).
    Process-id-keyed filenames mean a re-run of the same shard overwrites
    its own files and never a peer's."""
    if not telemetry.enabled():
        return
    pid, n = shard or (0, 1)
    directory = os.path.join(output_dir, telemetry.SNAPSHOT_DIR)
    path = os.path.join(directory, f"shard-{pid:03d}-of-{n:03d}.json")
    try:
        telemetry.REGISTRY.write_snapshot(path)
        path = os.path.join(directory, f"timeline-{pid:03d}-of-{n:03d}.json")
        timeline.write(path)
    except Exception:  # telemetry must never fail a build
        logger.exception("telemetry snapshot write failed: %s", path)


def _chunk_baselines(ok_chunk, detectors, loaded, pending=None) -> Dict[str, Any]:
    """Training-time residual sketches for a just-trained chunk — ONE
    stacked scoring dispatch over the still-resident training arrays
    (the device-stage cost rides the same thread the chunk trained on,
    like training itself).  ``pending`` (the chunk's collected
    :class:`PendingFleetBuild`, when it built async) re-exposes the
    fetched stacked arrays so the scorer skips its leaf-by-leaf restack
    of the per-machine views.  ``GORDO_FLEET_BASELINE=off`` skips it."""
    from gordo_tpu.telemetry import fleet_health

    hint = (
        pending.prestacked([m.name for m in ok_chunk])
        if pending is not None else None
    )
    return fleet_health.training_baselines(
        {m.name: det for m, det in zip(ok_chunk, detectors)},
        {m.name: loaded[m.name][0] for m in ok_chunk if m.name in loaded},
        prestacked_hint=hint,
    )


def _machine_metadata(
    m: Machine,
    detector,
    loaded_entry: Tuple,
    fit_seconds: float,
    fleet: bool,
    align_lengths: Optional[int] = None,
    pad_lengths: Optional[int] = None,
    cache_key: Optional[str] = None,
    baseline: Optional[Dict[str, Any]] = None,
    warm_info: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Assemble one machine's artifact metadata — everything except the
    disk writes, so the pipelined path can free the training arrays at
    enqueue time and hand the writer pool a closed payload."""
    X, _, dataset_meta, query_seconds = loaded_entry
    metadata = assemble_metadata(
        name=m.name,
        model=detector,
        model_config=m.model,
        data_config=m.dataset,
        dataset_metadata=dataset_meta,
        metadata=m.metadata,
        data_query_duration=query_seconds,
        cv_duration=fit_seconds,  # fleet: CV+fit are one fused program
        fit_duration=fit_seconds,
        cv_meta=getattr(detector, "cv_metadata_", {}),
    )
    metadata["model"]["fleet_built"] = fleet
    if align_lengths:
        # a truncated artifact must be distinguishable from a full-parity
        # one: record the alignment and the row count actually trained on
        metadata["model"]["align_lengths"] = int(align_lengths)
        metadata["model"]["rows_trained"] = int(X.shape[0])
    if pad_lengths and getattr(detector, "pad_built_", False):
        # padded-mode artifact: every real row trained, but fold/batch
        # geometry came from the padded group length.  Machines the
        # builder demoted to the exact path (too short / exotic splitter)
        # do NOT get the stamp — their artifacts are full-parity builds.
        metadata["model"]["pad_lengths"] = int(pad_lengths)
        metadata["model"]["rows_trained"] = int(X.shape[0])
    if warm_info is not None:
        # incremental-refresh attestation: either the warm-start lineage
        # (epochs trained, previous/final loss) or the cold-fallback
        # reason — auditable per machine, per generation
        metadata["model"]["warm_start"] = dict(warm_info)
    # the artifact stamps its own cache identity so a later lookup can
    # detect that this dir was overwritten by a different build
    if cache_key is not None:
        metadata["cache_key"] = cache_key
    if baseline is not None:
        # the training-time residual distribution (fleet-health sketch):
        # the serve plane loads it as the drift-comparison baseline
        metadata["fleet-health"] = {"version": 1, "baseline": baseline}
    return metadata


def _write_artifact(
    detector,
    metadata: Dict[str, Any],
    dest: str,
    model_register_dir: Optional[str],
    cache_key: Optional[str],
    tmp_root: str,
    definition: Optional[str] = None,
) -> None:
    """Serialize one artifact to ``dest`` and register it.

    The artifact dumps into a scratch dir under ``tmp_root`` and renames
    into place — the rename is atomic, so a kill mid-write leaves either
    no dir at ``dest`` or a complete artifact, never a partial one.  The
    registry entry follows the rename.  ``definition``: pre-rendered
    definition.yaml text (chunk-shared; see the drive loop).
    """
    tmp = os.path.join(
        tmp_root, f"{os.path.basename(dest)}.{uuid.uuid4().hex[:8]}"
    )
    serializer.dump(detector, tmp, metadata=metadata,
                    definition=definition)
    if os.path.isdir(dest):  # rebuild over an existing artifact dir
        shutil.rmtree(dest)
    os.replace(tmp, dest)
    _register(dest, model_register_dir, cache_key)


def _register(
    dest: str, model_register_dir: Optional[str], key: Optional[str]
) -> None:
    """Registry write under the key computed ONCE in step 1 — the stamp in
    metadata, the registry entry, and the next run's lookup must all agree
    or the overwrite-detection breaks.  v2 pack refs record verbatim (the
    pack index, not a per-machine path, is the unit the registry points
    at); v1 artifact dirs record as absolute paths, as always."""
    if model_register_dir and key:
        value = dest if artifacts.is_pack_ref(dest) else os.path.abspath(dest)
        disk_registry.write_key(model_register_dir, key, value)
