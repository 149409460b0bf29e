"""Async ML server.

Reference equivalent: ``gordo_components/server/server.py`` (Flask
``build_app``/``run_server`` behind gunicorn) and
``server/views/base.py``/``views/anomaly.py`` (the
``/gordo/v0/<project>/<machine>/...`` routes, payload validation against
model metadata, download-model).

Differences by design:
- aiohttp event loop instead of gunicorn worker forks: device dispatches run
  in a thread-pool executor so the loop keeps accepting while XLA computes.
- one process serves MANY machines (``ModelCollection``) — the reference
  runs one pod per machine; the per-machine route shape is preserved so
  clients cannot tell the difference.
- scoring goes through :class:`gordo_tpu.serve.scorer.CompiledScorer` — one
  fused jitted program per shape bucket instead of sklearn-transform hops.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np
import pandas as pd
from aiohttp import web

import gordo_tpu
from gordo_tpu import artifacts, faults, serializer, telemetry
from gordo_tpu import compile as compile_plane
from gordo_tpu.telemetry.fleet_health import drift_top_k
from gordo_tpu.serve import codec
from gordo_tpu.serve import coalesce as coalesce_mod
from gordo_tpu.serve import stream as stream_mod
from gordo_tpu.serve.scorer import CompiledScorer

logger = logging.getLogger(__name__)

API_PREFIX = "/gordo/v0"

# -- telemetry instruments (see docs/observability.md for the catalog) ------
_REQUEST_SECONDS = telemetry.histogram(
    "gordo_server_request_seconds",
    "End-to-end request handling time by route pattern and response codec",
    labels=("route", "codec"),
)
_REQUESTS_TOTAL = telemetry.counter(
    "gordo_server_requests_total",
    "Requests served by route pattern and HTTP status",
    labels=("route", "status"),
)
_MACHINES_GAUGE = telemetry.gauge(
    "gordo_server_machines",
    "Machines currently loaded in this server's collection",
)
_SHED_TOTAL = telemetry.counter(
    "gordo_server_shed_total",
    "Requests shed with 429 + Retry-After (coalescer stand-down escalated)",
)
_SHARD_INDEX_GAUGE = telemetry.gauge(
    "gordo_server_shard_index",
    "This replica's shard index (absent when serving unsharded)",
)
_SHARD_COUNT_GAUGE = telemetry.gauge(
    "gordo_server_shard_count",
    "Shard count of the serving tier this replica belongs to",
)
_FLEET_GENERATION_GAUGE = telemetry.gauge(
    "gordo_fleet_generation",
    "Artifact generation this replica is serving (set at scrape time)",
)
_RELOADS_TOTAL = telemetry.counter(
    "gordo_server_reloads_total",
    "Completed artifact reloads by kind (delta = O(changed-machines) "
    "restack; full = complete scorer rebuild)",
    labels=("kind",),
)
_QUARANTINED_GAUGE = telemetry.gauge(
    "gordo_machines_quarantined",
    "Machines this replica refuses with 503 because their pack failed "
    "validation (heals when a good generation flips)",
)

#: Prometheus exposition content type (text format 0.0.4)
METRICS_CONTENT_TYPE = "text/plain"


def _codec_label(content_type: Optional[str]) -> str:
    if content_type == codec.COLUMNAR_CONTENT_TYPE:
        return "columnar"
    if content_type == codec.MSGPACK_CONTENT_TYPE:
        return "msgpack"
    if content_type == "application/json":
        return "json"
    return "other"


@web.middleware
async def telemetry_middleware(request: web.Request, handler):
    """Per-request observability: a trace id from the ``X-Gordo-Trace-Id``
    header (minted when absent) binds to the handler's context and echoes
    back on the response; every request lands in the per-route/per-codec
    request histogram and the route/status counter.  Route label is the
    matched ROUTE PATTERN (``{machine}`` stays a placeholder), so
    cardinality is bounded by the route table, not the fleet."""
    trace_id = request.headers.get(telemetry.TRACE_HEADER) or (
        telemetry.new_trace_id()
    )
    telemetry.set_trace_id(trace_id)
    t0 = time.perf_counter()
    status = 500
    codec_label = "other"
    try:
        resp = await handler(request)
        status = resp.status
        codec_label = _codec_label(resp.content_type)
        resp.headers[telemetry.TRACE_HEADER] = trace_id
        return resp
    except web.HTTPException as exc:
        status = exc.status
        exc.headers[telemetry.TRACE_HEADER] = trace_id
        raise
    finally:
        resource = request.match_info.route.resource
        route = resource.canonical if resource is not None else "unmatched"
        _REQUEST_SECONDS.observe(
            time.perf_counter() - t0, route, codec_label
        )
        _REQUESTS_TOTAL.inc(1.0, route, str(status))

#: per-request absolute monotonic deadline (set by deadline_middleware
#: from the propagated X-Gordo-Deadline-Ms budget; absent = no deadline)
DEADLINE_KEY = "gordo-deadline"


def _deadline_expired_response(detail: str) -> web.Response:
    return web.json_response(
        {"error": f"deadline expired: {detail}"}, status=504
    )


@web.middleware
async def deadline_middleware(request: web.Request, handler):
    """Deadline propagation ingress + the ``server.request`` fault seam.

    The ``X-Gordo-Deadline-Ms`` header carries the client's REMAINING
    budget in milliseconds (wall clocks don't cross machines — only
    durations do); it converts here to an absolute ``time.monotonic()``
    deadline stored on the request for the handlers and the coalescer.
    A request arriving already expired is refused with 504 before any
    body parse or dispatch — the client upstream has given up, so every
    cycle spent on it is pure waste."""
    if faults.enabled():
        try:
            faults.check("server.request", path=request.path)
        except faults.InjectedFault as exc:
            if exc.mode == "reset":
                # drop the connection mid-request, as a crashing worker
                # would — the client sees a reset, not a status line
                if request.transport is not None:
                    request.transport.close()
                raise web.HTTPInternalServerError(text=str(exc))
            status = 503 if exc.mode == "http_503" else 500
            return web.json_response({"error": str(exc)}, status=status)
    raw = request.headers.get(telemetry.DEADLINE_HEADER)
    if raw is not None:
        try:
            ms = int(raw)
        except ValueError:
            ms = None
        if ms is not None:
            if ms <= 0:
                return _deadline_expired_response(
                    "budget exhausted on arrival"
                )
            request[DEADLINE_KEY] = time.monotonic() + ms / 1000.0
    return await handler(request)


COLLECTION_KEY: "web.AppKey[ModelCollection]" = web.AppKey(
    "collection", object
)
COALESCER_KEY: "web.AppKey[object]" = web.AppKey("coalescer", object)
WARMUP_TASK_KEY: "web.AppKey[object]" = web.AppKey("warmup_task", object)
STREAM_HUB_KEY: "web.AppKey[object]" = web.AppKey("stream_hub", object)


class ModelEntry:
    """One served machine, loaded through the artifact plane — a v1
    per-machine directory or a slot of a v2 pack, behind one surface.

    ``serve_dtype``: the collection's serving precision, threaded into
    this entry's scorer (``None`` resolves ``GORDO_SERVE_DTYPE`` per
    call — the bench/test compatibility path)."""

    def __init__(
        self, name: str, directory: str, serve_dtype: Optional[str] = None
    ):
        # v1-dir compatibility constructor (tests/bench build entries
        # straight from a dumped artifact dir)
        self._init_from(
            artifacts.ArtifactRef(name, "dir", directory, directory=directory),
            serve_dtype=serve_dtype,
        )

    @classmethod
    def from_artifact(
        cls, ref: "artifacts.ArtifactRef", serve_dtype: Optional[str] = None
    ) -> "ModelEntry":
        entry = cls.__new__(cls)
        entry._init_from(ref, serve_dtype=serve_dtype)
        return entry

    def _init_from(
        self, ref: "artifacts.ArtifactRef", serve_dtype: Optional[str] = None
    ) -> None:
        self.name = ref.name
        self.directory = ref.ref
        self.model = ref.load_model()
        self.metadata = ref.load_metadata()
        # machine= wires the single-machine scoring route into the
        # fleet-health plane: every response's total scores fold into
        # this machine's live sketch
        self.scorer = CompiledScorer(
            self.model, dtype=serve_dtype, machine=self.name
        )
        self.mtime, self.size = ref.stat()
        #: the artifact generation whose bytes this entry serves.  Pack
        #: rows written but not yet stamped carry ``gen = active + 1``;
        #: clamping to the store's published id makes a pending-loaded
        #: entry reload once when its stamp lands (bytes identical —
        #: harmless) instead of silently skipping the flip.  v1 dirs
        #: have no generations and stay at 0.
        if ref.kind == "pack" and ref._store is not None:
            self.generation = min(
                ref._store.row_generation(ref.name),
                ref._store.generation,
            )
        else:
            self.generation = 0

    @property
    def tags(self) -> List[str]:
        tag_list = self.metadata.get("dataset", {}).get("tag_list") or []
        return [t["name"] if isinstance(t, dict) else str(t) for t in tag_list]

    @property
    def resolution(self) -> Optional[str]:
        """The artifact's training resample resolution (pandas offset), used
        as the row-duration fallback when a request's index is too short to
        derive steps from."""
        return self.metadata.get("dataset", {}).get("resolution")


class ModelCollection:
    """All machines this server hosts: ``{name: ModelEntry}``.

    ``from_directory`` accepts either a single machine's artifact dir or a
    project output dir containing one artifact dir per machine (the layout
    ``build_project`` writes).
    """

    def __init__(
        self,
        entries: Dict[str, ModelEntry],
        project: str = "project",
        source_dir: Optional[str] = None,
        serve_mesh=None,
        pack_store=None,
        serve_dtype: Optional[str] = None,
        shard=None,
        fleet_machines: Optional[List[str]] = None,
        shard_owner: Optional[Dict[str, int]] = None,
        quarantined: Optional[Dict[str, Dict[str, Any]]] = None,
    ):
        from gordo_tpu.serve import precision

        self.entries = entries
        #: machines this replica owns but refuses to serve because their
        #: pack (or their individual load) failed validation:
        #: ``{name: {"error": str, "ts": epoch}}``.  The 503 surface,
        #: the ``gordo_machines_quarantined`` gauge, and the
        #: ``quarantined`` status in /fleet-health all read this; a
        #: rescan rebuilds it from scratch, so a good generation flip
        #: heals a machine the moment its pack validates again.
        self.quarantined: Dict[str, Dict[str, Any]] = dict(quarantined or {})
        #: most recent reload/quarantine failure, ``{"error", "ts"}`` —
        #: surfaced by /healthz so an operator sees WHY a fleet shrank
        #: without grepping logs
        self.last_error: Optional[Dict[str, Any]] = None
        if self.quarantined:
            worst = sorted(self.quarantined)[0]
            self.last_error = {
                "error": (
                    f"{len(self.quarantined)} machine(s) quarantined "
                    f"(e.g. {worst}: "
                    f"{self.quarantined[worst]['error']})"
                ),
                "ts": time.time(),
            }
        self.project = project
        self.source_dir = source_dir
        #: this replica's ShardSpec in a fleet-sharded tier (None when the
        #: process serves the whole project)
        self.shard = shard
        #: the FULL project machine list (sharded replicas serve a subset
        #: but must still answer "who owns machine X" — the 421 surface
        #: and the client/watchman shard-table source)
        self.fleet_machines = sorted(
            fleet_machines if fleet_machines is not None else entries
        )
        #: name → owning shard index, from the one shared shard function
        #: (``from_directory`` passes its already-computed table so a 10k-
        #: machine shard startup doesn't partition the fleet twice)
        if shard_owner is None and shard is not None:
            from gordo_tpu.serve.shard import shard_map

            shard_owner = shard_map(self.fleet_machines, shard.count)
        self.shard_owner: Dict[str, int] = shard_owner or {}
        #: optional ("models","data") fleet mesh: stacked serving dispatches
        #: shard their machine axis over it (multi-chip serving)
        self.serve_mesh = serve_mesh
        #: the v2 artifacts.PackStore these entries came from (None for a
        #: v1 directory layout): lets the fleet scorer ship each pack's
        #: stacked tensors to the device as ONE transfer
        self.pack_store = pack_store
        #: the ONE serving precision for this collection (env >
        #: build-manifest dtype > float32; resolved by from_directory) —
        #: per-machine mixing would make responses depend on bucketing
        self.serve_dtype = precision.canonical(serve_dtype) if (
            serve_dtype
        ) else precision.serve_dtype()
        self._fleet_scorer = None
        #: the published artifact generation these entries serve (0 for
        #: v1 layouts / pre-generation indexes) — the value the watch
        #: loop compares the on-disk GENERATION sidecar against
        self.artifact_generation: int = (
            int(getattr(pack_store, "generation", 0)) if pack_store else 0
        )
        #: True while a generation flip is being absorbed (entry rebuild
        #: + delta restack in an executor thread).  Scoring NEVER blocks
        #: on this — the old scorer keeps serving until the swap — but
        #: /healthz surfaces it so rollout tooling can see a reload in
        #: flight.
        self.reloading: bool = False
        # guards the (entries, _fleet_scorer) pair: the background rescan
        # swaps both from an executor thread while bulk requests lazily
        # build the scorer from other executor threads
        self._lock = threading.Lock()
        # adopt the build-time residual baselines riding the artifact
        # metadata — the reference distribution the drift signal (and
        # `gordo refresh`, eventually) compares live sketches against
        telemetry.FLEET_HEALTH.load_baselines(
            {name: e.metadata for name, e in entries.items()}
        )

    def device_doc(self) -> Dict[str, Any]:
        """The ``device`` object of ``/healthz``: read from the stacked
        serving arrays' own devices once the fleet scorer exists (warmup
        or the first stacked dispatch builds it); ``used`` is 0 before."""
        from gordo_tpu.mesh import array_devices, device_doc

        with self._lock:
            scorer = self._fleet_scorer
        held: set = set()
        if scorer is not None:
            for bucket in scorer.buckets:
                held |= array_devices(bucket.params)
        return device_doc(held)

    @property
    def fleet_scorer(self):
        """Stacked multi-machine scorer (built lazily on first bulk call)."""
        with self._lock:
            if self._fleet_scorer is None:
                from gordo_tpu.serve.fleet_scorer import FleetScorer

                self._fleet_scorer = FleetScorer.from_models(
                    {name: e.model for name, e in self.entries.items()},
                    mesh=self.serve_mesh,
                    pack_store=self.pack_store,
                    dtype=self.serve_dtype,
                )
            return self._fleet_scorer

    @classmethod
    def from_directory(
        cls, path: str, project: str = "project", serve_mesh=None,
        shard=None,
    ) -> "ModelCollection":
        """Load every artifact under ``path`` — a v2 pack index, v1
        per-machine dirs, a mixed output, or one machine's artifact dir.

        A failing pack quarantines ONLY its machines (they 503 with a
        ``quarantined`` detail and heal when a good generation flips)
        while the rest of the fleet loads and serves; it is never a
        silent shrink — the quarantine set rides /healthz, the project
        index, /fleet-health and the ``gordo_machines_quarantined``
        gauge.  Only when NOTHING loads does startup still die loudly
        (:class:`gordo_tpu.artifacts.PackCorruptError` — a server with
        zero machines serves nobody).  A single broken v1 dir only loses
        that machine, as before.

        ``shard`` (a :class:`gordo_tpu.serve.shard.ShardSpec`, default
        ``GORDO_SERVE_SHARD`` from the environment): load ONLY this
        replica's shard of the fleet — the partition is computed over the
        discovered machine list with the one shared shard function, so
        only the owned machines' models (and, pack-aligned, typically
        only the owned packs' bytes) are loaded, warmed, and made device-
        resident.  Per-replica time-to-ready scales as ~1/N.

        The serving dtype resolves here: ``GORDO_SERVE_DTYPE`` when set,
        else the build's warmup-manifest dtype (the precision decision
        travels with the artifacts), else float32."""
        from gordo_tpu.compile import load_warmup_manifest
        from gordo_tpu.serve import precision
        from gordo_tpu.serve.shard import ShardSpec, shard_map

        store, refs = artifacts.discover(path, quarantine=True)
        if shard is None:
            shard = ShardSpec.from_env()
        quarantined_errors: Dict[str, str] = dict(
            getattr(store, "quarantined_machines", None) or {}
        )
        # quarantined machines stay IN the fleet list: clients must keep
        # routing them to their owner (which answers 503 with the why),
        # and dropping them would shift the positional shard table
        fleet_machines = sorted(
            {r.name for r in refs} | set(quarantined_errors)
        )
        shard_owner: Optional[Dict[str, int]] = None
        if shard is not None:
            shard_owner = shard_map(fleet_machines, shard.count)
            refs = [
                r for r in refs
                if shard_owner.get(r.name) == shard.index
            ]
            # only this shard's quarantined machines are ours to report
            quarantined_errors = {
                n: e for n, e in quarantined_errors.items()
                if shard_owner.get(n) == shard.index
            }
            if not refs and not quarantined_errors and fleet_machines:
                raise FileNotFoundError(
                    f"Shard {shard} owns no machines of the "
                    f"{len(fleet_machines)}-machine fleet under {path!r} "
                    f"(shard count exceeds the machine count?)"
                )
            logger.info(
                "Serving shard %s: %d of %d machines",
                shard, len(refs), len(fleet_machines),
            )
        source_dir: Optional[str] = (
            None if artifacts.is_artifact_dir(path) else path
        )
        manifest_dtype = None
        if source_dir is not None:
            manifest = load_warmup_manifest(source_dir)
            manifest_dtype = (manifest or {}).get("dtype")
        serve_dtype = precision.serve_dtype(default=manifest_dtype)
        entries: Dict[str, ModelEntry] = {}
        for ref in refs:
            if ref.kind == "pack":
                try:
                    entries[ref.name] = ModelEntry.from_artifact(
                        ref, serve_dtype=serve_dtype
                    )
                except Exception as exc:
                    # pack-slot load failure (corrupt segment, injected
                    # read fault): quarantine just this machine — the
                    # pack's healthy siblings keep serving
                    logger.exception(
                        "quarantining %s: load failed", ref.name
                    )
                    quarantined_errors[ref.name] = str(exc)
                continue
            try:
                entries[ref.name] = ModelEntry.from_artifact(
                    ref, serve_dtype=serve_dtype
                )
            except Exception:
                logger.exception("Failed to load artifact %s", ref.ref)
        if not entries:
            if quarantined_errors:
                detail = "; ".join(
                    f"{n}: {e}" for n, e in
                    sorted(quarantined_errors.items())[:3]
                )
                raise artifacts.PackCorruptError(
                    f"every machine under {path!r} is quarantined "
                    f"({detail})"
                )
            raise FileNotFoundError(f"No model artifacts under {path!r}")
        now = time.time()
        return cls(
            entries,
            project=project,
            source_dir=source_dir,
            serve_mesh=serve_mesh,
            pack_store=store,
            serve_dtype=serve_dtype,
            shard=shard,
            fleet_machines=fleet_machines,
            shard_owner=shard_owner,
            quarantined={
                n: {"error": e, "ts": now}
                for n, e in quarantined_errors.items()
            },
        )

    def get(self, name: str) -> Optional[ModelEntry]:
        return self.entries.get(name)

    @property
    def generation(self) -> int:
        """Fleet-generation stamp: for v2 packs with a generations layer,
        the REAL published artifact generation id (small monotone ints —
        what ``client.wait_for_generation`` converges on and watchman
        republishes per target).  Layouts predating the generations layer
        fall back to the old change-detector integers: the pack index's
        mtime-in-ms, else the newest loaded artifact's — still monotone
        enough for rollout visibility, never confusable with real ids
        (ms timestamps are 13 digits, generation ids start at 1)."""
        if self.artifact_generation > 0:
            return self.artifact_generation
        if self.pack_store is not None:
            return int(self.pack_store.index_stat[0] * 1000)
        return int(
            max((e.mtime for e in self.entries.values()), default=0.0)
            * 1000
        )

    def maybe_delta_reload(self) -> Dict[str, List[str]]:
        """The generation watch loop's poll: read the tiny ``GENERATION``
        sidecar (one small file, no index parse, no pack validation) and
        run a rescan only when the published id advanced past what this
        collection serves.  Nothing blocks scoring either way."""
        unchanged = {"added": [], "reloaded": [], "removed": []}
        if self.source_dir is None:
            return unchanged
        try:
            gen = artifacts.read_generation(self.source_dir)
        except Exception:
            logger.exception("generation poll failed")
            return unchanged
        if gen <= self.artifact_generation:
            return unchanged
        return self.rescan()

    def rescan(self) -> Dict[str, List[str]]:
        """Pick up artifacts dumped/rebuilt/removed after startup.

        The reference got this "for free" from its pod-per-model design (a
        new machine = a new pod); one process serving a whole project must
        instead watch its artifact dir.  New artifacts load, vanished ones
        drop, changed ones reload — v1 dirs on (mtime, size) of model.pkl,
        v2 pack slots on the flock-serialized index GENERATION: a pack
        machine reloads iff its row's generation is newer than its entry's
        and no newer than the published id.  Pack mtimes are NOT a signal
        (``delta_write`` mutates pack bytes in place, so mtime ticks while
        a write is still torn; the generation flips only after the bytes
        are fsync'd) and pending rows (``gen > published``) are invisible
        until their build stamps.  When every change is a generation-gated
        pack reload, the fleet scorer is rebuilt by ``delta_restack`` —
        O(changed machines), one device transfer per touched pack, zero
        compiles — and swapped under the lock while the old scorer keeps
        serving; structural changes fall back to the full restack.  The
        entries dict is replaced atomically so in-flight requests keep a
        consistent view.
        """
        if self.source_dir is None or not os.path.isdir(self.source_dir):
            return {"added": [], "reloaded": [], "removed": []}
        try:
            store, refs = artifacts.discover(
                self.source_dir, quarantine=True
            )
        except Exception as exc:
            # a mid-write index (builder racing the rescan) must not take
            # down the serving loop — keep the current view, retry later
            logger.exception("Artifact discovery failed during rescan")
            self.last_error = {
                "error": f"rescan discovery failed: {exc}",
                "ts": time.time(),
            }
            return {"added": [], "reloaded": [], "removed": []}
        # this scan's quarantine view, rebuilt from scratch every rescan:
        # a machine whose new generation validates simply stops appearing
        # here — that IS the heal
        scan_quarantined: Dict[str, str] = dict(
            getattr(store, "quarantined_machines", None) or {}
        )
        fleet_machines = sorted(
            {r.name for r in refs} | set(scan_quarantined)
        )
        shard_owner: Dict[str, int] = {}
        if self.shard is not None:
            # re-partition over the CURRENT fleet: machines built after
            # startup land on their owning shard, and only that replica
            # loads them (every replica recomputes the same partition)
            from gordo_tpu.serve.shard import shard_map

            shard_owner = shard_map(fleet_machines, self.shard.count)
            refs = [
                r for r in refs
                if shard_owner.get(r.name) == self.shard.index
            ]
            scan_quarantined = {
                n: e for n, e in scan_quarantined.items()
                if shard_owner.get(n) == self.shard.index
            }
        if (
            store is not None
            and self.pack_store is not None
            and store.index_stat == self.pack_store.index_stat
        ):
            # unchanged index: keep the already-mapped store so entry
            # views and the fleet scorer's prestacking stay one object
            store = self.pack_store
            for ref in refs:
                if ref.kind == "pack":
                    ref._store = store
        store_generation = int(getattr(store, "generation", 0) or 0)
        flip = (
            store is not None
            and store_generation != self.artifact_generation
        )
        if flip:
            self.reloading = True
        try:
            added, reloaded, reloaded_dirs = [], [], []
            new_entries: Dict[str, ModelEntry] = {}
            for ref in refs:
                current = self.entries.get(ref.name)
                stale = False
                if current is not None:
                    if ref.kind == "pack" and store_generation > 0:
                        # generation gating — the torn-write-safe signal:
                        # delta_write rewrites pack bytes in place, so a
                        # stat-based compare can reload mid-write; the
                        # index generation flips only after fsync.  Rows
                        # newer than the published id are pending (a
                        # build still running) and must NOT load yet.
                        row_gen = store.row_generation(ref.name)
                        stale = (
                            current.generation < row_gen <= store_generation
                            # a restored/rolled-back index publishes an
                            # OLDER id than the entry serves: adopt it
                            or current.generation > store_generation
                        )
                    elif ref.kind == "pack":
                        # pre-generation index (never stamped): the old
                        # whole-store signals — an index swap remaps
                        # every pack, and (mtime, size) drift reloads
                        stale = store is not self.pack_store or (
                            ref.stat() != (current.mtime, current.size)
                        )
                    else:
                        # (mtime, size) inequality, not mtime>: a rebuild
                        # can land with an equal-or-older mtime (cache
                        # copies, clock skew) and must still reload.
                        # Known blind spot: an mtime-preserving copy
                        # (cp -p) of a same-size artifact is
                        # indistinguishable without hashing content.
                        stale = ref.stat() != (current.mtime, current.size)
                try:
                    if current is None:
                        new_entries[ref.name] = ModelEntry.from_artifact(
                            ref, serve_dtype=self.serve_dtype
                        )
                        added.append(ref.name)
                    elif stale:
                        new_entries[ref.name] = ModelEntry.from_artifact(
                            ref, serve_dtype=self.serve_dtype
                        )
                        reloaded.append(ref.name)
                        if ref.kind != "pack":
                            reloaded_dirs.append(ref.name)
                    else:
                        new_entries[ref.name] = current
                except Exception as exc:
                    logger.exception(
                        "Failed to (re)load artifact %s", ref.ref
                    )
                    if current is not None:  # keep serving the old model
                        new_entries[ref.name] = current
                    elif ref.kind == "pack":
                        # nothing to keep serving: the machine joins the
                        # quarantine set instead of silently vanishing
                        scan_quarantined[ref.name] = str(exc)
            removed = sorted(set(self.entries) - set(new_entries))
            # quarantine refresh + heal: the set is rebuilt from THIS
            # scan, so a machine whose new generation validates drops out
            # (heal) and a newly-corrupt one joins; a persisting error
            # keeps its original timestamp
            new_quarantined: Dict[str, Dict[str, Any]] = {}
            for name, err in scan_quarantined.items():
                prev = self.quarantined.get(name)
                new_quarantined[name] = (
                    prev if prev is not None and prev["error"] == err
                    else {"error": err, "ts": time.time()}
                )
            healed = sorted(
                n for n in self.quarantined if n not in new_quarantined
            )
            if healed:
                logger.info(
                    "quarantine healed for %s (generation %d)",
                    healed, store_generation,
                )
            newly_quarantined = sorted(
                set(new_quarantined) - set(self.quarantined)
            )
            if newly_quarantined:
                worst = newly_quarantined[0]
                self.last_error = {
                    "error": (
                        f"quarantined {newly_quarantined} "
                        f"({worst}: {new_quarantined[worst]['error']})"
                    ),
                    "ts": time.time(),
                }
            self.quarantined = new_quarantined
            if added or reloaded or removed or flip:
                logger.info(
                    "Collection rescan: +%s ~%s -%s (generation %d -> %d)",
                    added, reloaded, removed,
                    self.artifact_generation, store_generation,
                )
                # while the successor scorer builds, the OLD one keeps
                # serving — nothing below blocks a request until the
                # quick swap under the lock
                with self._lock:
                    old_scorer = self._fleet_scorer
                new_scorer = None
                if (
                    old_scorer is not None
                    and store is not None
                    and not added and not removed and not reloaded_dirs
                ):
                    try:
                        new_scorer = old_scorer.delta_restack(
                            {n: e.model for n, e in new_entries.items()},
                            store,
                            reloaded,
                            mesh=self.serve_mesh,
                        )
                    except Exception:
                        # a failed delta restack falls back to the lazy
                        # full rebuild — never to a stale scorer
                        logger.exception("delta restack failed")
                        new_scorer = None
                with self._lock:  # swap entries + scorer atomically
                    self.entries = new_entries
                    self.pack_store = store
                    self._fleet_scorer = new_scorer
                    self.artifact_generation = store_generation
                _RELOADS_TOTAL.inc(
                    1.0, "delta" if new_scorer is not None else "full"
                )
                # refresh drift baselines for (re)loaded artifacts — a
                # rebuilt machine's NEW training distribution is the one
                # its live window must be compared against from now on
                telemetry.FLEET_HEALTH.load_baselines(
                    {
                        name: new_entries[name].metadata
                        for name in added + reloaded
                        if name in new_entries
                    }
                )
        finally:
            self.reloading = False
        # fleet view refreshes even when this shard's entries didn't
        # change: a machine added to ANOTHER shard must still 421-route
        # (not 404) from here, and the shard table must agree fleet-wide
        self.fleet_machines = fleet_machines
        if self.shard is not None:
            self.shard_owner = shard_owner
        return {"added": added, "reloaded": reloaded, "removed": removed}


# ---------------------------------------------------------------------------
# payload parsing / response shaping
# ---------------------------------------------------------------------------

def parse_X(payload: Any, tags: List[str]) -> np.ndarray:
    """``{"X": ...}`` JSON → float32 matrix.  Accepts a list-of-lists or a
    list of records keyed by tag name (reference ``server/utils.py``
    ``@extract_X_y`` behaviors)."""
    if not isinstance(payload, dict) or "X" not in payload:
        raise ValueError("Payload must be a JSON object with an 'X' key")
    X = payload["X"]
    if isinstance(X, list) and X and isinstance(X[0], dict):
        if not tags:
            raise ValueError("Record-style X requires model tag metadata")
        try:
            X = [[rec[t] for t in tags] for rec in X]
        except KeyError as exc:
            raise ValueError(f"Record missing tag {exc}")
    try:
        arr = np.asarray(X, dtype=np.float32)
    except (TypeError, ValueError) as exc:
        # e.g. JSON nulls / non-numeric entries — a client error, not a 500
        raise ValueError(f"X is not a numeric matrix: {exc}")
    if arr.ndim == 1:
        arr = arr[:, None]
    if arr.ndim != 2:
        raise ValueError(f"X must be 2-dimensional, got shape {arr.shape}")
    return arr


#: bodies above this decode+parse in the executor: a 3 MB JSON request
#: costs ~20-30ms of json.loads + np.asarray — enough that at 64-way
#: concurrency the event loop itself was the serving bottleneck
_OFFLOAD_BYTES = 64 * 1024


def _decode_payload(raw: bytes, is_msgpack: bool) -> Any:
    """Bytes → payload dict; ValueError on malformed input (→ 400), 415
    for a body carrying an array dtype the wire doesn't speak (a media
    problem, not a malformed payload).  Pure function so handlers can run
    it on or off the event loop."""
    if is_msgpack:
        try:
            return codec.unpackb(raw)
        except codec.UnsupportedWireDtype as exc:
            raise web.HTTPUnsupportedMediaType(
                text=json.dumps({"error": str(exc)}),
                content_type="application/json",
            )
        except Exception as exc:
            raise ValueError(f"Invalid msgpack body: {exc}")
    # json.JSONDecodeError is a ValueError — same 400 surface as before
    return json.loads(raw)


async def _read_payload(request: web.Request) -> Any:
    """Request body → payload dict; msgpack bodies (the bundled client's
    bulk fast path) decode through the binary codec, anything else parses
    as JSON.  Large bodies decode in the executor so the accept loop
    stays responsive under concurrent load."""
    raw = await request.read()
    is_msgpack = request.content_type == codec.MSGPACK_CONTENT_TYPE
    if len(raw) > _OFFLOAD_BYTES:
        return await asyncio.get_running_loop().run_in_executor(
            None, _decode_payload, raw, is_msgpack
        )
    return _decode_payload(raw, is_msgpack)


async def _read_and_parse_single(request: web.Request, entry: "ModelEntry"):
    """Read → decode → parse for the single-machine routes, off-loop for
    large bodies (one executor hop covers decode AND the list→ndarray
    conversion, both loop-hostile at 2048-row request sizes).

    Returns ``(X, index, y)``; raises ValueError for client errors."""
    raw = await request.read()
    is_msgpack = request.content_type == codec.MSGPACK_CONTENT_TYPE

    def work():
        payload = _decode_payload(raw, is_msgpack)
        X = parse_X(payload, entry.tags)
        _validate_width(X, entry)
        index = parse_index(payload, X.shape[0])
        y = (
            parse_X({"X": payload["y"]}, entry.tags)
            if isinstance(payload, dict) and payload.get("y") is not None
            else None
        )
        return X, index, y

    if len(raw) > _OFFLOAD_BYTES:
        return await asyncio.get_running_loop().run_in_executor(None, work)
    return work()


async def _respond(
    request: web.Request, obj: Any, status: int = 200
) -> web.Response:
    """Encode a scoring response: GSB1 columnar blocks when the client
    lists ``Accept: application/x-gordo-columnar`` (the bulk route hands
    this path a still-stacked ``ColumnarResult`` — zero per-machine
    splitting on either end of the wire), msgpack when the client asks
    (``Accept: application/x-msgpack`` — raw array buffers, memcpy speed),
    JSON otherwise with ndarray
    leaves encoded by the native fastjson kernel (~13x stdlib json, which
    was the measured HTTP serving ceiling — see ``serve/codec.py``).
    An ``Accept`` ``dtype=`` media parameter selects the wire float
    precision (``application/x-msgpack;dtype=bfloat16`` halves bulk
    response bytes); an unknown dtype name is a 415, not a 500.
    Encoding runs in the executor: a large bulk body takes ~100ms even
    natively, which must not stall the accept loop."""
    try:
        encode, content_type = codec.negotiate(
            request.headers.get("Accept", "")
        )
    except codec.UnsupportedWireDtype as exc:
        raise web.HTTPUnsupportedMediaType(
            text=json.dumps({"error": str(exc)}),
            content_type="application/json",
        )
    body = await asyncio.get_running_loop().run_in_executor(
        None, encode, obj
    )
    return web.Response(body=body, status=status, content_type=content_type)


def parse_index(payload: Any, n_rows: int) -> Optional[pd.DatetimeIndex]:
    """Optional per-row timestamps riding with X (reference server-views
    behavior: requests carrying time info get time info back)."""
    idx = payload.get("index") if isinstance(payload, dict) else None
    if idx is None:
        return None
    if not isinstance(idx, list) or len(idx) != n_rows:
        got = len(idx) if isinstance(idx, list) else type(idx).__name__
        raise ValueError(
            f"index must list one timestamp per X row ({n_rows}), got {got}"
        )
    try:
        return pd.DatetimeIndex(pd.to_datetime(idx, utc=True))
    except Exception as exc:
        raise ValueError(f"index is not parseable as timestamps: {exc}")


def time_columns(
    index: pd.DatetimeIndex, n_out: int, resolution: Optional[str] = None
) -> Dict[str, List[str]]:
    """Per-output-row ``start``/``end`` (reference ``make_base_dataframe``
    columns): start = the input row's timestamp (offset rows consumed at the
    front), end = the NEXT row's timestamp — per-row diffs, so irregular
    indices get their true row spans (a median step would mislabel every row
    around a gap).  The last row extends by its preceding step; 1-row
    requests (no step to derive) fall back to the artifact's training
    ``resolution``, then to zero."""
    start = index[len(index) - n_out:]
    if len(index) >= 2:
        deltas = index[1:] - index[:-1]
        end_all = index[1:].append(
            pd.DatetimeIndex([index[-1] + deltas[-1]])
        )
        end = end_all[len(index) - n_out:]
    else:
        res_delta = pd.Timedelta(0)
        if resolution:
            try:
                res_delta = pd.Timedelta(
                    pd.tseries.frequencies.to_offset(resolution)
                )
            except (ValueError, TypeError):
                pass
        end = start + res_delta
    return {
        "start": [t.isoformat() for t in start],
        "end": [t.isoformat() for t in end],
    }


# ---------------------------------------------------------------------------
# handlers
# ---------------------------------------------------------------------------

def _misdirected(collection: "ModelCollection", name: str) -> Optional[str]:
    """When ``name`` is a real fleet machine owned by ANOTHER shard,
    the human-readable misroute message (else None).  Clients computing
    the shard table locally never hit this; it exists so a stale or
    hand-built client fails loudly with the owner's identity instead of
    a 404 that reads like 'machine was deleted'."""
    if collection.shard is None:
        return None
    owner = collection.shard_owner.get(name)
    if owner is None or owner == collection.shard.index:
        return None
    return (
        f"Machine {name!r} belongs to serving shard "
        f"{owner}/{collection.shard.count}; this replica serves shard "
        f"{collection.shard}"
    )


def _entry_or_404(request: web.Request) -> ModelEntry:
    return _resolve_entry(
        request.app[COLLECTION_KEY], request.match_info["machine"]
    )


def _resolve_entry(collection: "ModelCollection", name: str) -> ModelEntry:
    """``name`` -> entry, with the one quarantine/misroute/404 contract
    shared by the path-routed handlers and the streaming plane (whose
    machine names arrive in payloads and query strings, not the path)."""
    entry = collection.get(name)
    if entry is None:
        info = collection.quarantined.get(name)
        if info is not None:
            # 503, not 404: the machine EXISTS and will heal when a good
            # generation flips — clients should treat this as transient
            raise web.HTTPServiceUnavailable(
                text=json.dumps({
                    "error": (
                        f"Machine {name!r} is quarantined: "
                        f"{info['error']}"
                    ),
                    "quarantined": True,
                    "since": info["ts"],
                }),
                content_type="application/json",
            )
        misroute = _misdirected(collection, name)
        if misroute is not None:
            # 421 Misdirected Request: the machine exists, this replica
            # just isn't its owner — a routing bug, not a missing model
            # (and a non-retryable client error on the bundled client)
            raise web.HTTPMisdirectedRequest(
                text=json.dumps({
                    "error": misroute,
                    "shard": collection.shard_owner[name],
                    "shard-count": collection.shard.count,
                }),
                content_type="application/json",
            )
        raise web.HTTPNotFound(text=f"Machine {name!r} not found")
    return entry


def _shed_response(request: web.Request) -> Optional[web.Response]:
    """Overload shedding: once the coalescer's saturation stand-down has
    ESCALATED (consecutive stand-downs doubling the cooldown — not the
    first transient one), new scoring work is refused with 429 +
    ``Retry-After`` derived from the observed queue wait, instead of
    queueing toward a timeout.  The bundled client honors Retry-After on
    its retryable-status path, so a shed request comes back exactly when
    the server predicted it could be served."""
    coalescer = request.app.get(COALESCER_KEY)
    if coalescer is None:
        return None
    retry_after = coalesce_mod.shed_retry_after(coalescer)
    if retry_after is None:
        return None
    _SHED_TOTAL.inc()
    return web.json_response(
        {
            "error": (
                "server overloaded (queue wait escalated past service "
                "time); retry after the indicated delay"
            ),
            "retry-after-seconds": retry_after,
        },
        status=429,
        headers={"Retry-After": str(max(1, int(round(retry_after))))},
    )


async def healthcheck(request: web.Request) -> web.Response:
    _entry_or_404(request)
    return web.json_response({"gordo-server-version": gordo_tpu.__version__})


async def metadata(request: web.Request) -> web.Response:
    entry = _entry_or_404(request)
    return web.json_response(
        {
            "endpoint-metadata": {"model-name": entry.name},
            "metadata": entry.metadata,
        },
        dumps=_json_dumps,
    )


async def prediction(request: web.Request) -> web.Response:
    entry = _entry_or_404(request)
    t0 = time.perf_counter()
    try:
        X, index, _ = await _read_and_parse_single(request, entry)
    except ValueError as exc:
        return web.json_response({"error": str(exc)}, status=400)
    loop = asyncio.get_running_loop()
    try:
        with telemetry.span(
            "server.predict", machine=entry.name, rows=X.shape[0]
        ):
            out = await loop.run_in_executor(None, entry.scorer.predict, X)
    except ValueError as exc:  # client-input problem (e.g. short rows)
        return web.json_response({"error": str(exc)}, status=400)
    except Exception as exc:
        logger.exception("Prediction failed for %s", entry.name)
        return web.json_response({"error": str(exc)}, status=500)
    data: Dict[str, Any] = {"model-output": out}
    if index is not None:
        data.update(time_columns(index, out.shape[0], entry.resolution))
    return await _respond(
        request,
        {
            "data": data,
            "time-seconds": round(time.perf_counter() - t0, 6),
        },
    )


async def anomaly_prediction(request: web.Request) -> web.Response:
    entry = _entry_or_404(request)
    shed = _shed_response(request)
    if shed is not None:
        # refused before the body is even read: shedding exists to stop
        # spending on work that will queue to death anyway
        return shed
    if not entry.scorer.is_anomaly:
        return web.json_response(
            {
                "error": "Model is not an AnomalyDetector; use /prediction"
            },
            status=422,
        )
    t0 = time.perf_counter()
    try:
        X, index, y = await _read_and_parse_single(request, entry)
    except ValueError as exc:
        return web.json_response({"error": str(exc)}, status=400)
    deadline = request.get(DEADLINE_KEY)
    if deadline is not None and time.monotonic() >= deadline:
        # the budget ran out while the body was read/parsed — refuse
        # before dispatch rather than scoring into a dead socket
        return _deadline_expired_response("before dispatch")
    loop = asyncio.get_running_loop()
    coalescer = request.app.get(COALESCER_KEY)
    score_span = telemetry.span(
        "server.anomaly", machine=entry.name, rows=X.shape[0]
    )
    try:
        with score_span:
            if coalescer is not None and y is None:
                # handlers run on the single-threaded event loop, so the
                # inflight counter needs no lock; it counts EVERY in-flight
                # single-machine anomaly request (direct or coalesced) —
                # the concurrency signal the adaptive bypass keys on
                coalescer.inflight += 1
                try:
                    if coalescer.should_coalesce():
                        # concurrent requests across machines merge into
                        # one stacked dispatch (the _bulk route's program
                        # family)
                        out = await asyncio.wrap_future(
                            coalescer.submit(
                                entry.name,
                                X,
                                trace_id=telemetry.current_trace_id(),
                                deadline=deadline,
                            )
                        )
                    else:  # too few riders: direct dispatch wins — bypass
                        out = await loop.run_in_executor(
                            None, entry.scorer.anomaly_arrays, X, None
                        )
                finally:
                    coalescer.inflight -= 1
            else:
                out = await loop.run_in_executor(
                    None, entry.scorer.anomaly_arrays, X, y
                )
    except ValueError as exc:  # client-input problem (e.g. short rows)
        return web.json_response({"error": str(exc)}, status=400)
    except coalesce_mod.DeadlineExpired as exc:
        # the coalescer dropped this rider pre-dispatch: its propagated
        # budget expired while queued
        return _deadline_expired_response(str(exc))
    except Exception as exc:
        logger.exception("Anomaly scoring failed for %s", entry.name)
        return web.json_response({"error": str(exc)}, status=500)
    data = dict(out)
    if index is not None:
        data.update(
            time_columns(index, len(data["model-output"]), entry.resolution)
        )
    return await _respond(
        request,
        {
            "data": data,
            "time-seconds": round(time.perf_counter() - t0, 6),
        },
    )


async def bulk_anomaly_prediction(request: web.Request) -> web.Response:
    """Score MANY machines in one request via the stacked fleet scorer
    (one vmapped device program per structure bucket).  Payload:
    ``{"X": {"<machine>": [[...rows...]], ...}}``."""
    collection: ModelCollection = request.app[COLLECTION_KEY]
    t0 = time.perf_counter()
    try:
        payload = await _read_payload(request)
        if not isinstance(payload, dict) or not isinstance(payload.get("X"), dict):
            raise ValueError(
                "Payload must be {'X': {machine: rows}} for bulk scoring"
            )
    except ValueError as exc:
        return web.json_response({"error": str(exc)}, status=400)
    # per-machine validation: one bad machine reports in ITS result slot and
    # must not 400 the rest of the fleet.  The whole parse loop (dozens of
    # list->ndarray conversions) runs in the executor — at fleet request
    # sizes it is far too much work for the event loop.
    indices = payload.get("index") or {}

    def _parse_machines():
        X_by: Dict[str, np.ndarray] = {}
        idx_by: Dict[str, pd.DatetimeIndex] = {}
        errors: Dict[str, Dict[str, str]] = {}
        # bulk clients replay one fetch window across the fleet, so the
        # per-machine index lists are usually IDENTICAL — parse each
        # distinct list once (list equality is a C compare; re-running
        # pd.to_datetime per machine was the parse loop's hottest path)
        idx_cache: Dict[tuple, "tuple[list, pd.DatetimeIndex]"] = {}

        def parse_index_cached(raw: Any, n_rows: int):
            key = None
            if isinstance(raw, list) and raw and len(raw) == n_rows:
                key = (raw[0], raw[-1], len(raw))
                hit = idx_cache.get(key)
                if hit is not None and hit[0] == raw:
                    return hit[1]
            index = parse_index({"index": raw}, n_rows)
            if key is not None and index is not None:
                idx_cache[key] = (raw, index)
            return index

        for name, rows in payload["X"].items():
            entry = collection.get(name)
            try:
                if entry is None:
                    q = collection.quarantined.get(name)
                    if q is not None:
                        # in-slot, like every other per-machine bulk
                        # error: one quarantined machine must never tear
                        # the rest of the round's responses
                        raise ValueError(
                            f"Machine {name!r} is quarantined: "
                            f"{q['error']}"
                        )
                    # a foreign-shard machine reports its owner in-slot
                    # (scatter-gather clients route per shard and should
                    # never see this; a mis-split payload must say WHY)
                    raise ValueError(
                        _misdirected(collection, name)
                        or f"Unknown machine {name!r}"
                    )
                X = parse_X({"X": rows}, entry.tags)
                _validate_width(X, entry)
                if isinstance(indices, dict) and name in indices:
                    index = parse_index_cached(indices[name], X.shape[0])
                    if index is not None:
                        idx_by[name] = index
                X_by[name] = X
            except ValueError as exc:
                errors[name] = {"error": str(exc)}
        return X_by, idx_by, errors

    loop = asyncio.get_running_loop()
    X_by_name, index_by_name, machine_errors = await loop.run_in_executor(
        None, _parse_machines
    )
    if not X_by_name and machine_errors:
        return web.json_response(
            {"error": "No valid machines in payload",
             "data": machine_errors},
            status=400,
        )
    deadline = request.get(DEADLINE_KEY)
    if deadline is not None and time.monotonic() >= deadline:
        return _deadline_expired_response("before bulk dispatch")
    # a columnar client keeps the stacked dispatch output STACKED: decide
    # the assembly mode from Accept BEFORE dispatch so the hot path never
    # splits per machine just to re-glue the pieces at encode time
    columnar = codec.wants_columnar(request.headers.get("Accept"))
    try:
        # resolve the lazy scorer inside the executor too: first-call param
        # stacking for a large project must not stall the accept loop
        with telemetry.span("server.bulk", machines=len(X_by_name)):
            if columnar:
                col = await loop.run_in_executor(
                    None,
                    lambda: collection.fleet_scorer.dispatch_all(
                        X_by_name
                    ).assemble_columnar(),
                )
                out = col.rest
            else:
                col = None
                out = await loop.run_in_executor(
                    None, lambda: collection.fleet_scorer.score_all(X_by_name)
                )
    except Exception as exc:
        logger.exception("Bulk anomaly scoring failed")
        return web.json_response({"error": str(exc)}, status=500)
    # "client-error" is transport metadata (exception-type routing for the
    # coalescer), not response schema — strip it
    data = {
        name: {k: v for k, v in res.items() if k != "client-error"}
        for name, res in out.items()
    }
    # the parse loop dedupes equal indices to shared DatetimeIndex
    # objects, so one (index, n_out, resolution) rendering serves every
    # machine that shares the window — the per-machine isoformat loops
    # were, at fleet width, a bigger bill than the scoring itself
    tc_cache: Dict[tuple, Dict[str, List[str]]] = {}

    def cached_time_columns(name: str, n_out: int) -> Dict[str, List[str]]:
        entry = collection.get(name)
        resolution = entry.resolution if entry is not None else None
        index = index_by_name[name]
        key = (id(index), n_out, resolution)
        cols = tc_cache.get(key)
        if cols is None:
            cols = time_columns(index, n_out, resolution)
            tc_cache[key] = cols
        return cols

    for name, res in data.items():
        if name in index_by_name and "model-output" in res:
            res.update(cached_time_columns(name, len(res["model-output"])))
    if col is not None:
        # stacked machines never left the blocks; their time-column
        # partials ride the rest blob and merge client-side on decode
        for name in index_by_name:
            rows = col.rows(name)
            if rows:
                data.setdefault(name, {}).update(
                    cached_time_columns(name, rows)
                )
    data.update(machine_errors)
    if col is not None:
        col.rest = data
        payload_obj: Any = {
            "data": col,
            "time-seconds": round(time.perf_counter() - t0, 6),
        }
    else:
        payload_obj = {
            "data": data,
            "time-seconds": round(time.perf_counter() - t0, 6),
        }
    return await _respond(request, payload_obj)


async def download_model(request: web.Request) -> web.Response:
    entry = _entry_or_404(request)
    loop = asyncio.get_running_loop()
    # pickling a params pytree can take long enough to stall the accept loop
    body = await loop.run_in_executor(None, serializer.dumps, entry.model)
    return web.Response(body=body, content_type="application/octet-stream")


# -- streaming plane (serve/stream.py) --------------------------------------

def _stream_after(request: web.Request, hub) -> int:
    """The resume cursor: ``Last-Event-ID`` header (SSE reconnect), then
    ``?after=`` (long-poll / explicit replay), else the ring head — a
    fresh subscriber tails live events only."""
    raw = request.headers.get("Last-Event-ID") or request.query.get("after")
    if raw is None:
        return hub.ring.last_id
    try:
        return int(raw)
    except ValueError:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"bad event id {raw!r}"}),
            content_type="application/json",
        )


async def stream_ingest(request: web.Request) -> web.Response:
    """``POST {project}/stream/ingest``: feed arriving rows into the
    per-machine streams; verdicts/crossings push to subscribers.

    Body forms: ``{"machine": m, "x": row-or-rows}`` or the bulk-shaped
    ``{"X": {machine: rows}}``.  Scoring BYPASSES the coalescer — a
    streamed row is one O(1) fixed-shape dispatch already, and queueing
    it behind a micro-batch window would tax exactly the latency the
    push model exists to minimize.  Returns the accepted row count and
    the hub's event cursor (a poller can resume from it directly).
    """
    collection: ModelCollection = request.app[COLLECTION_KEY]
    hub = request.app[STREAM_HUB_KEY]
    payload = await _read_payload(request)
    if not isinstance(payload, dict):
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "body must be a JSON object"}),
            content_type="application/json",
        )
    try:
        if isinstance(payload.get("X"), dict):
            batches = [
                (name, rows) for name, rows in payload["X"].items()
            ]
        elif payload.get("machine"):
            batches = [(payload["machine"], payload.get("x"))]
        else:
            raise ValueError(
                'need {"machine": ..., "x": ...} or {"X": {machine: rows}}'
            )
        parsed = []
        for name, rows in batches:
            entry = _resolve_entry(collection, name)
            X = parse_X({"X": rows}, entry.tags)
            _validate_width(X, entry)
            parsed.append((name, entry, X))
    except ValueError as exc:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": str(exc)}),
            content_type="application/json",
        )
    accepted = 0
    published = 0
    for name, entry, X in parsed:
        try:
            events = hub.ingest_rows(
                name, entry.scorer, X, dtype=collection.serve_dtype
            )
        except stream_mod.StreamUnsupported as exc:
            raise web.HTTPUnprocessableEntity(
                text=json.dumps({"error": str(exc)}),
                content_type="application/json",
            )
        except faults.InjectedFault as exc:
            # the stream.ingest seam fires BEFORE state mutation, so
            # the client may retry without double-applying the row
            if exc.mode == "reset":
                if request.transport is not None:
                    request.transport.close()
                raise web.HTTPInternalServerError(text=str(exc))
            status = 503 if exc.mode == "http_503" else 500
            return web.json_response({"error": str(exc)}, status=status)
        accepted += int(X.shape[0])
        published += len(events)
    return await _respond(request, {
        "accepted": accepted,
        "events": published,
        "last-event-id": hub.ring.last_id,
    })


async def stream_subscribe(request: web.Request) -> web.StreamResponse:
    """``GET {project}/stream``: the push surface.

    Default is SSE (``text/event-stream`` frames with hub-global
    monotonic ids; reconnect with ``Last-Event-ID`` to replay what was
    missed).  ``?mode=poll&after=N`` is the chunked long-poll fallback
    for clients that can't hold SSE: it waits up to ``?timeout=`` (capped
    at the server's poll budget) for events past ``N`` and returns them
    as one JSON batch with the next cursor.  ``?machines=a,b`` filters;
    every named machine is resolved through the quarantine/shard
    contract first, so a subscription for a foreign machine 421s with
    the owner shard identified (clients split subscriptions per shard).
    """
    collection: ModelCollection = request.app[COLLECTION_KEY]
    hub = request.app[STREAM_HUB_KEY]
    machines = None
    if request.query.get("machines"):
        machines = [
            m for m in request.query["machines"].split(",") if m
        ]
        for name in machines:
            _resolve_entry(collection, name)
    after = _stream_after(request, hub)

    if request.query.get("mode") == "poll":
        try:
            timeout = min(
                float(request.query.get("timeout", "1e9")),
                stream_mod.poll_timeout_seconds(),
            )
        except ValueError:
            timeout = stream_mod.poll_timeout_seconds()
        doc = await stream_mod.poll_events(
            hub, set(machines) if machines else None, after, timeout
        )
        return await _respond(request, doc)

    sub = hub.subscribe(machines)
    response = web.StreamResponse(
        status=200,
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            # tells nginx-style proxies not to buffer the event stream
            "X-Accel-Buffering": "no",
        },
    )
    response.enable_chunked_encoding()
    await response.prepare(request)
    try:
        await stream_mod.run_sse(response, hub, sub, after)
    except faults.InjectedFault:
        # mid-event disconnect: kill the transport with the frame torn
        if request.transport is not None:
            request.transport.close()
    except (ConnectionResetError, ConnectionError, asyncio.CancelledError):
        pass  # peer went away / server shutdown — run_sse unsubscribed
    return response


async def readiness(request: web.Request) -> web.Response:
    """Readiness endpoint for orchestrators: 503 while a startup warmup is
    still compiling, 200 once it finishes (or when warmup is off).  The
    generated k8s Deployment points its readinessProbe here so a
    rescheduled pod only receives traffic once its programs are compiled.
    """
    fut = request.app.get(WARMUP_TASK_KEY)
    if fut is not None and not fut.done():
        return web.json_response(
            {"ready": False, "reason": "warmup in progress"}, status=503
        )
    return web.json_response({"ready": True})


async def healthz(request: web.Request) -> web.Response:
    """Liveness + warmup-state surface: 200 always (the process is up),
    with ``state`` reporting ``warming`` while the startup warmup is
    still pre-compiling serving programs and ``ready`` after — what
    ``gordo warmup --url`` polls, and the human-readable twin of the
    ``/ready`` readiness gate (which speaks HTTP status for kubernetes).
    """
    fut = request.app.get(WARMUP_TASK_KEY)
    state = "warming" if (fut is not None and not fut.done()) else "ready"
    collection = request.app.get(COLLECTION_KEY)
    if state == "ready" and collection is not None and collection.reloading:
        # a generation flip is being absorbed in the background; the OLD
        # scorer keeps serving throughout, so this state never gates
        # traffic — it is rollout visibility, not readiness
        state = "reloading"
    doc: Dict[str, Any] = {
        "state": state,
        "gordo-server-version": gordo_tpu.__version__,
        # programs that lost their AOT executable and now dispatch through
        # plain jit (compile plane) — serving continues, this says so
        "aot_fallbacks": compile_plane.aot_fallbacks(),
    }
    if collection is not None:
        doc["device"] = collection.device_doc()
        doc["fleet-generation"] = collection.generation
        if collection.quarantined:
            doc["quarantined"] = sorted(collection.quarantined)
        if collection.last_error is not None:
            # the most recent reload/quarantine failure (string +
            # timestamp): an operator probing a shrunken fleet sees WHY
            # here instead of grepping logs
            doc["last-error"] = dict(collection.last_error)
    if state == "ready" and fut is not None:
        # a FAILED warmup still goes ready (the pod can serve; programs
        # compile lazily) but says so, so the init-container gate can tell
        exc = None if fut.cancelled() else fut.exception()
        if exc is not None:
            doc["warmup_error"] = str(exc)
        elif fut.done():
            res = fut.result()
            doc["warmup_errors"] = int(res.get("errors", 0)) if isinstance(
                res, dict
            ) else 0
    return web.json_response(doc)


async def metrics_endpoint(request: web.Request) -> web.Response:
    """Prometheus scrape surface (mounted at ``/metrics``, where every
    scraper looks by default).  Point-in-time gauges (collection size,
    coalescer queue/policy state) refresh at scrape time — they describe
    "now", so sampling them on the read side is both cheaper and more
    honest than pushing every transition."""
    collection = request.app.get(COLLECTION_KEY)
    if collection is not None:
        _MACHINES_GAUGE.set(len(collection.entries))
        _QUARANTINED_GAUGE.set(float(len(collection.quarantined)))
        _FLEET_GENERATION_GAUGE.set(float(collection.generation))
        if collection.shard is not None:
            _SHARD_INDEX_GAUGE.set(collection.shard.index)
            _SHARD_COUNT_GAUGE.set(collection.shard.count)
        # fleet-health gauges refresh at scrape time too: top-K by drift
        # only (bounded cardinality on a 10k-machine fleet; the full
        # per-machine set lives at /gordo/v0/<p>/fleet-health)
        telemetry.FLEET_HEALTH.export_gauges(
            machines=sorted(collection.entries)
        )
    coalesce_mod.export_gauges(request.app.get(COALESCER_KEY))
    return web.Response(
        text=telemetry.render(), content_type=METRICS_CONTENT_TYPE
    )


async def fleet_health(request: web.Request) -> web.Response:
    """The full per-machine fleet-health document for THIS replica's
    machines: live score sketch, build-time baseline, drift score and
    status each, plus the top-K drift ranking (``?top=N`` overrides the
    default).  Sharded replicas report their shard identity so
    watchman's ``/fleet-health`` can merge N of these into one fleet
    view (sketches merge exactly — see telemetry/fleet_health.py)."""
    collection: ModelCollection = request.app[COLLECTION_KEY]
    try:
        top = int(request.query.get("top", "") or drift_top_k())
    except ValueError:
        return web.json_response(
            {"error": "top must be an integer"}, status=400
        )
    doc = telemetry.FLEET_HEALTH.doc(
        machines=sorted(collection.entries), top=top
    )
    doc["project-name"] = collection.project
    if collection.quarantined:
        # quarantined machines carry a `quarantined` status in the doc:
        # they have no live sketch (nothing scores them) but MUST NOT
        # read as merely "no data" — the fleet view has to show them red
        machines_doc = doc.setdefault("machines", {})
        for name, info in sorted(collection.quarantined.items()):
            slot = machines_doc.setdefault(name, {})
            slot["status"] = "quarantined"
            slot["quarantine-error"] = info["error"]
            slot["quarantine-since"] = info["ts"]
        doc["quarantined"] = sorted(collection.quarantined)
    if collection.shard is not None:
        doc["serve-shard"] = {
            "index": collection.shard.index,
            "count": collection.shard.count,
        }
    return web.json_response(doc)


async def scores_aggregate(request: web.Request) -> web.Response:
    """Aggregation pushdown over the score archive: per-machine,
    per-period summaries (count / mean / max / exceedance / sketch
    percentiles) computed server-side by scanning the mmap columns of
    ``.gordo-scores/`` under this collection's artifact dir — a
    fleet-year dashboard query returns kilobytes of summaries instead
    of the ~84M raw samples ``client.score_history`` would ship.

    Query: ``?machines=a,b&start=...&end=...&stats=count,p99&period=7d
    &threshold=1.0`` (all optional; defaults: full roster, the archive
    plan's span, the standard stat set, 1d, 1.0).  The response rides
    whatever the ``Accept`` header negotiates — the GSB1 columnar wire
    ships each stat as ONE contiguous ``[n_machines, n_periods]`` block
    (the bundled client's default); JSON/msgpack split per machine.
    The scan runs in the executor: a fleet-year pass takes ~100ms-class
    time that must not stall the accept loop."""
    collection: ModelCollection = request.app[COLLECTION_KEY]
    from gordo_tpu.batch import archive as score_archive

    root = collection.source_dir
    if root is None or not os.path.isdir(score_archive.archive_root(root)):
        return web.json_response(
            {"error": "no score archive under this server's artifact "
                      "dir (run gordo backfill first)"},
            status=404,
        )
    q = request.query
    machines = [m for m in (q.get("machines") or "").split(",") if m]
    stats = [s for s in (q.get("stats") or "").split(",") if s]
    period = (
        q.get("period")
        or os.environ.get("GORDO_SCORES_AGG_PERIOD", "")
        or "1d"
    )
    try:
        threshold = float(q.get("threshold", "") or 1.0)
    except ValueError:
        return web.json_response(
            {"error": "threshold must be a number"}, status=400
        )
    arch = score_archive.ScoreArchive(root)

    def scan() -> Dict[str, Any]:
        return arch.aggregate(
            machines or None,
            q.get("start") or None,
            q.get("end") or None,
            stats=stats or None,
            period=period,
            threshold=threshold,
        )

    try:
        doc = await asyncio.get_running_loop().run_in_executor(None, scan)
    except (ValueError, score_archive.ArchiveError) as exc:
        return web.json_response({"error": str(exc)}, status=400)
    # each stat matrix ships as one contiguous GSB1 block; the machine
    # map hands every machine its row view, so the JSON/msgpack
    # fallbacks split into per-machine dicts via the same one rule
    stat_arrays = doc.pop("stats")
    blocks = [np.ascontiguousarray(a) for a in stat_arrays.values()]
    entry_map = {
        name: {
            stat: (bi, mi, None)
            for bi, stat in enumerate(stat_arrays)
        }
        for mi, name in enumerate(doc["machines"])
    }
    envelope = dict(doc)
    envelope["stats"] = list(stat_arrays)
    envelope["data"] = codec.ColumnarResult(
        blocks=blocks, machines=entry_map
    )
    return await _respond(request, envelope)


def _mesh_doc(mesh) -> dict:
    """Wire-shape description of a serve mesh (``None`` = single-device)."""
    if mesh is None:
        return {"device-count": 1, "shape": None, "sharded": False}
    return {
        "device-count": int(mesh.devices.size),
        "shape": {str(k): int(v) for k, v in mesh.shape.items()},
        "sharded": True,
    }


async def project_index(request: web.Request) -> web.Response:
    collection: ModelCollection = request.app[COLLECTION_KEY]
    store = collection.pack_store
    doc = {
        "project-name": collection.project,
        "machines": sorted(collection.entries),
        "gordo-server-version": gordo_tpu.__version__,
        "coalescer": coalesce_mod.stats(request.app.get(COALESCER_KEY)),
        # client/watchman artifact discovery: which format backs this
        # collection, and how many packs when v2
        "artifact-format": "v2-packs" if store is not None else "v1-dirs",
        # the serving precision this collection dispatches at (the
        # serving-precision plane; clients reading bulk responses at
        # reduced wire dtypes can confirm what the compute side ran)
        "serving-dtype": collection.serve_dtype,
        # change-detector stamp for the artifacts backing this replica;
        # watchman republishes it per target (routing-topology surface)
        "fleet-generation": collection.generation,
        # placement plane: the device mesh this replica's stacked fleet
        # dispatches shard over (no mesh = single-device serving)
        "mesh": _mesh_doc(collection.serve_mesh),
    }
    if collection.quarantined:
        doc["quarantined"] = sorted(collection.quarantined)
    if collection.shard is not None:
        # the routing-topology surface: which shard this replica is, and
        # the FULL fleet list every client needs to compute the shard
        # table locally ("machines" stays this replica's served subset)
        doc["serve-shard"] = {
            "index": collection.shard.index,
            "count": collection.shard.count,
        }
        doc["fleet-machines"] = collection.fleet_machines
    if store is not None:
        doc["artifact-packs"] = len(store.packs)
        doc["artifact-pack-bytes"] = store.total_bytes()
        doc["artifact-generations-retained"] = len(store.generations)
    return web.json_response(doc)


def _validate_width(X: np.ndarray, entry: ModelEntry) -> None:
    tags = entry.tags
    if tags and X.shape[1] != len(tags):
        raise ValueError(
            f"X has {X.shape[1]} columns; model expects {len(tags)} tags"
        )


def _json_dumps(obj) -> str:
    import json

    return json.dumps(obj, default=str)


# ---------------------------------------------------------------------------
# app factory
# ---------------------------------------------------------------------------

def warmup_scorers(
    collection: ModelCollection,
    row_sizes: Optional[List[int]] = None,
) -> Dict[str, Any]:
    """Precompile the serving programs so early requests don't pay
    compilation (~20-40s cold on TPU).

    Delegates to the compile plane (:func:`gordo_tpu.compile.
    warmup_collection`): per structural bucket, per row bucket, the full
    stacked dispatch, the 1-machine subset gather, and the per-machine
    fused program are AOT-compiled (``lower(shapes).compile()`` — no
    input data, nothing executes).  Row buckets come from ``row_sizes``,
    else the build's warmup manifest under the collection's source dir,
    else the defaults (the minimum serving bucket and the 2048-row
    replayed-stream shape).  Errors are logged and counted, never raised:
    a warmup failure must not take down startup.
    """
    from gordo_tpu.compile import warmup_collection

    return warmup_collection(collection, row_sizes=row_sizes)


def build_app(
    collection: ModelCollection,
    rescan_interval: float = 0.0,
    coalesce_window_ms: float = 0.0,
    warmup: bool = False,
    coalesce_min_concurrency: int = 2,
    coalesce_knee_batch: int = 0,
    health_rollup_interval: float = 0.0,
    reload_watch_interval: float = 0.0,
) -> web.Application:
    """``rescan_interval > 0`` starts a background artifact-dir rescan so
    machines built after startup begin serving without a restart.
    ``health_rollup_interval > 0`` periodically appends this replica's
    fleet-health doc as one JSONL line under the artifact dir
    (``.gordo-fleet-health/``, size-capped keep-last-2 rotation) — the
    no-HTTP interface a ``gordo refresh`` loop (ROADMAP item 3) and
    ``gordo fleet-health --dir`` consume.
    ``coalesce_window_ms > 0`` micro-batches concurrent single-machine
    anomaly requests into stacked fleet dispatches (``serve/coalesce.py``):
    a continuous drain groups whatever is queued, capping each dispatch at
    the measured throughput knee and standing down to direct dispatch when
    the saturation signal says batching is losing.  ``coalesce_window_ms``
    bounds only the single-rider grace wait (one queued request holding
    for a second rider); requests below ``coalesce_min_concurrency`` in
    flight dispatch directly (adaptive bypass), so an idle or
    lightly-loaded server keeps uncoalesced latency.
    ``coalesce_knee_batch`` pins the batch cap explicitly (0 = estimate
    it from a short warmup sweep on first use).
    ``reload_watch_interval > 0`` starts the generation watch: a cheap
    poll of the artifact index's ``GENERATION`` sidecar that triggers a
    delta hot reload the moment a build (or ``delta_write``) stamps a
    new generation — O(changed machines), zero compiles, the old scorer
    serving until the swap.  It complements (does not replace) the
    coarser full ``rescan_interval`` sweep, which also covers v1 dirs
    and fleet membership changes.
    ``warmup`` precompiles the serving programs in a background executor
    task at startup (``warmup_scorers``) — the server accepts traffic
    immediately; an early request races the warmup at worst."""
    from gordo_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    app = web.Application(
        client_max_size=256 * 1024 * 1024,
        middlewares=[telemetry_middleware, deadline_middleware],
    )
    app[COLLECTION_KEY] = collection
    app[STREAM_HUB_KEY] = stream_mod.StreamHub(collection)

    if warmup:

        async def _warmup(app: web.Application):
            # a DAEMON thread, not the loop's executor: compiles can't be
            # interrupted, and a non-daemon worker (incl. any
            # ThreadPoolExecutor's) would be joined at interpreter exit —
            # Ctrl-C during a multi-minute TPU warmup must still exit
            # promptly
            loop = asyncio.get_running_loop()
            fut: asyncio.Future = loop.create_future()
            # readiness() only checks fut.done() — consume a failure here
            # so GC doesn't log "Future exception was never retrieved"
            fut.add_done_callback(
                lambda f: None if f.cancelled() else f.exception()
            )

            def _resolve(setter):
                try:
                    loop.call_soon_threadsafe(
                        lambda: None if fut.done() else setter()
                    )
                except RuntimeError:
                    pass  # loop already closed — nothing to resolve into

            def runner():
                try:
                    res = warmup_scorers(collection)
                    coalescer = app.get(COALESCER_KEY)
                    if coalescer is not None:
                        # the knee sweep rides the warmup thread: it warms
                        # the subset programs coalesced rounds run at AND
                        # fixes the batch cap before real traffic arrives
                        res["coalesce_knee"] = coalescer.ensure_knee(
                            rows=2048
                        )
                except Exception as exc:  # warmup_scorers logs details
                    # bind now: CPython deletes the except-bound name when
                    # the block exits, before the scheduled callback runs
                    _resolve(lambda e=exc: fut.set_exception(e))
                else:
                    _resolve(lambda: fut.set_result(res))
                finally:
                    # /healthz flips to "ready" and the coalescer stops
                    # queueing riders behind the warmup
                    compile_plane.set_warming(False)

            compile_plane.set_warming(True)
            threading.Thread(
                target=runner, name="gordo-warmup", daemon=True
            ).start()
            app[WARMUP_TASK_KEY] = fut

        app.on_startup.append(_warmup)

    if coalesce_window_ms > 0:
        coalescer = coalesce_mod.CoalescingScorer(
            lambda: collection.fleet_scorer,
            max_wait_s=coalesce_window_ms / 1000.0,
            min_concurrency=coalesce_min_concurrency,
            knee_batch=coalesce_knee_batch,
        )
        app[COALESCER_KEY] = coalescer

        async def _close_coalescer(app: web.Application):
            await asyncio.get_running_loop().run_in_executor(
                None, coalescer.close
            )

        app.on_cleanup.append(_close_coalescer)

    if rescan_interval > 0 and collection.source_dir is not None:

        async def _rescan_loop(app: web.Application):
            loop = asyncio.get_running_loop()
            while True:
                await asyncio.sleep(rescan_interval)
                try:
                    # artifact loads unpickle params — keep the accept loop
                    # responsive by rescanning in the executor
                    await loop.run_in_executor(None, collection.rescan)
                except Exception:
                    logger.exception("Artifact rescan failed")

        async def _start(app: web.Application):
            app["_rescan_task"] = asyncio.get_running_loop().create_task(
                _rescan_loop(app)
            )

        async def _stop(app: web.Application):
            task = app.get("_rescan_task")
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass

        app.on_startup.append(_start)
        app.on_cleanup.append(_stop)

    if reload_watch_interval > 0 and collection.source_dir is not None:

        async def _reload_watch_loop(app: web.Application):
            loop = asyncio.get_running_loop()
            while True:
                await asyncio.sleep(reload_watch_interval)
                try:
                    # the poll itself is one tiny file read; a detected
                    # flip runs the (heavier) delta reload in the
                    # executor so the accept loop never stalls
                    await loop.run_in_executor(
                        None, collection.maybe_delta_reload
                    )
                except Exception:
                    logger.exception("generation watch failed")

        async def _start_watch(app: web.Application):
            app["_reload_watch_task"] = (
                asyncio.get_running_loop().create_task(
                    _reload_watch_loop(app)
                )
            )

        async def _stop_watch(app: web.Application):
            task = app.get("_reload_watch_task")
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass

        app.on_startup.append(_start_watch)
        app.on_cleanup.append(_stop_watch)

    if health_rollup_interval > 0 and collection.source_dir is not None:

        def _write_health_rollup() -> None:
            doc = telemetry.FLEET_HEALTH.doc(
                machines=sorted(collection.entries)
            )
            doc["project-name"] = collection.project
            if collection.shard is not None:
                doc["serve-shard"] = {
                    "index": collection.shard.index,
                    "count": collection.shard.count,
                }
            telemetry.write_rollup(
                collection.source_dir, doc, shard=collection.shard
            )

        async def _rollup_loop(app: web.Application):
            loop = asyncio.get_running_loop()
            while True:
                await asyncio.sleep(health_rollup_interval)
                try:
                    # the doc build walks every machine's sketch — off
                    # the accept loop like the rescan
                    await loop.run_in_executor(None, _write_health_rollup)
                except Exception:
                    logger.exception("fleet-health rollup failed")

        async def _start_rollup(app: web.Application):
            app["_health_rollup_task"] = (
                asyncio.get_running_loop().create_task(_rollup_loop(app))
            )

        async def _stop_rollup(app: web.Application):
            task = app.get("_health_rollup_task")
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
            # last-gasp rollup at shutdown so a clean drain leaves the
            # freshest doc on disk for the file-interface consumers
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, _write_health_rollup
                )
            except Exception:
                logger.exception("final fleet-health rollup failed")

        app.on_startup.append(_start_rollup)
        app.on_cleanup.append(_stop_rollup)

    # scrape surface at the conventional root path (no project segment:
    # one process = one scrape target, whatever it hosts)
    app.router.add_get("/metrics", metrics_endpoint)
    # liveness + warmup state at the conventional root path too
    app.router.add_get("/healthz", healthz)
    p = f"{API_PREFIX}/{{project}}"
    app.router.add_get(f"{p}/", project_index)
    app.router.add_get(f"{p}/ready", readiness)
    # the fleet-under-observation surface (per-machine drift/sketches);
    # registered before the {machine} routes like _bulk
    app.router.add_get(f"{p}/fleet-health", fleet_health)
    # registered before the {machine} routes so "_bulk" never resolves as a
    # machine name
    app.router.add_post(f"{p}/_bulk/anomaly/prediction", bulk_anomaly_prediction)
    # score-archive aggregation pushdown (r20): summaries over the
    # backfill plane's archive, served from this collection's source dir
    app.router.add_get(f"{p}/scores/aggregate", scores_aggregate)
    # streaming plane: also before {machine} ("stream" is a path segment,
    # not a machine name)
    app.router.add_post(f"{p}/stream/ingest", stream_ingest)
    app.router.add_get(f"{p}/stream", stream_subscribe)
    app.router.add_get(f"{p}/{{machine}}/healthcheck", healthcheck)
    app.router.add_get(f"{p}/{{machine}}/metadata", metadata)
    app.router.add_post(f"{p}/{{machine}}/prediction", prediction)
    app.router.add_post(f"{p}/{{machine}}/anomaly/prediction", anomaly_prediction)
    app.router.add_get(f"{p}/{{machine}}/download-model", download_model)
    return app


def run_server(
    model_dir: str,
    host: str = "0.0.0.0",
    port: int = 5555,
    project: str = "project",
    rescan_interval: float = 30.0,
    coalesce_window_ms: float = 0.0,
    coalesce_min_concurrency: int = 2,
    coalesce_knee_batch: int = 0,
    model_parallel: bool = False,
    mesh_devices: Optional[str] = None,
    warmup: bool = False,
    shard: Optional[str] = None,
    health_rollup_interval: Optional[float] = None,
    reload_watch_interval: Optional[float] = None,
) -> None:
    """Blocking entrypoint (reference: ``gordo run-server``).

    ``model_parallel=True`` shards every stacked serving dispatch over all
    visible devices (the ``"models"`` mesh axis) — one server process
    driving a whole slice instead of one chip. ``mesh_devices`` narrows
    the fleet-mesh width (``"all"``/``"auto"``/``"1"``/an integer N;
    default is the ``GORDO_MESH_DEVICES`` env var, else all devices).

    ``shard``: ``"i/N"`` (or a :class:`~gordo_tpu.serve.shard.ShardSpec`)
    — serve only shard i of an N-replica fleet-sharded tier; default is
    the ``GORDO_SERVE_SHARD`` env var (what the generated per-shard
    Deployments stamp), else unsharded.

    ``health_rollup_interval``: seconds between fleet-health JSONL
    rollup lines under the artifact dir (default: the
    ``GORDO_HEALTH_ROLLUP_SECONDS`` env var, else 60; 0 disables).

    ``reload_watch_interval``: seconds between generation-sidecar polls
    for the delta hot reload (default: the ``GORDO_RELOAD_WATCH_SECONDS``
    env var, else 5; 0 disables — the coarse rescan still reloads, just
    slower and via a full restack).
    """
    # before the first compile: loading and stacking the collection below
    # already compiles, and what compiles before the cache is on is never
    # written to it
    from gordo_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    if health_rollup_interval is None:
        try:
            health_rollup_interval = float(
                os.environ.get("GORDO_HEALTH_ROLLUP_SECONDS", "") or 60.0
            )
        except ValueError:
            health_rollup_interval = 60.0
    if reload_watch_interval is None:
        try:
            reload_watch_interval = float(
                os.environ.get("GORDO_RELOAD_WATCH_SECONDS", "") or 5.0
            )
        except ValueError:
            reload_watch_interval = 5.0
    from gordo_tpu.serve.shard import ShardSpec

    if isinstance(shard, str):
        shard = ShardSpec.parse(shard)
    serve_mesh = None
    if model_parallel:
        from gordo_tpu.mesh import FleetMesh

        fm = FleetMesh.resolve(mesh_devices)  # honors GORDO_MESH_DEVICES
        if not fm.is_sharded:
            raise ValueError(
                "--model-parallel needs more than one device to shard "
                f"over, but the mesh resolved to 1 ({fm.devices[0]}); "
                "check device visibility and GORDO_MESH_DEVICES, or serve "
                "without --model-parallel"
            )
        serve_mesh = fm.mesh
        logger.info("Model-parallel serving over %d devices", fm.n_devices)
    # crash-safe writer audit before loading: sweep orphaned tmp files a
    # killed build left behind and re-publish a stale GENERATION sidecar;
    # unrepairable findings (truncated packs) are logged here and then
    # quarantined machine-by-machine by the collection load below
    try:
        report = artifacts.fsck(model_dir, repair=True)
        if report.get("findings"):
            logger.warning(
                "artifact fsck: %d finding(s), %d repaired — %s",
                len(report["findings"]),
                len(report.get("repaired", [])),
                report["findings"][:5],
            )
    except Exception:
        logger.exception("artifact fsck failed (continuing to load)")
    collection = ModelCollection.from_directory(
        model_dir, project=project, serve_mesh=serve_mesh, shard=shard
    )
    logger.info(
        "Serving %d machine(s)%s from %s on %s:%d",
        len(collection.entries),
        f" (shard {collection.shard})" if collection.shard else "",
        model_dir,
        host,
        port,
    )
    web.run_app(
        build_app(
            collection,
            rescan_interval=rescan_interval,
            coalesce_window_ms=coalesce_window_ms,
            coalesce_min_concurrency=coalesce_min_concurrency,
            coalesce_knee_batch=coalesce_knee_batch,
            warmup=warmup,
            health_rollup_interval=health_rollup_interval,
            reload_watch_interval=reload_watch_interval,
        ),
        host=host,
        port=port,
    )
