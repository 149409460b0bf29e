"""The backfill runner: fleet-scale historical scoring, no HTTP anywhere.

Reference status: absent upstream — the reference could only score
history by replaying requests through the latency-bound server.  This
plane is the Podracer-style decoupling (PAPERS.md): a dedicated bulk
path that drives the SAME fused, compile-plane-registered programs the
server dispatches, at the configured serving dtype, but feeds them
device-saturating stacked chunks instead of request payloads — large-
batch offline inference is where the hardware earns its keep (the
Gemma-on-TPU comparison, PAPERS.md).

Pipeline per chunk (the ``parallel/fleet`` stage/dispatch discipline —
host work for chunk N overlaps device work for chunk N+1):

1. dataset providers → per-machine frames over the backfill period
   (one fetch per distinct dataset fingerprint: replicated fleets share
   tags, so the host cost scales with distinct datasets, not machines);
2. time-windowed chunk slicing (``chunk_rows`` resolution steps per
   chunk — the deterministic plan resumability depends on);
3. ``FleetScorer.dispatch_all`` — the server's exact stacked bucket
   geometry, pack-backed staging, and jit registry, so archive bytes
   are fp32-identical to the online fused path over the same windows
   (pinned by test).  Dispatches run under
   ``telemetry.FLEET_HEALTH.suspended()``: historical scores must not
   masquerade as live traffic in the drift sketches;
4. while the device computes chunk N, chunk N-1 assembles and lands in
   the :class:`~gordo_tpu.batch.archive.ScoreArchive` (columnar mmap
   segments + completion records under ``.gordo-scores/``).

Resumability: completed chunks are skipped on re-run (the archive's
completion records are the ledger); a mid-run kill therefore costs one
chunk of work.  Sharding rides ``distributed.partition``'s one shard
function — ``--shard i/N`` (or the Indexed-Job env pair) scores a
disjoint machine subset into the same flock-shared archive.

Plane boundary (lint-gated): this package never imports
``serve.server``, the HTTP client, or any HTTP machinery — models load
straight from the artifact plane, data from providers, scores to disk.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from gordo_tpu import artifacts, telemetry
from gordo_tpu.batch.archive import ScoreArchive
from gordo_tpu.compile import load_warmup_manifest
from gordo_tpu.dataset import dataset_from_metadata
from gordo_tpu.ingest.fingerprint import provider_fingerprint
from gordo_tpu.serve import precision
from gordo_tpu.serve.shard import shard_slices
from gordo_tpu.serve.fleet_scorer import FleetScorer
from gordo_tpu.serve.scorer import refuse_sequence_model

logger = logging.getLogger(__name__)

# -- knobs (docs/configuration.md "Backfill plane") -------------------------
ENV_CHUNK_ROWS = "GORDO_BACKFILL_CHUNK_ROWS"
DEFAULT_CHUNK_ROWS = 2048
ENV_SHARD = "GORDO_BACKFILL_SHARD"
#: the Indexed-Job spelling: the generator maps JOB_COMPLETION_INDEX
#: into the index half, the shard count rides the job spec
ENV_SHARD_INDEX = "GORDO_BACKFILL_SHARD_INDEX"
ENV_NUM_SHARDS = "GORDO_BACKFILL_NUM_SHARDS"

# -- telemetry instruments (docs/observability.md) --------------------------
_CHUNKS_TOTAL = telemetry.counter(
    "gordo_backfill_chunks_total",
    "Backfill chunks handled, by outcome",
    labels=("outcome",),  # ok | skipped | empty | failed
)
_ROWS_TOTAL = telemetry.counter(
    "gordo_backfill_rows_total",
    "Scored rows written to the score archive",
)
_SAMPLES_TOTAL = telemetry.counter(
    "gordo_backfill_samples_total",
    "Scored samples (rows x tags) written to the score archive",
)
_SAMPLES_PER_SECOND = telemetry.gauge(
    "gordo_backfill_samples_per_second",
    "End-to-end archive-path scoring rate of the last backfill run",
)
_DEVICE_TRANSFERS = telemetry.counter(
    "gordo_backfill_device_transfers_total",
    "Stacked host->device chunk dispatches (one per bucket program per "
    "chunk — the device-transfer attestation bench reads)",
)
_CHUNK_OCCUPANCY = telemetry.histogram(
    "gordo_backfill_chunk_occupancy",
    "Fraction of a chunk's row window each machine actually had data "
    "for (1.0 = fully dense history)",
    buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
)
_MACHINES = telemetry.gauge(
    "gordo_backfill_machines",
    "Machines scored by the last backfill run (this shard)",
)


class BackfillError(RuntimeError):
    """A chunk failed mid-run.  The archive keeps every completed chunk's
    record, so a re-run resumes — the CLI maps this onto the shared
    resumable exit code (75)."""


def resolve_shard(spec: Optional[str] = None) -> Tuple[int, int]:
    """``(index, count)`` from an ``i/N`` spec, ``GORDO_BACKFILL_SHARD``,
    or the Indexed-Job env pair; ``(0, 1)`` unsharded."""
    spec = spec or os.environ.get(ENV_SHARD) or ""
    if not spec:
        n = os.environ.get(ENV_NUM_SHARDS, "")
        if n:
            spec = f"{os.environ.get(ENV_SHARD_INDEX, '0') or '0'}/{n}"
    if not spec:
        return (0, 1)
    idx_s, sep, n_s = spec.partition("/")
    try:
        idx, n = int(idx_s), int(n_s)
    except ValueError:
        raise ValueError(f"shard spec must be i/N, got {spec!r}")
    if not sep or not 0 <= idx < n:
        raise ValueError(f"shard spec must satisfy 0 <= i < N, got {spec!r}")
    return (idx, n)


@dataclasses.dataclass
class BackfillConfig:
    """One backfill invocation's wiring."""

    model_dir: str
    start: Any
    end: Any
    #: archive destination root; defaults to ``model_dir`` (the archive
    #: lands next to the artifacts it was scored with)
    archive_dir: Optional[str] = None
    project: str = "project"
    #: machine-name subset (None = every discovered machine)
    machines: Optional[Sequence[str]] = None
    #: ``i/N`` spec; None resolves env (Indexed Job) then unsharded
    shard: Optional[str] = None
    #: resolution steps per chunk; None resolves GORDO_BACKFILL_CHUNK_ROWS
    chunk_rows: Optional[int] = None
    #: stop after scoring this many NEW chunks (bounded runs / tests —
    #: remaining chunks stay resumable)
    max_chunks: Optional[int] = None
    mesh: Any = None


def _to_utc(value: Any) -> pd.Timestamp:
    ts = pd.Timestamp(value)
    return ts.tz_localize("UTC") if ts.tzinfo is None else ts


def chunk_windows(
    start: Any, end: Any, resolution: str, chunk_rows: int
) -> List[Tuple[pd.Timestamp, pd.Timestamp]]:
    """The deterministic chunk plan: half-open ``[t0, t1)`` windows of
    ``chunk_rows`` resolution steps covering ``[start, end)``.  Pure
    arithmetic over the period — every shard and every re-run computes
    the identical plan, which is what completion records key on."""
    start, end = _to_utc(start), _to_utc(end)
    if start >= end:
        raise ValueError(f"backfill start {start} must precede end {end}")
    step = pd.tseries.frequencies.to_offset(resolution).nanos * chunk_rows
    windows = []
    t = start.value
    while t < end.value:
        t1 = min(t + step, end.value)
        windows.append((
            pd.Timestamp(t, unit="ns", tz="UTC"),
            pd.Timestamp(t1, unit="ns", tz="UTC"),
        ))
        t = t1
    return windows


# Frames are shareable iff tags + resolution + provider match —
# replicated fleets collapse to one provider fetch.  The fingerprint
# definition was hoisted into the shared ingest plane (r24) so the
# builder, refresh, and batch planes cannot drift on what "same data"
# means.
_dataset_fingerprint = provider_fingerprint


def _load_fleet(
    cfg: BackfillConfig, shard: Tuple[int, int]
) -> Tuple[Any, List[Any]]:
    """Discover artifacts, filter to the requested subset, take this
    shard's slice with the ONE shard function (``serve.shard`` wrapping
    ``distributed.partition`` — so a backfill shard owns exactly the
    machines the same-index serving shard would)."""
    store, refs = artifacts.discover(cfg.model_dir, quarantine=True)
    if not refs:
        raise BackfillError(f"no artifacts under {cfg.model_dir}")
    if cfg.machines:
        wanted = set(cfg.machines)
        missing = wanted - {r.name for r in refs}
        if missing:
            raise BackfillError(
                f"machines not in the artifact fleet: {sorted(missing)}"
            )
        refs = [r for r in refs if r.name in wanted]
    refs = sorted(refs, key=lambda r: r.name)
    if shard[1] > 1:
        owned = set(
            shard_slices([r.name for r in refs], shard[1])[shard[0]]
        )
        refs = [r for r in refs if r.name in owned]
    return store, refs


def run_backfill(cfg: BackfillConfig) -> Dict[str, Any]:
    """Score ``[start, end)`` for this shard's fleet into the archive.

    Returns a summary dict (the CLI prints it as JSON).  ``remaining``
    > 0 means the run is resumable rather than complete (``max_chunks``
    bound hit); a chunk failure raises :class:`BackfillError` and leaves
    every completed chunk's record durable."""
    t_run = time.perf_counter()
    shard = resolve_shard(cfg.shard)
    chunk_rows = int(
        cfg.chunk_rows
        if cfg.chunk_rows is not None
        else os.environ.get(ENV_CHUNK_ROWS, "") or DEFAULT_CHUNK_ROWS
    )
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")

    store, refs = _load_fleet(cfg, shard)
    names = [r.name for r in refs]
    _MACHINES.set(float(len(names)))
    logger.info(
        "backfill shard %d/%d: %d machine(s), %s -> %s",
        shard[0], shard[1], len(names), cfg.start, cfg.end,
    )

    # models + metadata at the serving precision (the server's exact
    # resolution order: env > warmup-manifest dtype > float32)
    models = {r.name: r.load_model() for r in refs}
    for name, model in models.items():
        refuse_sequence_model(model, name, "the backfill runner")
    metas = {r.name: (r.load_metadata() or {}) for r in refs}
    manifest_dtype = (load_warmup_manifest(cfg.model_dir) or {}).get("dtype")
    dtype = precision.serve_dtype(default=manifest_dtype)
    scorer = FleetScorer.from_models(
        models, mesh=cfg.mesh, pack_store=store, dtype=dtype
    )

    # one provider fetch per distinct dataset fingerprint
    frames: Dict[str, pd.DataFrame] = {}
    by_fp: Dict[str, pd.DataFrame] = {}
    tags_of: Dict[str, List[str]] = {}
    resolutions: Dict[str, int] = {}
    for name in names:
        dataset_meta = metas[name].get("dataset") or {}
        fp = _dataset_fingerprint(dataset_meta)
        if fp not in by_fp:
            dataset = dataset_from_metadata(dataset_meta, cfg.start, cfg.end)
            X, _ = dataset.get_data()
            by_fp[fp] = X
        frames[name] = by_fp[fp]
        tags_of[name] = list(frames[name].columns)
        res = dataset_meta.get("resolution", "10min")
        resolutions[res] = resolutions.get(res, 0) + 1
    # the plan resolution: the fleet's most common (ties break stably);
    # machines at other resolutions still slice correctly by timestamp,
    # their occupancy just reads off-unity
    resolution = max(sorted(resolutions), key=lambda r: resolutions[r])

    windows = chunk_windows(cfg.start, cfg.end, resolution, chunk_rows)
    archive = ScoreArchive.create(
        cfg.archive_dir or cfg.model_dir,
        project=cfg.project,
        start=str(_to_utc(cfg.start)),
        end=str(_to_utc(cfg.end)),
        resolution=resolution,
        chunk_rows=chunk_rows,
        n_chunks=len(windows),
        dtype=dtype,
        machines=names,
        shard=shard,
    )
    done = archive.completed_chunks(shard[0])

    counts = {"ok": 0, "skipped": 0, "empty": 0, "short": 0}
    rows_written = 0
    samples = 0
    transfers = 0

    def finalize(ci: int, disp, idx_by: Dict[str, pd.Index]) -> None:
        nonlocal rows_written, samples
        with telemetry.FLEET_HEALTH.suspended():
            results = disp.assemble()
        per_machine: Dict[str, Dict[str, Any]] = {}
        for name, res in results.items():
            if "error" in res:
                # short windows (rows <= the model's lookback offset)
                # are a property of the chunk boundary, not a failure
                counts["short"] += 1
                continue
            total = np.asarray(res["total-anomaly-score"], np.float32)
            tag_scores = np.asarray(res["tag-anomaly-scores"], np.float32)
            idx = idx_by[name]
            # scored rows = input rows - the model's lookback offset;
            # derive from output length so the two can never diverge
            ts = idx[len(idx) - len(total):]
            per_machine[name] = {
                "index-ns": ts.as_unit("ns").asi8
                if ts.unit != "ns" else ts.asi8,
                "total-anomaly-score": total,
                "tag-anomaly-scores": tag_scores,
                "tags": tags_of[name],
            }
            rows_written += len(total)
            samples += int(tag_scores.size)
            _CHUNK_OCCUPANCY.observe(min(1.0, len(idx) / chunk_rows))
        archive.write_chunk(ci, per_machine, shard=shard[0])
        _ROWS_TOTAL.inc(float(sum(
            len(r["total-anomaly-score"]) for r in per_machine.values()
        )))
        _CHUNKS_TOTAL.inc(1.0, "ok" if per_machine else "empty")
        counts["ok" if per_machine else "empty"] += 1

    pending: Optional[Tuple[int, Any, Dict[str, pd.Index]]] = None
    scored_new = 0
    remaining = 0
    try:
        for ci, (t0, t1) in enumerate(windows):
            if ci in done:
                _CHUNKS_TOTAL.inc(1.0, "skipped")
                counts["skipped"] += 1
                continue
            if cfg.max_chunks is not None and scored_new >= cfg.max_chunks:
                remaining += 1
                continue
            X_by: Dict[str, np.ndarray] = {}
            idx_by: Dict[str, pd.Index] = {}
            for name, X in frames.items():
                lo = X.index.searchsorted(t0)
                hi = X.index.searchsorted(t1)
                if hi > lo:
                    window = X.iloc[lo:hi]
                    X_by[name] = window.to_numpy(np.float32)
                    idx_by[name] = window.index
            scored_new += 1
            if not X_by:
                archive.write_chunk(ci, {}, shard=shard[0])
                _CHUNKS_TOTAL.inc(1.0, "empty")
                counts["empty"] += 1
                continue
            # dispatch chunk N, then archive chunk N-1 while the device
            # runs — the fleet_stage/fleet_dispatch overlap discipline
            with telemetry.FLEET_HEALTH.suspended():
                disp = scorer.dispatch_all(X_by)
            n_disp = disp.n_device_dispatches
            transfers += n_disp
            _DEVICE_TRANSFERS.inc(float(n_disp))
            if pending is not None:
                finalize(*pending)
            pending = (ci, disp, idx_by)
        if pending is not None:
            finalize(*pending)
            pending = None
    except (ArithmeticError, OSError, RuntimeError, ValueError) as exc:
        _CHUNKS_TOTAL.inc(1.0, "failed")
        raise BackfillError(
            f"backfill failed mid-run ({counts['ok']} chunk(s) archived "
            f"and durable; re-run to resume): {exc}"
        ) from exc

    elapsed = time.perf_counter() - t_run
    rate = samples / elapsed if elapsed > 0 else 0.0
    _SAMPLES_TOTAL.inc(float(samples))
    _SAMPLES_PER_SECOND.set(rate)
    summary = {
        "project": cfg.project,
        "archive": archive.directory,
        "shard": f"{shard[0]}/{shard[1]}",
        "machines": len(names),
        "dtype": dtype,
        "resolution": resolution,
        "chunk-rows": chunk_rows,
        "chunks": len(windows),
        "chunks-ok": counts["ok"],
        "chunks-skipped": counts["skipped"],
        "chunks-empty": counts["empty"],
        "short-windows": counts["short"],
        "remaining": remaining,
        "rows": rows_written,
        "samples": samples,
        "seconds": round(elapsed, 3),
        "samples-per-second": round(rate, 1),
        "device-transfers": transfers,
    }
    logger.info("backfill summary: %s", summary)
    return summary
