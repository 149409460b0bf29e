"""Fleet-vectorized chunk ingest: one columnar pass instead of 512.

:func:`load_chunk` assembles a whole builder chunk's ``(X, y, metadata)``
entries at once:

1. **Fetch dedup** — machines are partitioned by
   :func:`~gordo_tpu.ingest.fingerprint.dataset_fingerprint`; each
   distinct fingerprint fetches and assembles ONCE, duplicates copy the
   leader's stacked slot (one float32 memcpy) and deep-copy its
   metadata.
2. **Columnar assembly** — fingerprints whose fetched series share one
   index geometry (equal timestamps, same resolution) resample and join
   as ONE ``np.add.reduceat`` pass over a ``(rows, Σtags)`` float64
   matrix — the per-machine fast path of
   :meth:`TimeSeriesDataset._resample_one_arrays` extended across the
   machine axis, using the same :func:`resample_prep` geometry so the
   two cannot drift.
3. **Stacked handoff** — results land directly in a preallocated
   ``(m_pad, n, tags)`` float32 buffer (capacity from the dispatch
   plane's model-axis padding); per-machine ``X``/``y`` are views of it,
   and ``FleetDiffBuilder`` adopts the buffer without re-stacking
   (``_stack_machine_axis`` / in-place model padding in
   ``gordo_tpu/parallel/anomaly.py``).

Anything the columnar pass cannot express — row filters, non-mean
aggregation, targets != inputs, ragged per-tag indexes, subclassed
assembly — takes :func:`_load_fallback`, the sanctioned per-machine
``dataset.get_data()`` path.  Both paths produce byte-identical arrays
and metadata (pinned by tests/test_ingest.py).

scripts/lint.py bans per-machine pandas verbs (``.resample(...)``,
``pd.concat``, ``pd.DataFrame``) in this module outside the sanctioned
fallback — the hot path must stay columnar numpy.
"""

from __future__ import annotations

import contextlib
import copy
import logging
import time
import weakref
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from gordo_tpu import telemetry
from gordo_tpu.dataset.base import GordoBaseDataset
from gordo_tpu.dataset.datasets import (
    InsufficientDataError,
    TimeSeriesDataset,
    resample_prep,
    summary_statistics_arrays,
)
from gordo_tpu.ingest.fingerprint import dataset_fingerprint

logger = logging.getLogger(__name__)

# -- telemetry instruments (docs/observability.md) --------------------------
_FETCH_TOTAL = telemetry.counter(
    "gordo_ingest_fetch_total",
    "Provider fetches by the fleet ingest plane, by outcome "
    "(fetched: one provider pull; deduped: shared a fingerprint-equal "
    "machine's fetch)",
    labels=("path",),
)
DEDUP_HITS_TOTAL = telemetry.counter(
    "gordo_build_ingest_dedup_hits_total",
    "Machines whose dataset fetch was satisfied by another machine with "
    "an identical dataset fingerprint (one fetch per distinct "
    "fingerprint — see gordo_tpu/ingest/fingerprint.py)",
)
_MACHINES_TOTAL = telemetry.counter(
    "gordo_ingest_machines_total",
    "Machines assembled by the fleet ingest plane, by path "
    "(vectorized: columnar cross-machine pass; fallback: sanctioned "
    "per-machine get_data; deduped: slot-copied from a fingerprint twin)",
    labels=("path",),
)
_STAGE_SECONDS = telemetry.histogram(
    "gordo_ingest_stage_seconds",
    "Busy seconds per ingest-plane stage (fetch: one fingerprint's "
    "provider pull; resample: one geometry group's columnar pass; "
    "assemble: stacked-buffer fill; finalize: stats + metadata; "
    "fallback: one per-machine get_data)",
    labels=("stage",),
)


#: the stages are spans inside the builder's ``gordo.build.load`` span of
#: the chunk: that one opens before a profiler session that starts at a
#: chunk completion (the benchmark's traced window) and is then never
#: recorded, these begin inside it
SPAN_PREFIX = "gordo.build.load."


@contextlib.contextmanager
def _stage(name: str) -> Iterator[Dict[str, Any]]:
    """One unit of an ingest stage: the span ``gordo.build.load.<name>``
    and its seconds in ``gordo_ingest_stage_seconds{stage=name}``."""
    with telemetry.span(SPAN_PREFIX + name) as sp:
        yield sp
    _STAGE_SECONDS.observe(sp.get("seconds", 0.0), name)


# -- stacked-buffer ownership ----------------------------------------------
# The dispatch plane may adopt (and pad in place) ONLY buffers this plane
# allocated — a registry of live base arrays makes the mutation provably
# sanctioned instead of inferred from view geometry alone.
_STACK_BASES: Dict[int, Any] = {}


def _register_stack(base: np.ndarray, live_slots: int = 0) -> None:
    key = id(base)
    ref = weakref.ref(
        base, lambda _ref, _key=key: _STACK_BASES.pop(_key, None)
    )
    _STACK_BASES[key] = [ref, int(live_slots)]


def _set_live_slots(base: np.ndarray, live_slots: int) -> None:
    entry = _STACK_BASES.get(id(base))
    if entry is not None:
        entry[1] = int(live_slots)


def owned_stack_base(arr: np.ndarray) -> Optional[np.ndarray]:
    """The ingest-owned stacked buffer ``arr`` is a view of, or None."""
    base = getattr(arr, "base", None)
    if base is None:
        return None
    entry = _STACK_BASES.get(id(base))
    if entry is None or entry[0]() is not base:
        return None
    return base


def stack_live_slots(base: np.ndarray) -> int:
    """Machine slots of an ingest-owned buffer holding real data; rows at
    and past this index are scratch the dispatch plane may fill with
    model-axis padding in place."""
    entry = _STACK_BASES.get(id(base))
    return entry[1] if entry is not None else 0


# -- the sanctioned per-machine fallback ------------------------------------

def _load_fallback(dataset, align_lengths: Optional[int]):
    """Per-machine ``get_data()`` — the same work the pre-ingest builder
    did per machine, kept as the escape hatch for everything the
    columnar pass cannot express (byte-identical output either way)."""
    t0 = time.time()  # load seconds are artifact metadata, not telemetry
    with _stage("fallback"):
        X, y = dataset.get_data()
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        if align_lengths and len(X) >= align_lengths:
            keep = (len(X) // align_lengths) * align_lengths
            # newest rows win (mirrors the builder's truncation)
            X, y = X[len(X) - keep:], y[len(y) - keep:]
    dt = time.time() - t0
    _MACHINES_TOTAL.inc(1.0, "fallback")
    return (X, y, dataset.get_metadata(), dt)


def _vectorizable(dataset) -> bool:
    """Whether the columnar cross-machine pass can express this dataset
    exactly: stock TimeSeriesDataset assembly (subclasses overriding it
    fall back), mean aggregation, no row filter, targets == inputs,
    unique tag names."""
    if not isinstance(dataset, TimeSeriesDataset):
        return False
    cls = type(dataset)
    if (
        cls.get_data is not TimeSeriesDataset.get_data
        or cls._join_timeseries is not TimeSeriesDataset._join_timeseries
        or cls._resample_one_arrays
        is not TimeSeriesDataset._resample_one_arrays
    ):
        return False
    if dataset.aggregation_methods != "mean" or dataset.row_filter:
        return False
    if dataset.target_tag_list != dataset.tag_list:
        return False
    names = [t.name for t in dataset.tag_list]
    return bool(names) and len(set(names)) == len(names)


# -- vectorized assembly ----------------------------------------------------

class _FpGroup:
    """One distinct dataset fingerprint: the leader dataset, every machine
    name sharing it, and (once fetched) the shared raw arrays."""

    __slots__ = (
        "fp", "dataset", "names", "index", "idx_ns", "values", "nanos",
        "col0", "keep", "n_rows", "offset", "meta", "error", "slots",
    )

    def __init__(self, fp: str, dataset) -> None:
        self.fp = fp
        self.dataset = dataset
        self.names: List[str] = []
        self.index = None          # shared pd.DatetimeIndex
        self.idx_ns = None         # its int64 ns view
        self.values = None         # (n_raw, T) float64
        self.nanos = 0
        self.col0 = 0              # column offset in the geometry matrix
        self.keep = None           # joined-row mask on the bin grid
        self.n_rows = 0            # rows after join (== after filter)
        self.offset = 0            # head rows dropped by align_lengths
        self.meta: Optional[Dict[str, Any]] = None
        self.error: Optional[Exception] = None
        self.slots: List[Tuple[str, int]] = []  # (machine name, slot)


def _fetch_group(g: _FpGroup) -> bool:
    """Provider fetch for one fingerprint: array-grain when the provider
    supports it, else per-tag series flattened to one matrix.  Returns
    False (no exception) when the fetched shape disqualifies the
    vectorized path — the caller reroutes the group to the fallback."""
    ds = g.dataset
    with _stage("fetch"):
        tags = ds.tag_list  # targets == inputs (checked by _vectorizable)
        fetched = ds.data_provider.load_arrays(
            ds.train_start_date, ds.train_end_date, tags
        )
        if fetched is None:
            series_list = list(
                ds.data_provider.load_series(
                    ds.train_start_date, ds.train_end_date, tags
                )
            )
            if len(series_list) != len(tags) or not all(
                len(s) and (
                    s.index is series_list[0].index
                    or s.index.equals(series_list[0].index)
                )
                for s in series_list
            ):
                return False
            index = series_list[0].index
            values = np.column_stack(
                [s.to_numpy(dtype=np.float64, copy=False) for s in series_list]
            )
        else:
            index, values = fetched
        _FETCH_TOTAL.inc(1.0, "fetched")
    if (
        len(index) == 0
        or str(index.tz) != "UTC"
        or not index.is_monotonic_increasing
    ):
        return False
    try:
        g.nanos = pd.tseries.frequencies.to_offset(ds.resolution).nanos
    except ValueError:  # non-fixed frequency — pandas path territory
        return False
    g.index = index
    g.idx_ns = index.asi8 if index.unit == "ns" else index.as_unit("ns").asi8
    g.values = values
    return True


def _assemble_geometry_group(
    groups: List[_FpGroup],
    prep: Tuple[np.ndarray, int, np.ndarray, pd.DatetimeIndex],
    align_lengths: Optional[int],
    capacity: Optional[Callable[[int], int]],
    out: Dict[str, Any],
) -> None:
    """One shared-index geometry group end to end: columnar resample,
    per-fingerprint join mask + threshold, stacked-buffer fill, stats and
    metadata — no per-machine pandas anywhere."""
    starts, grid_size, scatter, _label = prep
    with _stage("resample"):
        if len(groups) == 1:
            V = groups[0].values
        else:
            V = np.concatenate([g.values for g in groups], axis=1)
        col = 0
        for g in groups:
            g.col0 = col
            col += g.values.shape[1]
        # the machine-axis extension of _resample_one_arrays: one reduceat
        # over every tag of every machine in the group (bit-identical per
        # column — reduction order along axis 0 is the per-tag order)
        nan_mask = np.isnan(V)
        had_nan = bool(nan_mask.any())
        if had_nan:
            sums = np.add.reduceat(np.where(nan_mask, 0.0, V), starts, axis=0)
            valid = np.add.reduceat((~nan_mask).astype(np.int64), starts, axis=0)
            means = np.divide(
                sums, valid, out=np.full(sums.shape, np.nan), where=valid > 0
            )
        else:
            # NaN-free input: the where-copy and the int64 count pass drop
            # out; sums/counts divides the identical float64 operands, so
            # the quotient bits match the masked-divide branch exactly
            sums = np.add.reduceat(V, starts, axis=0)
            counts = np.diff(np.append(starts, V.shape[0]))
            means = sums / counts[:, None]
        if len(starts) == grid_size:
            # occupied bins are strictly increasing, so covering every bin
            # means scatter is the identity — the grid IS the means matrix
            grid = means
            clean = not had_nan
        else:
            grid = np.full((grid_size, col), np.nan)
            grid[scatter] = means
            clean = False

    # join mask + n_samples_threshold per fingerprint.  A clean group
    # (NaN-free input, every bin occupied) has no NaN anywhere in the
    # grid: every fingerprint keeps every row, no per-fp isnan scans.
    alive: List[_FpGroup] = []
    for g in groups:
        if clean:
            g.keep = None
            g.n_rows = grid_size
        else:
            sub = grid[:, g.col0 : g.col0 + g.values.shape[1]]
            g.keep = ~np.isnan(sub).any(axis=1)
            g.n_rows = int(g.keep.sum())
        ds = g.dataset
        if g.n_rows < max(ds.n_samples_threshold, 1):
            g.error = InsufficientDataError(
                f"Only {g.n_rows} rows after filtering "
                f"(threshold {ds.n_samples_threshold}) for period "
                f"{ds.train_start_date} → {ds.train_end_date}"
            )
            for name in g.names:
                out[name] = g.error
            continue
        g.offset = 0
        if align_lengths and g.n_rows >= align_lengths:
            g.offset = g.n_rows - (g.n_rows // align_lengths) * align_lengths
        alive.append(g)

    # clean group: every fingerprint's stats matrix is a column slice of
    # the one grid — four whole-grid reductions replace 4 x len(groups)
    # per-fingerprint ones (numpy's axis-0 reduction accumulates row by
    # row, so each column's result is bit-identical either way)
    grid_stats = None
    if clean and len(alive) > 1:
        with _stage("finalize"):
            with np.errstate(all="ignore"):
                import warnings

                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", category=RuntimeWarning)
                    grid_stats = (
                        np.nanmean(grid, axis=0),
                        np.nanstd(grid, axis=0, ddof=1),
                        np.nanmin(grid, axis=0),
                        np.nanmax(grid, axis=0),
                    )

    # stacked buffers: one per (final row count, tag count) subgroup;
    # every machine (dups included) gets its own slot so the dispatch
    # plane sees consecutive leading-axis views of one base
    with _stage("assemble"):
        by_shape: Dict[Tuple[int, int], List[_FpGroup]] = {}
        for g in alive:
            shape = (g.n_rows - g.offset, g.values.shape[1])
            by_shape.setdefault(shape, []).append(g)
        for (n_final, n_tags), members in by_shape.items():
            m_total = sum(len(g.names) for g in members)
            cap = max(capacity(m_total) if capacity else m_total, m_total)
            base = np.empty((cap, n_final, n_tags), dtype=np.float32)
            _register_stack(base)
            slot = 0
            for g in members:
                sub = grid[:, g.col0 : g.col0 + g.values.shape[1]]
                d64 = sub if g.n_rows == grid_size else sub[g.keep]
                base[slot] = d64[g.offset:] if g.offset else d64
                g.slots = [(g.names[0], slot)]
                lead = slot
                slot += 1
                for dup in g.names[1:]:
                    base[slot] = base[lead]  # fingerprint twin: one memcpy
                    g.slots.append((dup, slot))
                    slot += 1
                # stats/metadata read the pre-truncation float64 rows, exactly
                # like the per-machine path (align truncation happens in the
                # builder AFTER get_data there)
                stats_dict = None
                if grid_stats is not None:
                    smean, sstd, smin, smax = grid_stats
                    stats_dict = {
                        t.name: {
                            "mean": float(smean[g.col0 + k]),
                            "std": float(sstd[g.col0 + k]),
                            "min": float(smin[g.col0 + k]),
                            "max": float(smax[g.col0 + k]),
                        }
                        for k, t in enumerate(g.dataset.tag_list)
                    }
                g.meta = _group_metadata(g, d64, grid_size, stats_dict)
                for i, (name, s) in enumerate(g.slots):
                    X = base[s]
                    meta = g.meta if i == 0 else copy.deepcopy(g.meta)
                    out[name] = (X, X, meta, 0.0)
                    _MACHINES_TOTAL.inc(1.0, "vectorized" if i == 0 else "deduped")
            _set_live_slots(base, slot)


def _group_metadata(
    g: _FpGroup,
    d64: np.ndarray,
    grid_size: int,
    stats: Optional[Dict[str, Dict[str, float]]] = None,
) -> Dict[str, Any]:
    """The exact metadata dict ``get_data`` + ``get_metadata`` would
    record for this fingerprint (same keys, same insertion order — the
    metadata JSON is a byte-parity artifact)."""
    with _stage("finalize"):
        ds = g.dataset
        n_raw = len(g.index)
        names = [t.name for t in ds.tag_list]
        meta: Dict[str, Any] = {
            "tag_loading_metadata": {
                name: {
                    "original_length": int(n_raw),
                    "resampled_length": int(grid_size),
                }
                for name in names
            },
            "train_start_date": str(ds.train_start_date),
            "train_end_date": str(ds.train_end_date),
            "resolution": ds.resolution,
            "row_filter": ds.row_filter,
            "rows_after_join": int(g.n_rows),
            "rows_after_filter": int(g.n_rows),
            "filtered_periods": 0,
            "tag_list": [t.to_json() for t in ds.tag_list],
            "target_tag_list": [t.to_json() for t in ds.target_tag_list],
            "data_provider": ds.data_provider.to_dict(),
            "summary_statistics": (
                stats
                if stats is not None
                else summary_statistics_arrays(d64, names)
            ),
        }
    return meta


def load_chunk(
    machines: Sequence[Any],
    align_lengths: Optional[int] = None,
    capacity: Optional[Callable[[int], int]] = None,
    stats: Optional[Dict[str, int]] = None,
) -> Dict[str, Any]:
    """Assemble one builder chunk: ``{machine name: (X, y, metadata,
    load_seconds) | Exception}``.

    ``machines`` are Machine-likes (``.name``, ``.dataset`` config
    mapping).  ``capacity(m)`` maps a stacked subgroup's machine count to
    its buffer capacity (the builder passes the dispatch plane's
    model-axis padding so the buffer IS the ``(m_pad, n, tags)`` array
    the fleet program stages).  ``stats`` (optional dict) accumulates
    ``machines / vectorized / deduped / fallback / fetches`` counts for
    build-result reporting.  Failures are per-machine values, never
    raises — exactly like the per-machine loader pool it replaces."""
    t_chunk = time.time()
    out: Dict[str, Any] = {}
    by_fp: Dict[str, _FpGroup] = {}
    order: List[_FpGroup] = []
    fallback: List[Tuple[str, Any]] = []  # (name, dataset)

    for m in machines:
        cfg = dict(m.dataset)
        try:
            fp = dataset_fingerprint(cfg)
            g = by_fp.get(fp)
            if g is not None:
                g.names.append(m.name)
                DEDUP_HITS_TOTAL.inc(1.0)
                _FETCH_TOTAL.inc(1.0, "deduped")
                continue
            dataset = GordoBaseDataset.from_dict(cfg)
        except Exception as exc:
            out[m.name] = exc
            continue
        g = _FpGroup(fp, dataset)
        g.names.append(m.name)
        by_fp[fp] = g
        order.append(g)

    # fetch vectorizable fingerprints; everything else → fallback
    geometry: Dict[Tuple, List[_FpGroup]] = {}
    for g in order:
        ok = False
        if _vectorizable(g.dataset):
            try:
                ok = _fetch_group(g)
            except Exception as exc:
                g.error = exc
                for name in g.names:
                    out[name] = exc
                continue
        if not ok:
            fallback.append((g.names[0], g.dataset))
            for dup in g.names[1:]:
                fallback.append((dup, None))  # share the leader's entry
            continue
        key = (
            len(g.idx_ns), int(g.idx_ns[0]), int(g.idx_ns[-1]), g.nanos,
            g.index.name,
        )
        # content-verified grouping: equal endpoints but different interior
        # timestamps must not share binning geometry
        bucket = geometry.setdefault(key, [])
        while bucket and not np.array_equal(bucket[0].idx_ns, g.idx_ns):
            key = key + ("'",)
            bucket = geometry.setdefault(key, [])
        bucket.append(g)

    for groups in geometry.values():
        ref = groups[0]
        prep = resample_prep(ref.index, ref.nanos)
        try:
            _assemble_geometry_group(
                groups, prep, align_lengths, capacity, out
            )
        except Exception:
            logger.exception(
                "vectorized ingest failed for %d fingerprint group(s); "
                "falling back per machine", len(groups),
            )
            for g in groups:
                if g.names and g.names[0] not in out:
                    fallback.append((g.names[0], g.dataset))
                    for dup in g.names[1:]:
                        fallback.append((dup, None))

    # the sanctioned per-machine path (+ fingerprint-shared entries)
    shared: Dict[str, str] = {}  # dup name -> leader name (fallback dups)
    last_leader: Optional[str] = None
    for name, dataset in fallback:
        if dataset is None:
            shared[name] = last_leader
            continue
        last_leader = name
        try:
            out[name] = _load_fallback(dataset, align_lengths)
        except Exception as exc:
            out[name] = exc
    for dup, leader in shared.items():
        src = out.get(leader)
        if src is None or isinstance(src, Exception):
            out[dup] = src if src is not None else RuntimeError(
                f"fingerprint leader {leader} produced no entry"
            )
        else:
            X, y, meta, _secs = src
            out[dup] = (X, y, copy.deepcopy(meta), 0.0)
            _MACHINES_TOTAL.inc(1.0, "deduped")

    # attribute load seconds evenly across the chunk's successful entries
    # (wall-clock only — data_query_duration_sec is volatile metadata)
    dt = time.time() - t_chunk
    good = [n for n, e in out.items() if not isinstance(e, Exception)]
    share = dt / max(len(good), 1)
    for n in good:
        X, y, meta, secs = out[n]
        out[n] = (X, y, meta, secs or share)

    if stats is not None:
        n_dups = sum(len(g.names) - 1 for g in order)
        stats["machines"] = stats.get("machines", 0) + len(list(machines))
        stats["dedup_hits"] = stats.get("dedup_hits", 0) + n_dups
        stats["fetches"] = stats.get("fetches", 0) + len(order)
        n_fallback = len([1 for _n, d in fallback if d is not None])
        stats["fallback"] = stats.get("fallback", 0) + n_fallback
        stats["vectorized"] = (
            stats.get("vectorized", 0)
            + sum(1 for g in order if g.slots and g.error is None)
        )
    return out
