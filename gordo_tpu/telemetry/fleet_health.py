"""Fleet health plane: per-machine score-distribution sketches and drift.

Reference status: absent upstream — the reference could say whether a
*server* was up (watchman's health poll) but nothing about the *fleet
under observation*: which of 10k machines are drifting away from their
training-time behavior, scoring hot, or silently receiving no traffic.
This module is the observability layer ROADMAP item 3 (drift-driven
incremental rebuilds) is blocked on: scoring feeds a per-machine
streaming sketch, the build plane records the same sketch over the
training residuals, and the distance between the two IS the drift
signal `gordo refresh` will consume.

Design constraints, in priority order:

- **Near-zero hot-path cost.**  Recording accumulates from the response
  arrays the serve path has ALREADY fetched to host (no extra D2H): one
  vectorized ``searchsorted`` + ``bincount`` over the request's total
  anomaly scores, a few float adds, under a per-sketch lock.  The
  ``GORDO_TELEMETRY=off`` kill switch applies, and
  ``bench.py --stage health_overhead`` holds the recording path within
  the existing <= 2% telemetry budget.
- **Exactly mergeable.**  Sketches are fixed log-scale bucket counts
  plus plain sums — shard A + shard B is integer/float addition, so a
  fleet-sharded tier's per-replica health docs merge into the SAME doc
  a single process serving the whole fleet would produce (modulo
  timestamps; the bench pins this byte-equivalence).  Associativity and
  commutativity are pinned by tests.
- **Order-invariant drift.**  The drift score is computed from bucket
  counts only (a Hellinger distance between the normalized baseline and
  live distributions), never from order-sensitive state like the EWMA —
  resorting the request stream cannot change it.

Surfaces: ``gordo_machine_*`` / ``gordo_machine_drift`` gauges (top-K by
drift, so exposition cardinality stays bounded on a 10k-machine fleet),
the full per-machine doc at ``GET /gordo/v0/<project>/fleet-health``,
periodic JSONL rollups under the artifact dir (the file interface a
``gordo refresh`` loop consumes without HTTP), and watchman's
``GET /fleet-health`` merging every shard's doc into one fleet view.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence

import numpy as np

from gordo_tpu.telemetry import metrics
from gordo_tpu.telemetry.rotate import append_jsonl_line

logger = logging.getLogger(__name__)

#: bump when the bucket layout below changes — sketches only merge and
#: only compare within one edges version (a mixed pair raises)
EDGES_VERSION = 1

#: fixed log-scale bucket edges for anomaly scores: HALF-OCTAVE buckets
#: (edges ``2^e`` and ``1.5 * 2^e`` for e in -10..9) spanning ~1e-3 to
#: 1024.  Half-octaves are chosen so the bucket index of a float32
#: score is a pure bit extraction — ``(bits >> 22) - offset`` (the
#: exponent plus the top mantissa bit; the raw bit pattern of a
#: positive float is monotone in its value) — which costs ~10us per
#: 2048-score response where a binary-search ``searchsorted`` cost ~30:
#: the difference between fitting the <= 2% serving budget and not.
#: Bit-extracted indices agree EXACTLY with
#: ``searchsorted(EDGES, x, side="right")`` on these edges (pinned by
#: test), and identical edges everywhere make build-time baselines,
#: live shards, and watchman merges exactly comparable.  Scores are
#: non-negative L2 magnitudes; zeros/denormals land in the underflow
#: slot, NaN/inf (a blown-up model is a distribution shift too) in
#: overflow.
N_BUCKETS = 40
EDGES = np.asarray(
    [v * 2.0 ** e for e in range(-10, 10) for v in (1.0, 1.5)]
    + [2.0 ** 10]
)

#: ``float32 bits >> 22`` of the lowest in-range edge (2^-10): the
#: offset turning raw half-octave indices into count slots
_RAW_LO = (127 - 10) << 1

#: counts layout: [underflow] + N_BUCKETS bins + [overflow]
N_SLOTS = N_BUCKETS + 2

#: EWMA smoothing for the per-machine score level (one update per
#: recorded response, on the response's mean score): recent-window
#: signal for the ``gordo_machine_score_ewma_mean`` gauge.  The drift
#: score NEVER reads it (order-sensitive by construction).
EWMA_ALPHA = 0.1

#: minimum observations BOTH sides need before a drift score is
#: computed: the Hellinger distance between a finite sample and its own
#: source distribution is positively biased ~sqrt(B/8n) (B occupied
#: buckets, n samples), so a 64-row live window against a 2048-row
#: baseline reads ~0.3 of pure sampling noise.  At 128+ scores the bias
#: sits well under the 0.25 flag threshold; until then the doc reports
#: drift=null rather than an arithmetically-true, operationally-false
#: number.
MIN_DRIFT_COUNT = 128

ENV_DRIFT_THRESHOLD = "GORDO_DRIFT_THRESHOLD"
ENV_DRIFT_TOP_K = "GORDO_DRIFT_TOP_K"
ENV_BASELINE = "GORDO_FLEET_BASELINE"
ENV_ROLLUP_MAX_BYTES = "GORDO_HEALTH_ROLLUP_MAX_BYTES"

#: directory (under a build output / artifact dir) where serving
#: processes append their periodic fleet-health rollup lines
ROLLUP_DIR = ".gordo-fleet-health"

#: default rollup file size cap before rotation (keep last 2 files)
DEFAULT_ROLLUP_MAX_BYTES = 16 * 1024 * 1024

#: metadata key the builder records the training-time baseline under
#: (``metadata["fleet-health"]["baseline"]`` = a sketch doc)
METADATA_KEY = "fleet-health"

#: training rows the baseline sketch sees, taken from the TAIL of the
#: training matrix (most recent regime): enough samples for a stable
#: 48-bucket distribution while bounding the builder's extra scoring
#: dispatch — one stacked forward pass per trained chunk, ~a bulk
#: serving round, against epochs of fwd+bwd the chunk just paid
BASELINE_MAX_ROWS = 2048


def drift_threshold() -> float:
    """Drift score above which a machine is flagged ``drifting`` (the
    Hellinger distance is bounded [0, 1]; 0.25 flags a distribution
    whose mass visibly moved across buckets while tolerating sampling
    noise on thin live windows)."""
    try:
        return float(os.environ.get(ENV_DRIFT_THRESHOLD, "") or 0.25)
    except ValueError:
        return 0.25


def drift_top_k() -> int:
    """How many machines the drift gauges export (exposition cardinality
    bound; the full set is always available via ``/fleet-health``)."""
    try:
        return int(os.environ.get(ENV_DRIFT_TOP_K, "") or 10)
    except ValueError:
        return 10


def baselines_enabled() -> bool:
    """``GORDO_FLEET_BASELINE=off`` skips the builder's training-time
    baseline sketch (the drift signal then has nothing to compare
    against — serving still sketches live scores)."""
    return os.environ.get(ENV_BASELINE, "").strip().lower() not in (
        "off", "0", "false", "disabled",
    )


class ScoreSketch:
    """Streaming sketch of one machine's anomaly-score distribution.

    Fixed log-scale bucket counts (mergeable by addition), exact
    count/sum/sum-of-squares (mergeable by addition), an EWMA of
    per-response mean scores (recent-level signal; NOT merged by
    addition — the later-seen side wins), and a last-seen timestamp.
    Thread-safe: serving records from executor threads.
    """

    __slots__ = (
        "counts", "count", "sum", "sum_sq",
        "ewma_mean", "ewma_var", "last_seen", "_lock",
    )

    def __init__(self):
        self.counts = np.zeros(N_SLOTS, dtype=np.int64)
        self.count = 0
        self.sum = 0.0
        self.sum_sq = 0.0
        self.ewma_mean: Optional[float] = None
        self.ewma_var = 0.0
        self.last_seen = 0.0
        self._lock = threading.Lock()

    def observe(self, scores: Any, ts: Optional[float] = None) -> None:
        """Fold one response's total-anomaly-score array in.  Host
        arrays only — the caller already holds the encoded response, so
        this adds no D2H and, for f32 serving outputs, no float copy:
        the bucket index is extracted straight from the float32 bit
        patterns (see EDGES), then one bincount, one f64 sum and one
        BLAS dot.  ~15us per 2048-score response."""
        flat = np.asarray(scores)
        if flat.dtype != np.float32 or not flat.flags.c_contiguous:
            flat = np.ascontiguousarray(flat, dtype=np.float32)
        flat = flat.ravel()
        if flat.size == 0:
            return
        # bin i covers [EDGES[i-1], EDGES[i]) — identical to
        # searchsorted(EDGES, flat, side="right") (pinned by test):
        # positive-float bit patterns are monotone, so exponent + top
        # mantissa bit IS the half-octave index.  Values below 2^-10
        # (incl. 0 and any negative, whose int32 view is negative) clip
        # to the underflow slot; >= 2^10, NaN and inf clip to overflow.
        idx = (flat.view(np.int32) >> 22) - (_RAW_LO - 1)
        np.clip(idx, 0, N_SLOTS - 1, out=idx)
        add = np.bincount(idx, minlength=N_SLOTS)
        total = float(flat.sum(dtype=np.float64))
        batch_mean = total / flat.size
        with self._lock:
            self.counts += add
            self.count += int(flat.size)
            self.sum += total
            self.sum_sq += float(np.dot(flat, flat))
            if self.ewma_mean is None:
                self.ewma_mean = batch_mean
            else:
                prev = self.ewma_mean
                self.ewma_mean = prev + EWMA_ALPHA * (batch_mean - prev)
                self.ewma_var = (1.0 - EWMA_ALPHA) * (
                    self.ewma_var + EWMA_ALPHA * (batch_mean - prev) ** 2
                )
            self.last_seen = time.time() if ts is None else float(ts)

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def merge(self, other: "ScoreSketch") -> None:
        """Fold ``other`` in.  Counts/sums add exactly; the EWMA pair
        combines count-weighted — weights add across merges, so the
        operation is associative AND commutative (A+B == B+A and
        (A+B)+C == A+(B+C), pinned by tests), which is what lets shard
        docs merge in any order.  A machine-affinity-sharded tier never
        actually merges two live sketches of one machine, so the
        weighted EWMA is only ever a tie-break for replayed/overlapping
        streams."""
        with self._lock:
            if other.ewma_mean is not None:
                if self.ewma_mean is None:
                    self.ewma_mean = other.ewma_mean
                    self.ewma_var = other.ewma_var
                else:
                    total = self.count + other.count
                    if total > 0:
                        w_self = self.count / total
                        w_other = other.count / total
                        self.ewma_mean = (
                            w_self * self.ewma_mean
                            + w_other * other.ewma_mean
                        )
                        self.ewma_var = (
                            w_self * self.ewma_var
                            + w_other * other.ewma_var
                        )
            self.counts += other.counts
            self.count += other.count
            self.sum += other.sum
            self.sum_sq += other.sum_sq
            self.last_seen = max(self.last_seen, other.last_seen)

    def to_doc(self) -> Dict[str, Any]:
        with self._lock:
            doc: Dict[str, Any] = {
                "v": 1,
                "edges-version": EDGES_VERSION,
                "counts": [int(c) for c in self.counts],
                "count": int(self.count),
                "sum": float(self.sum),
                "sum-sq": float(self.sum_sq),
                "last-seen": float(self.last_seen),
            }
            if self.ewma_mean is not None:
                doc["ewma-mean"] = float(self.ewma_mean)
                doc["ewma-var"] = float(self.ewma_var)
            return doc

    @classmethod
    def from_doc(cls, doc: Dict[str, Any]) -> "ScoreSketch":
        ver = int(doc.get("edges-version", 0))
        if ver != EDGES_VERSION:
            raise ValueError(
                f"sketch edges-version {ver} != supported {EDGES_VERSION}"
            )
        counts = np.asarray(doc.get("counts", ()), dtype=np.int64)
        if counts.shape != (N_SLOTS,):
            raise ValueError(
                f"sketch has {counts.size} slots, expected {N_SLOTS}"
            )
        sk = cls()
        sk.counts = counts.copy()
        sk.count = int(doc.get("count", 0))
        sk.sum = float(doc.get("sum", 0.0))
        sk.sum_sq = float(doc.get("sum-sq", 0.0))
        if doc.get("ewma-mean") is not None:
            sk.ewma_mean = float(doc["ewma-mean"])
            sk.ewma_var = float(doc.get("ewma-var", 0.0))
        sk.last_seen = float(doc.get("last-seen", 0.0))
        return sk


def sketch_from_scores(scores: Any, ts: Optional[float] = None) -> ScoreSketch:
    """One-shot sketch of an array (the builder's baseline constructor)."""
    sk = ScoreSketch()
    sk.observe(scores, ts=ts)
    return sk


def drift_score(
    baseline: Optional[Dict[str, Any]], live: Optional[Dict[str, Any]]
) -> Optional[float]:
    """Hellinger distance between two sketch docs' normalized bucket
    distributions, in [0, 1] (0 = identical shape, 1 = disjoint
    support).  Computed from counts ONLY, so it is invariant to the
    order scores arrived in and to how the stream was sharded.  None
    when either side has fewer than :data:`MIN_DRIFT_COUNT`
    observations — below that, sampling noise alone reads as drift."""
    if not baseline or not live:
        return None
    for doc in (baseline, live):
        ver = int(doc.get("edges-version", 0))
        if ver != EDGES_VERSION:
            raise ValueError(
                f"sketch edges-version {ver} != supported {EDGES_VERSION}"
            )
    p = np.asarray(baseline.get("counts", ()), dtype=np.float64)
    q = np.asarray(live.get("counts", ()), dtype=np.float64)
    if (
        p.sum() < MIN_DRIFT_COUNT
        or q.sum() < MIN_DRIFT_COUNT
        or p.shape != q.shape
    ):
        return None
    p = p / p.sum()
    q = q / q.sum()
    h = float(
        np.sqrt(0.5 * np.square(np.sqrt(p) - np.sqrt(q)).sum())
    )
    return round(min(1.0, h), 9)


def machine_status(
    baseline: Optional[Dict[str, Any]],
    live: Optional[Dict[str, Any]],
    drift: Optional[float],
    threshold: float,
) -> str:
    """One word per machine: ``drifting`` (distance past the threshold),
    ``silent`` (a baseline exists but NO live scores — the machine the
    fleet forgot), ``no-baseline`` (live traffic but the build recorded
    no residual distribution), else ``ok``."""
    has_live = bool(live and live.get("count"))
    if baseline and not has_live:
        return "silent"
    if drift is not None and drift > threshold:
        return "drifting"
    if not baseline and has_live:
        return "no-baseline"
    return "ok"


# -- telemetry instruments (docs/observability.md "Fleet health") -----------
#: exported for the TOP-K machines by drift only — a 10k-machine fleet
#: must not put 10k series on /metrics; the full set lives in the
#: /fleet-health doc.  Series reset at each export so machines rotating
#: out of the top-K don't leave stale samples behind.
_DRIFT_GAUGE = metrics.gauge(
    "gordo_machine_drift",
    "Baseline-vs-live anomaly-score distribution distance (Hellinger, "
    "0..1) for the top-K drifting machines",
    labels=("machine",),
)
_EWMA_GAUGE = metrics.gauge(
    "gordo_machine_score_ewma_mean",
    "EWMA of per-response mean total anomaly score, top-K machines",
    labels=("machine",),
)
_COUNT_GAUGE = metrics.gauge(
    "gordo_machine_score_count",
    "Live-window anomaly scores sketched per machine, top-K machines",
    labels=("machine",),
)
_STATUS_GAUGE = metrics.gauge(
    "gordo_fleet_health_machines",
    "Machines by fleet-health status (ok / drifting / silent / "
    "no-baseline) as of the latest export",
    labels=("status",),
)


class FleetHealth:
    """Process-wide registry of per-machine live sketches + baselines.

    The module-level :data:`FLEET_HEALTH` is the default every serving
    component records into (mirroring ``telemetry.metrics.REGISTRY``).
    Machines are keyed by name only: a fleet-sharded tier's replicas
    serve disjoint machines, so even two in-process test replicas never
    collide.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._live: Dict[str, ScoreSketch] = {}
        self._baselines: Dict[str, Dict[str, Any]] = {}
        self._suspend = threading.local()

    @contextlib.contextmanager
    def suspended(self) -> Iterator[None]:
        """Recording no-op for this thread while the context holds —
        the builder scores training data through the SAME serving path
        to derive baselines, and those scores must not masquerade as
        live traffic (a build+serve test process would otherwise start
        with its live windows pre-filled)."""
        prev = getattr(self._suspend, "on", False)
        self._suspend.on = True
        try:
            yield
        finally:
            self._suspend.on = prev

    # -- recording (the serve hot path) ---------------------------------
    def record(self, machine: Optional[str], scores: Any) -> None:
        """Fold one scoring response's total-anomaly-score array into
        ``machine``'s live sketch.  The ONE hot-path entry: called by
        ``serve/scorer.py`` (per-machine responses) and
        ``serve/fleet_scorer.py`` (stacked-dispatch assembly), always on
        host arrays already fetched for response encoding.  Honors the
        telemetry kill switch."""
        if machine is None or scores is None or not metrics.enabled():
            return
        if getattr(self._suspend, "on", False):
            return
        with self._lock:
            sk = self._live.get(machine)
            if sk is None:
                sk = self._live[machine] = ScoreSketch()
        sk.observe(scores)

    # -- baselines -------------------------------------------------------
    def set_baseline(
        self, machine: str, doc: Optional[Dict[str, Any]]
    ) -> None:
        with self._lock:
            if doc:
                self._baselines[machine] = dict(doc)
            else:
                self._baselines.pop(machine, None)

    def baseline(self, machine: str) -> Optional[Dict[str, Any]]:
        return self._baselines.get(machine)

    def load_baselines(self, metadata_by_name: Dict[str, Dict]) -> int:
        """Adopt training-time baselines from artifact metadata docs
        (``metadata["fleet-health"]["baseline"]``, what the builder
        records).  Returns how many machines got one."""
        n = 0
        for name, meta in metadata_by_name.items():
            doc = ((meta or {}).get(METADATA_KEY) or {}).get("baseline")
            if doc:
                self.set_baseline(name, doc)
                n += 1
        return n

    # -- lifecycle -------------------------------------------------------
    def clear(self, machines: Optional[Iterable[str]] = None) -> None:
        """Drop live sketches (and baselines) for ``machines`` — or
        everything when None.  Tests and benches phase-separate with
        this; a serving process keeps accumulating across rescans."""
        with self._lock:
            if machines is None:
                self._live.clear()
                self._baselines.clear()
                return
            for m in machines:
                self._live.pop(m, None)
                self._baselines.pop(m, None)

    def tracked(self) -> List[str]:
        with self._lock:
            return sorted(set(self._live) | set(self._baselines))

    # -- documents -------------------------------------------------------
    def doc(
        self,
        machines: Optional[Iterable[str]] = None,
        top: Optional[int] = None,
        threshold: Optional[float] = None,
    ) -> Dict[str, Any]:
        """The fleet-health document: per-machine live/baseline sketches,
        drift score and status, plus the top-K drift ranking.  Machine
        keys are sorted, so two docs over the same state serialize
        identically (the merge-parity gate depends on it)."""
        names = sorted(machines) if machines is not None else self.tracked()
        threshold = drift_threshold() if threshold is None else threshold
        top = drift_top_k() if top is None else int(top)
        out_machines: Dict[str, Any] = {}
        ranking: List[Any] = []
        for name in names:
            sk = self._live.get(name)
            live_doc = sk.to_doc() if sk is not None and sk.count else None
            base_doc = self._baselines.get(name)
            drift = drift_score(base_doc, live_doc)
            status = machine_status(base_doc, live_doc, drift, threshold)
            out_machines[name] = {
                "live": live_doc,
                "baseline": dict(base_doc) if base_doc else None,
                "drift": drift,
                "status": status,
            }
            if drift is not None:
                ranking.append((name, drift))
        ranking.sort(key=lambda item: (-item[1], item[0]))
        return {
            "gordo-fleet-health": 1,
            "time": time.time(),
            "edges-version": EDGES_VERSION,
            "drift-threshold": threshold,
            "top-drift": [
                {"machine": n, "drift": d} for n, d in ranking[:top]
            ],
            "machines": out_machines,
        }

    # -- gauges ----------------------------------------------------------
    def export_gauges(
        self,
        machines: Optional[Iterable[str]] = None,
        top: Optional[int] = None,
    ) -> None:
        """Refresh the ``gordo_machine_*`` gauges for the top-K machines
        by drift (falling back to live volume when no drift is
        computable) and the by-status fleet summary.  Called at scrape
        time — these describe "now", and resetting the series each time
        bounds cardinality at K no matter how the top set rotates."""
        if not metrics.enabled():
            return
        doc = self.doc(machines=machines, top=top)
        k = drift_top_k() if top is None else int(top)
        ranked = sorted(
            doc["machines"].items(),
            key=lambda kv: (
                -(kv[1]["drift"] if kv[1]["drift"] is not None else -1.0),
                -((kv[1]["live"] or {}).get("count", 0)),
                kv[0],
            ),
        )
        for g in (_DRIFT_GAUGE, _EWMA_GAUGE, _COUNT_GAUGE, _STATUS_GAUGE):
            g.reset_series()
        status_counts: Dict[str, int] = {}
        for name, entry in doc["machines"].items():
            status_counts[entry["status"]] = (
                status_counts.get(entry["status"], 0) + 1
            )
        for status, n in status_counts.items():
            _STATUS_GAUGE.set(float(n), status)
        for name, entry in ranked[:k]:
            live = entry["live"] or {}
            if entry["drift"] is not None:
                _DRIFT_GAUGE.set(entry["drift"], name)
            if live.get("ewma-mean") is not None:
                _EWMA_GAUGE.set(float(live["ewma-mean"]), name)
            if live.get("count"):
                _COUNT_GAUGE.set(float(live["count"]), name)


#: the process-wide default registry scoring responses record into
FLEET_HEALTH = FleetHealth()


def merge_health_docs(
    docs: Sequence[Dict[str, Any]],
    top: Optional[int] = None,
    threshold: Optional[float] = None,
) -> Dict[str, Any]:
    """Merge per-shard fleet-health docs into ONE fleet view — what
    watchman serves at ``/fleet-health`` and the CLI's ``--dir`` mode
    computes from rollup files.  Live sketches add exactly (the sketch
    merge contract); a machine seen by several docs keeps the first
    baseline (identical across shards by construction — they all read
    the same artifact metadata).  Drift, status and the top-K ranking
    recompute from the merged counts, so a machine-affinity-sharded
    tier's merged doc equals the single-process doc for the same request
    stream (modulo timestamps; pinned by ``bench --stage
    health_overhead``)."""
    live: Dict[str, ScoreSketch] = {}
    baselines: Dict[str, Dict[str, Any]] = {}
    thresholds: List[float] = []
    for doc in docs:
        if not doc:
            continue
        if doc.get("drift-threshold") is not None:
            thresholds.append(float(doc["drift-threshold"]))
        for name, entry in (doc.get("machines") or {}).items():
            if entry.get("baseline") and name not in baselines:
                baselines[name] = dict(entry["baseline"])
            if entry.get("live"):
                sk = ScoreSketch.from_doc(entry["live"])
                if name in live:
                    live[name].merge(sk)
                else:
                    live[name] = sk
    merged = FleetHealth()
    merged._live = live
    merged._baselines = baselines
    if threshold is None and thresholds:
        threshold = max(thresholds)
    return merged.doc(top=top, threshold=threshold)


def normalize_health_doc(doc: Dict[str, Any]) -> Dict[str, Any]:
    """A health doc with every volatile field removed — wall-clock
    timestamps (``time``, per-sketch ``last-seen``) and per-instance
    identity (``serve-shard``, ``instances``, ``project-name``) — so two
    docs over the same request stream compare byte-for-byte
    (``json.dumps(..., sort_keys=True)``)."""
    drop_top = {"time", "serve-shard", "instances", "project-name",
                "targets-responding"}
    out = {k: v for k, v in doc.items() if k not in drop_top}
    machines = {}
    for name, entry in (out.get("machines") or {}).items():
        entry = dict(entry)
        for key in ("live", "baseline"):
            if entry.get(key):
                entry[key] = {
                    k: v for k, v in entry[key].items() if k != "last-seen"
                }
        machines[name] = entry
    if "machines" in out:
        out["machines"] = machines
    return out


# ---------------------------------------------------------------------------
# training-time baselines (the build plane's half of the drift signal)
# ---------------------------------------------------------------------------

def training_baseline(model: Any, X: Any) -> Optional[Dict[str, Any]]:
    """One machine's training-time residual sketch, or None.

    Scores the TAIL of the training matrix (``BASELINE_MAX_ROWS`` rows)
    through the SAME fused serving scorer the live traffic will run —
    apples-to-apples by construction: any systematic difference between
    the build-time and serve-time scoring paths would read as permanent
    phantom drift.  Timestamps are pinned to 0 (a training artifact has
    no "last seen"), so a rebuilt artifact's bytes depend only on the
    model and data.  Never raises — a baseline is a hint, not a build
    step that may fail the machine."""
    if not baselines_enabled():
        return None
    try:
        from gordo_tpu.serve.scorer import CompiledScorer

        scorer = CompiledScorer(model)
        if not scorer.is_anomaly:
            return None
        Xa = np.asarray(X, np.float32)[-BASELINE_MAX_ROWS:]
        with FLEET_HEALTH.suspended():
            out = scorer.anomaly_arrays(Xa)
        return sketch_from_scores(
            out["total-anomaly-score"], ts=0.0
        ).to_doc()
    except Exception:
        logger.debug("training baseline sketch failed", exc_info=True)
        return None


def training_baselines(
    models: Dict[str, Any], X_by_name: Dict[str, Any],
    prestacked_hint: Optional[Dict[str, Any]] = None,
) -> Dict[str, Dict[str, Any]]:
    """Training-time residual sketches for a whole trained chunk in ONE
    stacked dispatch (the chunk shares a structural signature, so the
    fleet scorer buckets it into a single vmapped program — the builder
    pays ~one bulk serving round per chunk, not one dispatch per
    machine).  ``prestacked_hint``: the chunk's stacked host arrays as
    fetched by the build's collect side (``PendingFleetBuild.prestacked``)
    — the scorer adopts them whole instead of re-stacking per-machine
    views leaf by leaf.  Returns ``{machine: sketch doc}``; machines
    whose scoring failed are simply absent."""
    if not baselines_enabled() or not models:
        return {}
    docs: Dict[str, Dict[str, Any]] = {}
    try:
        from gordo_tpu.serve.fleet_scorer import FleetScorer
        from gordo_tpu.serve.scorer import SequenceModelUnsupported

        X_by = {
            name: np.asarray(X, np.float32)[-BASELINE_MAX_ROWS:]
            for name, X in X_by_name.items()
            if name in models
        }
        scorer = FleetScorer.from_models(
            {n: models[n] for n in X_by},
            prestacked_hint=prestacked_hint,
        )
        with FLEET_HEALTH.suspended():
            out = scorer.score_all(X_by)
        for name, res in out.items():
            scores = res.get("total-anomaly-score")
            if scores is not None:
                docs[name] = sketch_from_scores(scores, ts=0.0).to_doc()
    except SequenceModelUnsupported as exc:
        # the stacked scorer refuses sequence models: no baseline sketch
        logger.info("no training baseline for chunk %s...: %s",
                    sorted(models)[:3], exc)
    except Exception:
        logger.exception(
            "training baseline sketching failed for chunk %s...",
            sorted(models)[:3],
        )
    return docs


# ---------------------------------------------------------------------------
# rollup files (the no-HTTP interface `gordo refresh` consumes)
# ---------------------------------------------------------------------------

def rollup_max_bytes() -> int:
    try:
        return int(
            os.environ.get(ENV_ROLLUP_MAX_BYTES, "")
            or DEFAULT_ROLLUP_MAX_BYTES
        )
    except ValueError:
        return DEFAULT_ROLLUP_MAX_BYTES


def rollup_path(directory: str, shard=None) -> str:
    """This process's rollup file under ``<directory>/.gordo-fleet-health/``.
    Shard-keyed when serving a shard (stable across restarts; replica i
    always appends to the same file), ``rollup-unsharded.jsonl``
    otherwise."""
    if shard is not None:
        name = (
            f"rollup-shard-{int(shard.index):03d}"
            f"-of-{int(shard.count):03d}.jsonl"
        )
    else:
        name = "rollup-unsharded.jsonl"
    return os.path.join(directory, ROLLUP_DIR, name)


def write_rollup(
    directory: str,
    doc: Dict[str, Any],
    shard=None,
    max_bytes: Optional[int] = None,
) -> Optional[str]:
    """Append one health-doc line to this process's rollup JSONL under
    the artifact dir (size-capped, keep-last-2 rotation).  Never raises
    — a full disk must not take down scoring."""
    path = rollup_path(directory, shard=shard)
    try:
        append_jsonl_line(
            path,
            json.dumps(doc, sort_keys=True),
            max_bytes=rollup_max_bytes() if max_bytes is None else max_bytes,
        )
        return path
    except Exception:
        logger.exception("fleet-health rollup write failed: %s", path)
        return None


def load_rollups(directory: str) -> List[Dict[str, Any]]:
    """The latest health doc from every rollup file under ``directory``
    (an artifact dir, or its ``.gordo-fleet-health/`` subdir directly) —
    one doc per serving process/shard, ready for
    :func:`merge_health_docs`."""
    candidates = [os.path.join(directory, ROLLUP_DIR), directory]
    rolldir = next((d for d in candidates if os.path.isdir(d)), None)
    docs: List[Dict[str, Any]] = []
    if rolldir is None:
        return docs
    for fname in sorted(os.listdir(rolldir)):
        if not fname.endswith(".jsonl"):
            continue
        latest: Optional[Dict[str, Any]] = None
        try:
            with open(os.path.join(rolldir, fname)) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        doc = json.loads(line)
                    except ValueError:
                        continue  # torn tail line mid-append
                    if doc.get("gordo-fleet-health"):
                        latest = doc
        except OSError:
            continue
        if latest is not None:
            docs.append(latest)
    return docs


def read_rollups(
    directory: str,
    top: Optional[int] = None,
    threshold: Optional[float] = None,
) -> Optional[Dict[str, Any]]:
    """ONE merged fleet-health doc from the shard-keyed rollup JSONL
    files under ``directory`` (an artifact dir, or its
    ``.gordo-fleet-health/`` directly), or None when no rollups exist.

    The shared file-interface reader: the refresh loop, ``gordo
    fleet-health --dir``, and tests all consume rollups through this —
    none of them needs private knowledge of the file layout, the
    torn-tail skip, or the shard merge algebra
    (:func:`load_rollups` + :func:`merge_health_docs`)."""
    docs = load_rollups(directory)
    if not docs:
        return None
    return merge_health_docs(docs, top=top, threshold=threshold)


def baselines_from_archive(
    directory: str,
    machines: Optional[Sequence[str]] = None,
    apply: bool = False,
) -> Dict[str, Dict[str, Any]]:
    """Per-machine baseline sketch docs regenerated from a backfill
    score archive (``<directory>/.gordo-scores/``) — REAL served-history
    distributions instead of training residuals.

    A baseline built from months of archived scores is the distribution
    the machine actually lives at, so drift measured against it flags
    behavior changes rather than train/serve skew.  Returns
    ``{machine: sketch doc}`` (machines with no archived rows are
    omitted); ``apply=True`` additionally installs each doc as the live
    process's baseline (:meth:`FleetHealth.set_baseline`), the hook a
    server rescan or refresh loop calls after a backfill lands.

    The batch plane import is deferred: telemetry must stay importable
    without the backfill plane's jax surface."""
    from gordo_tpu.batch.archive import ScoreArchive

    arch = ScoreArchive(directory)
    docs: Dict[str, Dict[str, Any]] = {}
    for name in machines if machines is not None else arch.machines():
        rec = arch.read_machine(name)
        if rec is None:
            continue
        scores = rec["total-anomaly-score"]
        if scores.size == 0:
            continue
        docs[name] = sketch_from_scores(scores).to_doc()
        if apply:
            FLEET_HEALTH.set_baseline(name, docs[name])
    return docs
