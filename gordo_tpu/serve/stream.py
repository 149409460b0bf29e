"""Streaming scoring plane: push-based online anomaly detection.

The third workload (after request/response serving and offline batch):
long-lived sessions where sensor rows arrive one at a time and anomaly
verdicts are PUSHED to subscribers instead of polled.

Three pieces live here:

* **The incremental-window step program** (``serve.stream_step``, owned
  by the compile plane).  The fused request path re-scores the whole
  request series per poll — a 1-row update costs a bucket-padded
  O(lookback-series) dispatch plus smoothing over the full history.
  Here the carried state (a fixed ``offset + smooth_window`` raw-input
  ring plus the row count) lives as device-resident leaves threaded
  through the program, so one arriving row pays an O(1) state shift
  plus ONE tiny fixed-shape dispatch.  At steady state the fp32 verdict
  is byte-identical to the full-window program over the same trailing
  rows (:func:`reference_verdict` is the oracle; ``tests/test_stream.py``
  pins it at every step, across a generation flip) — the fixed state
  shape means XLA lowers the same kernels every step, and the math is
  stage-for-stage the request path's.

* **Per-machine stream state** (:class:`MachineStream`).  Carries the
  device leaves plus a small host mirror of the raw input ring.  When a
  delta hot-reload (r15) swaps the underlying :class:`ModelEntry`, the
  stream re-primes by replaying the mirrored rows through the NEW
  model's step program — subscribers keep their session and the first
  post-flip verdict is already byte-equal to a full re-score under the
  new generation.

* **The hub** (:class:`StreamHub`): a monotonic event log with a bounded
  replay ring, fan-out to per-subscriber bounded queues, and the SSE /
  long-poll transport.  Event ids are hub-global and strictly
  increasing; a client that reconnects with ``Last-Event-ID`` replays
  everything it missed from the ring (no verdict lost or duplicated —
  the chaos suite pins this).  Slow consumers are DISCONNECTED on queue
  overflow rather than silently dropped-from: the client notices,
  resumes by id, and the ring bridges the gap.

Event types pushed: ``verdict`` (per valid scored row), ``threshold``
(total-score crossings of the model's aggregate threshold, transitions
only), ``drift`` (fleet-health status transitions, evaluated every
:data:`DRIFT_CHECK_EVERY` verdicts against the r14 sketches).

Env knobs (docs/configuration.md "Streaming"): ``GORDO_STREAM_REPLAY``
(replay-ring events, default 4096), ``GORDO_STREAM_QUEUE``
(per-subscriber queue depth, default 256), ``GORDO_STREAM_KEEPALIVE``
(SSE keepalive comment interval seconds, default 15),
``GORDO_STREAM_POLL_TIMEOUT`` (long-poll max wait seconds, default 25).

Fault seams: ``stream.ingest`` (pre-state-mutation, so an injected
failure never half-applies a row) and ``stream.push`` (per-event in the
SSE writer; ``disconnect`` kills the transport mid-event,
``slow_consumer`` stalls the writer until its queue overflows).
"""

from __future__ import annotations

import asyncio
import collections
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import compile as compile_plane
from gordo_tpu import faults, telemetry
from gordo_tpu.anomaly.diff import scores_fn
from gordo_tpu.ops.windows import make_windows
from gordo_tpu.serve import precision
from gordo_tpu.serve.scorer import refuse_sequence_model

logger = logging.getLogger(__name__)

__all__ = [
    "MachineStream",
    "StreamHub",
    "Subscriber",
    "EventRing",
    "StreamUnsupported",
    "warm_stream_program",
    "reference_verdict",
    "sse_format",
    "run_sse",
    "poll_events",
    "replay_ring_size",
    "queue_depth",
    "keepalive_seconds",
    "poll_timeout_seconds",
]

# -- env knobs (read live, like fleet_health's thresholds) ------------------


def replay_ring_size() -> int:
    """``GORDO_STREAM_REPLAY``: events the hub retains for by-id resume."""
    return int(os.environ.get("GORDO_STREAM_REPLAY", "4096"))


def queue_depth() -> int:
    """``GORDO_STREAM_QUEUE``: per-subscriber queue bound; overflow
    disconnects the subscriber (it resumes by Last-Event-ID)."""
    return int(os.environ.get("GORDO_STREAM_QUEUE", "256"))


def keepalive_seconds() -> float:
    """``GORDO_STREAM_KEEPALIVE``: SSE comment interval keeping idle
    connections alive through ingress idle timeouts."""
    return float(os.environ.get("GORDO_STREAM_KEEPALIVE", "15"))


def poll_timeout_seconds() -> float:
    """``GORDO_STREAM_POLL_TIMEOUT``: long-poll fallback max wait."""
    return float(os.environ.get("GORDO_STREAM_POLL_TIMEOUT", "25"))


#: evaluate the machine's fleet-health drift status every N valid
#: verdicts — a sketch comparison per row would tax the O(1) hot path
DRIFT_CHECK_EVERY = 16

# -- telemetry (docs/observability.md "Streaming") --------------------------

_SUBSCRIBERS = telemetry.gauge(
    "gordo_stream_subscribers",
    "Live stream subscribers (SSE + long-poll) on this replica",
)
_EVENTS_PUSHED = telemetry.counter(
    "gordo_stream_events_pushed_total",
    "Stream events published to the hub, by event type",
    labels=("type",),
)
_PUSH_SECONDS = telemetry.histogram(
    "gordo_stream_push_seconds",
    "Detection-to-push latency: ingest scoring to SSE frame write",
    buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0),
)
_DROPPED = telemetry.counter(
    "gordo_stream_dropped_total",
    "Stream subscriber disconnects/drops, by reason "
    "(slow_consumer = queue overflow, replay_gap = resume id aged out "
    "of the replay ring)",
    labels=("reason",),
)
_INGESTED = telemetry.counter(
    "gordo_stream_ingest_rows_total",
    "Rows accepted by the streaming ingest path",
)


class StreamUnsupported(ValueError):
    """Model cannot serve the streaming plane (needs the fused anomaly
    chain: pure-stats scalers + BaseJaxEstimator + diff detector)."""


# ---------------------------------------------------------------------------
# The incremental step program
# ---------------------------------------------------------------------------


def _mode_offset(mode: str, lookback: int) -> int:
    """Rows consumed before the first output row — identical to the
    fused path's ``X.shape[0] - pred.shape[0]``."""
    if mode == "ae":
        return lookback - 1
    if mode == "forecast":
        return lookback
    return 0


def _stream_step_fn(
    module,
    scaler_classes,
    mode,
    lookback,
    det_cls,
    smooth_window,
    dtype,
    with_confidence,
    scaler_stats,
    params,
    det_stats,
    agg_threshold,
    rows,
    count,
    x,
):
    """One arriving row -> (new state, verdict arrays).

    State leaves (device-resident, threaded through every call):

    * ``rows``  (H, F) f32 — raw input ring, newest last, where
      ``H = offset + W`` (W = max(smooth_window, 1)): exactly enough
      rows to window the newest sample AND recompute the W raw scores
      its trailing rolling median covers
    * ``count`` ()  i32 — total rows ever ingested (drives the
      min_periods=1 validity mask, so early-stream medians match the
      full path's NaN-padded windows, and warm-up garbage in the ring
      never reaches a verdict)

    The math is stage-for-stage the request path's ``_score_program_fn``
    over the ring: cast, scaler chain, the W newest model windows,
    detector |diff| + L2, masked nanmedian standing in for the trailing
    rolling median at the newest row.  Because the ring has a FIXED
    shape, XLA lowers the exact same kernels every step — at steady
    state (count >= H) the fp32 verdict is byte-identical to running
    the full-window program over the same trailing rows.  (The ring is
    also deliberately raw input, not carried scores: it is
    model-independent, so a generation flip keeps the state and the
    first post-flip verdict is already exact under the new params.)
    """
    offset = _mode_offset(mode, lookback)
    w = max(smooth_window, 1)
    rows = jnp.concatenate([rows[1:], x[None, :]], axis=0)
    count = count + 1

    Xc = precision.cast_input(rows, dtype)
    scaler_stats = precision.cast_params(scaler_stats, dtype)
    params = precision.cast_params(params, dtype)
    det_stats = precision.cast_params(det_stats, dtype)

    Xs = Xc
    for cls, stats in zip(scaler_classes, scaler_stats):
        Xs = cls.apply(stats, Xs)

    if mode == "none":
        inputs = Xs                              # (W, F)
    elif mode == "ae":
        inputs = make_windows(Xs, lookback)      # (W, lookback, F)
    else:  # forecast
        inputs = make_windows(Xs[:-1], lookback)

    pred = module.apply({"params": params}, inputs)  # (W, n_out)
    y_al = Xc[offset:]                               # (W, F)
    tag_raw, tot_raw = scores_fn(det_cls, det_stats, y_al, pred)
    tag_raw = tag_raw.astype(jnp.float32)
    tot_raw = tot_raw.astype(jnp.float32)

    # min_periods=1 reconstructed from the row count: the newest
    # n_valid raw scores are real, older slots cover ring positions the
    # stream has not filled yet — masked to NaN exactly where the full
    # path's rolling window would hold its NaN padding
    n_valid = jnp.clip(count - offset, 0, w)
    mask = jnp.arange(w) >= (w - n_valid)
    if smooth_window > 1:
        tag = jnp.nanmedian(
            jnp.where(mask[:, None], tag_raw, jnp.nan), axis=0
        )
        tot = jnp.nanmedian(jnp.where(mask, tot_raw, jnp.nan))
    else:
        tag = tag_raw[-1]
        tot = tot_raw[-1]

    out = {
        "rows": rows,
        "count": count,
        "valid": count > offset,
        "tag-anomaly-scores": tag.astype(jnp.float32),
        "total-anomaly-score": tot.astype(jnp.float32),
    }
    if with_confidence:
        out["anomaly-confidence"] = out["total-anomaly-score"] / jnp.maximum(
            agg_threshold.astype(jnp.float32), 1e-12
        )
    return out


#: the per-machine incremental program, owned by the compile plane —
#: warmed per fleet signature at server startup (compile/warmup.py), so
#: the first streamed row of any machine never traces
_stream_program = compile_plane.program(
    "serve.stream_step",
    _stream_step_fn,
    static_argnames=(
        "module", "scaler_classes", "mode", "lookback", "det_cls",
        "smooth_window", "dtype", "with_confidence",
    ),
)


def _stream_args(
    c: Dict[str, Any], dtype: str, state: Dict[str, Any], x
) -> Tuple:
    """The ONE assembly of ``_stream_program`` arguments — dispatch,
    replay, and AOT warmup must agree on statics and pytree layout."""
    det = c["detector"]
    with_confidence = det["feature_thresholds"] is not None
    return (
        c["module"],
        tuple(cls for cls, _ in c["scalers"]),
        c["mode"],
        c["lookback"],
        det["scaler_cls"],
        max(int(det["window"] or 0), 1),
        dtype,
        with_confidence,
        tuple(stats for _, stats in c["scalers"]),
        c["params"],
        det["scaler_stats"],
        np.float32(det["aggregate_threshold"]) if with_confidence else None,
        state["rows"],
        state["count"],
        x,
    )


def warm_stream_program(
    scorer, n_features: int, dtype: Optional[str] = None
) -> List[Tuple[str, float]]:
    """AOT-compile the stream step for one machine's chain — shape
    structs only.  Returns ``[("serve.stream_step", compile_seconds)]``
    (0.0 = cached), or ``[]`` when the model can't stream."""
    c = scorer.chain
    if not c or not c.get("detector"):
        return []
    det = c["detector"]
    if det["feature_thresholds"] is None and det["require_thresholds"]:
        return []
    dtype = precision.canonical(dtype) if dtype else scorer.dtype
    w = max(int(det["window"] or 0), 1)
    h = _mode_offset(c["mode"], c["lookback"]) + w
    f = int(n_features)
    state = {
        "rows": jax.ShapeDtypeStruct((h, f), jnp.float32),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }
    x = jax.ShapeDtypeStruct((f,), jnp.float32)
    args = _stream_args(c, dtype, state, x)
    return [("serve.stream_step", _stream_program.warm(*args))]


def reference_verdict(
    scorer, rows: np.ndarray, dtype: Optional[str] = None
) -> Dict[str, np.ndarray]:
    """The parity oracle: the request path's full-window program
    (``serve.score``) over ``rows`` at its EXACT shape — no bucket
    padding — returning the newest row's verdict arrays.

    ``tests/test_stream.py`` pins the streaming step byte-identical
    (fp32) to this at every steady-state step: both paths then lower
    fixed input shapes, so XLA picks identical kernels and the only
    question is the math — which is stage-for-stage the same.  (The
    production ``anomaly_arrays`` surface pads requests to row buckets;
    kernel selection varies with batch shape at the last ulp, which is
    why the oracle dispatches unpadded.)
    """
    from gordo_tpu.serve import scorer as scorer_mod

    c = scorer.chain
    det = c["detector"]
    with_confidence = det["feature_thresholds"] is not None
    X = jnp.asarray(np.asarray(rows, np.float32))
    dtype = precision.canonical(dtype) if dtype else scorer.dtype
    args, kw = scorer_mod._program_args(
        c, X, True, 0, dtype, with_confidence
    )
    out = scorer_mod._score_program(*args, **kw)
    verdict = {
        "tag-anomaly-scores": np.asarray(out["tag-anomaly-scores"])[-1],
        "total-anomaly-score": np.asarray(out["total-anomaly-score"])[-1],
    }
    if with_confidence:
        verdict["anomaly-confidence"] = np.asarray(
            out["anomaly-confidence"]
        )[-1]
    return verdict


# ---------------------------------------------------------------------------
# Per-machine carried state
# ---------------------------------------------------------------------------


class MachineStream:
    """One machine's streaming session: device ring + host row mirror.

    The carried state is the raw-input ring (plus the running count) —
    deliberately model-INdependent, so ``rebind(scorer)`` after a delta
    hot-reload (r15) keeps the session: when the new model shares the
    old one's window geometry the device ring survives untouched and
    the first post-flip verdict is already byte-equal to a full
    re-score under the new generation; when geometry changed, the host
    mirror re-primes a fresh ring from whatever history still fits.
    """

    def __init__(self, name: str, scorer, dtype: Optional[str] = None):
        self.name = name
        self.count = 0
        self.exceeding = False
        self.drift_status: Optional[str] = None
        self._state: Optional[Dict[str, Any]] = None
        self._bound = None  # AOT fast path, resolved on first dispatch
        self._rows: "collections.deque[np.ndarray]" = collections.deque()
        self._scorer = None
        self.state_rows = 0
        self.rebind(scorer, dtype)

    # -- model binding -------------------------------------------------------

    def rebind(self, scorer, dtype: Optional[str] = None) -> None:
        """(Re)attach to ``scorer``, carrying the session state across."""
        refuse_sequence_model(scorer.model, self.name, "MachineStream")
        c = scorer.chain
        if not c or not c.get("detector"):
            raise StreamUnsupported(
                f"machine {self.name!r} has no fused anomaly chain; "
                "the streaming plane needs pure-stats scalers, a jax "
                "estimator, and a diff-based detector"
            )
        det = c["detector"]
        if det["feature_thresholds"] is None and det["require_thresholds"]:
            raise StreamUnsupported(
                f"machine {self.name!r} requires thresholds but "
                "cross_validate() never derived them"
            )
        prior_rows = self.state_rows
        self._scorer = scorer
        self._bound = None  # statics changed with the generation
        self.chain = c
        self.dtype = precision.canonical(dtype) if dtype else scorer.dtype
        self.offset = _mode_offset(c["mode"], c["lookback"])
        self.window = max(int(det["window"] or 0), 1)
        self.state_rows = self.offset + self.window
        self.with_confidence = det["feature_thresholds"] is not None
        if self.state_rows != prior_rows:
            # window geometry changed: re-prime a fresh ring from the
            # host mirror.  The device count is capped at the mirrored
            # depth so the min_periods mask treats unfillable older
            # slots as warm-up — verdicts equal a cold start over the
            # retained history (self.count keeps the true position for
            # event numbering).
            mirror = list(self._rows)[-self.state_rows:]
            self._rows = collections.deque(mirror, maxlen=self.state_rows)
            self._state = None
            if mirror:
                f = mirror[0].shape[0]
                ring = np.zeros((self.state_rows, f), np.float32)
                if len(mirror):
                    ring[self.state_rows - len(mirror):] = np.stack(mirror)
                self._state = {
                    "rows": jnp.asarray(ring),
                    "count": jnp.asarray(
                        min(self.count, len(mirror)), jnp.int32
                    ),
                }

    @property
    def scorer(self):
        return self._scorer

    def _init_state(self, n_features: int, count: int = 0) -> None:
        self._state = {
            "rows": jnp.zeros((self.state_rows, n_features), jnp.float32),
            "count": jnp.asarray(count, jnp.int32),
        }

    # -- the hot path --------------------------------------------------------

    def _advance(self, x: np.ndarray) -> Dict[str, Any]:
        args = _stream_args(self.chain, self.dtype, self._state, x)
        # the ring's shape is fixed by construction, so the call
        # signature never varies between rebinds: resolve the AOT
        # executable once and skip the registry's per-call keying —
        # it otherwise costs more than the device step itself
        if self._bound is None:
            self._bound = _stream_program.bind(*args)
        out = (
            self._bound(*args) if self._bound is not None
            else _stream_program(*args)
        )
        self._state = {k: out[k] for k in ("rows", "count")}
        return out

    def ingest(self, x: np.ndarray) -> Optional[Dict[str, Any]]:
        """Score one arriving row; returns the verdict arrays (fp32) for
        a valid (post-warmup) row, else None."""
        x = np.asarray(x, np.float32).reshape(-1)
        if self._state is None:
            self._init_state(x.shape[0], count=self.count)
        self._rows.append(x)
        out = self._advance(x)
        self.count += 1
        if not bool(out["valid"]):
            return None
        verdict = {
            "tag-anomaly-scores": np.asarray(out["tag-anomaly-scores"]),
            "total-anomaly-score": np.asarray(out["total-anomaly-score"]),
        }
        if "anomaly-confidence" in out:
            verdict["anomaly-confidence"] = np.asarray(
                out["anomaly-confidence"]
            )
        # the same per-verdict fold the request path does: streamed
        # totals feed the r14 health sketches (which feed r17 refresh)
        telemetry.FLEET_HEALTH.record(
            self.name, verdict["total-anomaly-score"].reshape(1)
        )
        return verdict


# ---------------------------------------------------------------------------
# Event log + subscribers
# ---------------------------------------------------------------------------


class EventRing:
    """Bounded in-memory event log with hub-global monotonic ids."""

    def __init__(self, maxlen: Optional[int] = None):
        self._events: "collections.deque[Dict[str, Any]]" = (
            collections.deque(maxlen=maxlen or replay_ring_size())
        )
        self.last_id = 0

    def append(self, etype: str, data: Dict[str, Any]) -> Dict[str, Any]:
        self.last_id += 1
        ev = {"id": self.last_id, "type": etype, "data": data}
        self._events.append(ev)
        return ev

    def since(
        self, after: int, machines: Optional[Set[str]] = None
    ) -> Tuple[List[Dict[str, Any]], bool]:
        """Events with id > ``after`` (filtered), plus a gap flag: True
        when ids between ``after`` and the oldest retained event have
        been trimmed — the subscriber missed events it can never replay."""
        oldest = self._events[0]["id"] if self._events else self.last_id + 1
        gap = after + 1 < oldest and after < self.last_id
        out = [
            ev for ev in self._events
            if ev["id"] > after
            and (machines is None or ev["data"].get("machine") in machines)
        ]
        return out, gap


class Subscriber:
    """One live consumer: a bounded queue the hub fans into."""

    def __init__(
        self,
        machines: Optional[Set[str]] = None,
        maxsize: Optional[int] = None,
    ):
        self.machines = machines
        self.queue: "asyncio.Queue[Dict[str, Any]]" = asyncio.Queue(
            maxsize=maxsize or queue_depth()
        )
        self.dead = False

    def wants(self, ev: Dict[str, Any]) -> bool:
        return self.machines is None or (
            ev["data"].get("machine") in self.machines
        )


class StreamHub:
    """The per-replica streaming hub: machine streams, event ring,
    subscriber fan-out.

    Loop-confined by design: ingest handlers, the SSE writers, and the
    watchman relay all run on the serving event loop, so fan-out needs
    no locking beyond the ring's (which also serves sync callers like
    bench's in-process replay).  A hub with ``collection=None`` is a
    pure relay (watchman re-fans upstream events through one).
    """

    def __init__(self, collection=None, ring_size: Optional[int] = None):
        self.collection = collection
        self.ring = EventRing(ring_size)
        self.streams: Dict[str, MachineStream] = {}
        self._subscribers: Set[Subscriber] = set()
        self._lock = threading.Lock()

    # -- subscriptions -------------------------------------------------------

    def subscribe(
        self,
        machines: Optional[Iterable[str]] = None,
        maxsize: Optional[int] = None,
    ) -> Subscriber:
        sub = Subscriber(
            set(machines) if machines is not None else None, maxsize
        )
        with self._lock:
            self._subscribers.add(sub)
            _SUBSCRIBERS.set(float(len(self._subscribers)))
        return sub

    def unsubscribe(self, sub: Subscriber) -> None:
        with self._lock:
            self._subscribers.discard(sub)
            _SUBSCRIBERS.set(float(len(self._subscribers)))

    @property
    def n_subscribers(self) -> int:
        return len(self._subscribers)

    # -- publishing ----------------------------------------------------------

    def publish(self, etype: str, data: Dict[str, Any]) -> Dict[str, Any]:
        """Append to the ring and fan out; slow consumers (full queue)
        are marked dead — their transport closes and they resume by id."""
        with self._lock:
            ev = self.ring.append(etype, data)
            subs = list(self._subscribers)
        _EVENTS_PUSHED.inc(1.0, etype)
        for sub in subs:
            if sub.dead or not sub.wants(ev):
                continue
            try:
                sub.queue.put_nowait(ev)
            except asyncio.QueueFull:
                sub.dead = True
                _DROPPED.inc(1.0, "slow_consumer")
        return ev

    # -- ingest --------------------------------------------------------------

    def stream_for(self, name: str, scorer, dtype=None) -> MachineStream:
        """The machine's stream, rebound when a hot reload swapped the
        scorer object underneath it (entry identity IS the generation)."""
        ms = self.streams.get(name)
        if ms is None:
            ms = self.streams[name] = MachineStream(name, scorer, dtype)
        elif ms.scorer is not scorer:
            ms.rebind(scorer, dtype)
        return ms

    def ingest_rows(
        self,
        name: str,
        scorer,
        X: np.ndarray,
        dtype: Optional[str] = None,
    ) -> List[Dict[str, Any]]:
        """Feed rows for one machine; returns the events published.

        The ``stream.ingest`` fault seam fires BEFORE any state
        mutation, so an injected failure never half-applies a row and a
        client retry is safe.
        """
        if faults.enabled():
            faults.check("stream.ingest", machine=name)
        ms = self.stream_for(name, scorer, dtype)
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X[None, :]
        events: List[Dict[str, Any]] = []
        for row in X:
            verdict = ms.ingest(row)
            _INGESTED.inc(1.0)
            if verdict is None:
                continue
            events.extend(self._emit(ms, verdict))
        return events

    def _emit(
        self, ms: MachineStream, verdict: Dict[str, Any]
    ) -> List[Dict[str, Any]]:
        now = time.time()
        total = float(verdict["total-anomaly-score"])
        data = {
            "machine": ms.name,
            "step": ms.count,
            "time": now,
            "total-anomaly-score": total,
            "tag-anomaly-scores": [
                float(v) for v in verdict["tag-anomaly-scores"]
            ],
        }
        if "anomaly-confidence" in verdict:
            data["anomaly-confidence"] = float(verdict["anomaly-confidence"])
        events = [self.publish("verdict", data)]

        det = ms.chain["detector"]
        if det["feature_thresholds"] is not None:
            threshold = float(det["aggregate_threshold"])
            exceeding = total > threshold
            if exceeding != ms.exceeding:
                ms.exceeding = exceeding
                events.append(self.publish("threshold", {
                    "machine": ms.name,
                    "direction": "above" if exceeding else "below",
                    "total-anomaly-score": total,
                    "threshold": threshold,
                    "time": now,
                }))

        if ms.count % DRIFT_CHECK_EVERY == 0:
            doc = telemetry.FLEET_HEALTH.doc(machines=[ms.name])
            status = doc["machines"][ms.name]["status"]
            if status != ms.drift_status:
                was, ms.drift_status = ms.drift_status, status
                if was is not None:
                    events.append(self.publish("drift", {
                        "machine": ms.name,
                        "status": status,
                        "was": was,
                        "drift": doc["machines"][ms.name]["drift"],
                        "time": now,
                    }))
        return events


# ---------------------------------------------------------------------------
# Transport: SSE framing + long-poll
# ---------------------------------------------------------------------------


def sse_format(ev: Dict[str, Any]) -> bytes:
    """One event as an SSE frame: ``id`` / ``event`` / ``data`` lines."""
    payload = json.dumps(ev["data"], separators=(",", ":"))
    return (
        f"id: {ev['id']}\nevent: {ev['type']}\ndata: {payload}\n\n"
    ).encode()


async def run_sse(response, hub: StreamHub, sub: Subscriber, after: int
                  ) -> None:
    """Drive one SSE connection: replay from ``after``, then live fan-out
    with keepalive comments.  Returns when the subscriber dies (slow
    consumer), the fault plane disconnects it, or the peer goes away.

    The ``stream.push`` seam fires per frame: ``disconnect`` aborts the
    transport mid-event (a partial frame hits the wire — the client's
    parser must resync on reconnect), ``slow_consumer`` stalls the
    writer until the hub marks the queue overflowed.
    """
    replayed, gap = hub.ring.since(after, sub.machines)
    if gap:
        _DROPPED.inc(1.0, "replay_gap")
        await response.write(
            b": replay-gap - events before this id were trimmed\n\n"
        )
    # the caller subscribed BEFORE this replay (so nothing lands in the
    # window between the two), which means events published during that
    # window sit in BOTH the replay batch and the queue — the id cursor
    # below filters the queued copies
    sent = replayed[-1]["id"] if replayed else after
    try:
        for ev in replayed:
            await response.write(sse_format(ev))
        while not sub.dead:
            try:
                ev = await asyncio.wait_for(
                    sub.queue.get(), timeout=keepalive_seconds()
                )
            except asyncio.TimeoutError:
                await response.write(b": keepalive\n\n")
                continue
            if ev["id"] <= sent:
                continue
            sent = ev["id"]
            if faults.enabled():
                try:
                    faults.check(
                        "stream.push", machine=ev["data"].get("machine", ""),
                        event_id=ev["id"],
                    )
                except faults.InjectedFault as exc:
                    if exc.mode == "slow_consumer":
                        # stall until the bounded queue overflows and the
                        # hub marks us dead — the real pathology (capped
                        # so a quiet hub can't wedge the writer forever)
                        stall_until = time.monotonic() + 10.0
                        while not sub.dead and time.monotonic() < stall_until:
                            await asyncio.sleep(0.005)
                        break
                    # mid-event disconnect: leak a partial frame, then die
                    await response.write(
                        f"id: {ev['id']}\nevent: {ev['type']}\n".encode()
                    )
                    raise
            if "time" in ev["data"]:
                _PUSH_SECONDS.observe(max(time.time() - ev["data"]["time"], 0.0))
            await response.write(sse_format(ev))
    finally:
        hub.unsubscribe(sub)


async def poll_events(
    hub: StreamHub,
    machines: Optional[Set[str]],
    after: int,
    timeout: Optional[float] = None,
) -> Dict[str, Any]:
    """Long-poll fallback: wait up to ``timeout`` for at least one event
    past ``after``, then return the batch + resume cursor as one doc."""
    timeout = poll_timeout_seconds() if timeout is None else timeout
    deadline = time.monotonic() + timeout
    # subscribe BEFORE the ring check so an event landing between the
    # two can't slip through the wait (the queue wakes us, the ring
    # re-read below is what actually returns it — ids dedup naturally)
    sub = hub.subscribe(machines)
    try:
        events, gap = hub.ring.since(after, machines)
        if not events and timeout > 0:
            remaining = deadline - time.monotonic()
            if remaining > 0:
                try:
                    await asyncio.wait_for(sub.queue.get(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass
            events, gap = hub.ring.since(after, machines)
    finally:
        hub.unsubscribe(sub)
    return {
        "events": events,
        "last-event-id": events[-1]["id"] if events else after,
        "replay-gap": gap,
    }
