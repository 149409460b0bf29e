"""Cross-request micro-batching: concurrent single-machine requests ride
one stacked device dispatch.

Reference equivalent: none — the reference's pod-per-model design gave
each request its own Flask worker and its own Keras predict; aggregate
throughput scaled only with pod count.  Here many machines share one chip,
and the per-request cost is DISPATCH (tiny program launch + transfer
latency), not compute: the measured single-machine HTTP route sustains
~600k samples/s while the stacked bulk route moves 3.1M on the same
hardware.  The coalescer closes that gap for clients that can't use the
bulk route: queued requests are grouped and scored through the SAME
vmapped fleet program the ``_bulk`` route uses, then sliced back per
request.

Batching policy (r6 — the r5 windowed drain lost 15% throughput and +48%
p99 at 64-way concurrency, BENCH_r05):

- **Continuous drain.**  The worker pulls the queue the moment it is free
  instead of idling through a fixed window; the previous dispatch's own
  service time is the accumulation window.  Under light load a lone
  request waits at most ``max_wait_s`` for a second rider; under heavy
  load nothing ever waits idle.
- **Knee cap.**  Effective batch size is capped at the measured
  throughput knee — the batch size past which a bigger dispatch no longer
  improves per-request amortization (it only stretches service time and
  p99).  ``knee_batch`` sets it explicitly; by default a short warmup
  sweep (:func:`estimate_knee`) measures it against the live fleet
  scorer, exercising the same gathered-subset and full-bucket dispatch
  paths production rounds use.
- **Assembly off the drain thread.**  The drain thread runs only the
  device dispatch (``FleetScorer.dispatch_all``); per-request result
  assembly and future resolution run on a separate finish pool, so
  response fan-out never serializes behind the next batch's gather.
- **Saturation stand-down.**  When queue wait runs away from service time
  (p99 wait > ``standdown_ratio`` × median service), batching is losing —
  new arrivals dispatch directly for ``standdown_cooldown_s`` while the
  queue drains, then coalescing resumes.  The combined path is never
  worse than direct for longer than one cooldown.

Semantics are identical to the per-machine path (same fused program
family, same padding rules, same per-machine error isolation).

Enabled via ``build_app(collection, coalesce_window_ms=...)`` /
``gordo run-server --coalesce-ms ...``; off by default.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from gordo_tpu import compile as compile_plane
from gordo_tpu import telemetry

logger = logging.getLogger(__name__)

# -- telemetry instruments (docs/observability.md) --------------------------
_REQUESTS_TOTAL = telemetry.counter(
    "gordo_coalesce_requests_total",
    "Requests entering the coalescer (stacked and fallback-routed)",
)
_DISPATCHES_TOTAL = telemetry.counter(
    "gordo_coalesce_dispatches_total",
    "Stacked device dispatches run by the drain worker",
)
_BYPASSED_TOTAL = telemetry.counter(
    "gordo_coalesce_bypassed_total",
    "Requests routed direct instead of coalescing, by reason",
    labels=("reason",),
)
_BATCH_SIZE = telemetry.histogram(
    "gordo_coalesce_batch_size",
    "Requests drained per batch (before round-splitting)",
    buckets=telemetry.metrics.DEFAULT_SIZE_BUCKETS,
)
_QUEUE_WAIT_SECONDS = telemetry.histogram(
    "gordo_coalesce_queue_wait_seconds",
    "Per-request wait between enqueue and dispatch",
)
_DISPATCH_SECONDS = telemetry.histogram(
    "gordo_coalesce_dispatch_seconds",
    "Device service time of one stacked coalesced dispatch",
)
_STANDDOWNS_TOTAL = telemetry.counter(
    "gordo_coalesce_standdowns_total",
    "Saturation stand-downs (batching judged losing; routing direct)",
)
_KNEE_ESTIMATES_TOTAL = telemetry.counter(
    "gordo_coalesce_knee_estimates_total",
    "Knee-sweep runs by outcome",
    labels=("outcome",),
)
_QUEUE_DEPTH_GAUGE = telemetry.gauge(
    "gordo_coalesce_queue_depth", "Requests currently queued for a dispatch"
)
_INFLIGHT_GAUGE = telemetry.gauge(
    "gordo_coalesce_inflight",
    "In-flight single-machine anomaly requests (the bypass signal)",
)
_BATCH_CAP_GAUGE = telemetry.gauge(
    "gordo_coalesce_batch_cap", "Effective per-dispatch batch bound"
)
_STANDING_DOWN_GAUGE = telemetry.gauge(
    "gordo_coalesce_standing_down",
    "1 while the saturation stand-down routes requests direct",
)
_WAIT_SERVICE_RATIO_GAUGE = telemetry.gauge(
    "gordo_coalesce_wait_service_ratio",
    "Latest p99 queue wait over median service time (the overload and "
    "HPA signal; stand-down fires past standdown_ratio, shedding past "
    "the first cooldown doubling)",
)
_SHEDDING_GAUGE = telemetry.gauge(
    "gordo_coalesce_shedding",
    "1 while escalated saturation sheds new requests with 429",
)
_EXPIRED_TOTAL = telemetry.counter(
    "gordo_coalesce_expired_total",
    "Queued riders dropped before dispatch because their propagated "
    "deadline (X-Gordo-Deadline-Ms) expired while waiting",
)


class DeadlineExpired(Exception):
    """A queued rider's propagated deadline passed before its batch
    dispatched — the client upstream has already given up, so scoring it
    would spend device time on a dead response.  The handler maps this
    to 504."""


def export_gauges(coalescer: Optional["CoalescingScorer"]) -> None:
    """Refresh the point-in-time coalescer gauges (called by the server's
    ``/metrics`` handler at scrape time — gauges describe 'now')."""
    if coalescer is None:
        return
    _QUEUE_DEPTH_GAUGE.set(len(coalescer._queue))
    _INFLIGHT_GAUGE.set(coalescer.inflight)
    _BATCH_CAP_GAUGE.set(coalescer.batch_cap)
    _STANDING_DOWN_GAUGE.set(1.0 if coalescer.standing_down else 0.0)
    _WAIT_SERVICE_RATIO_GAUGE.set(coalescer.wait_service_ratio)
    _SHEDDING_GAUGE.set(
        1.0 if shed_retry_after(coalescer) is not None else 0.0
    )


#: consecutive stand-downs before the server starts SHEDDING (429 +
#: Retry-After) instead of routing direct: the first stand-down is a
#: transient probe (base cooldown); the second is the first cooldown
#: doubling — overload that persisted through a full cooldown, where
#: accepting more work only queues it to death
SHED_MIN_STREAK = 2
#: Retry-After ceiling: a shed client should probe again within the
#: stand-down's own escalation horizon, not minutes later
SHED_RETRY_MAX_S = 30.0


def shed_retry_after(
    coalescer: Optional["CoalescingScorer"],
) -> Optional[float]:
    """Seconds a shed request should wait before retrying, or None when
    the server should accept work.

    Shedding engages when the saturation stand-down has ESCALATED — at
    least :data:`SHED_MIN_STREAK` consecutive stand-downs, i.e. the
    cooldown has started doubling — and the suggested delay derives from
    what was OBSERVED, not a constant: at least the p99 queue wait that
    tripped the signal (a retry sooner than that lands in the same
    queue), at least the remaining cooldown (before it, batching is
    still stood down), floored at 1s (the header's second granularity)
    and capped at :data:`SHED_RETRY_MAX_S`."""
    if coalescer is None:
        return None
    if not coalescer.standing_down:
        return None
    if coalescer._standdown_streak < SHED_MIN_STREAK:
        return None
    remaining = coalescer._standdown_until - time.monotonic()
    suggest = max(coalescer.last_wait_p99, remaining, 1.0)
    return min(suggest, SHED_RETRY_MAX_S)


#: knee sweep acceptance: doubling the batch must improve throughput by at
#: least this factor to keep doubling (1.1 = 10% — below that the bigger
#: dispatch only stretches p99 for no amortization gain)
KNEE_MIN_GAIN = 1.1


def estimate_knee(
    fleet: Any,
    rows: int = 1024,
    max_batch: int = 512,
    min_gain: float = KNEE_MIN_GAIN,
) -> Optional[Dict[str, float]]:
    """Short warmup sweep for the batch-size throughput knee.

    Doubles the dispatch size (1, 2, 4, …) against the fleet scorer's
    largest bucket — subset-gather dispatches below the bucket size, the
    full stacked program at it — and stops when throughput(b) <
    ``min_gain`` × throughput(b/2), i.e. when a bigger batch stops paying
    for its longer service time.  Each size is timed as the MIN of two
    warm repetitions: a single noisy rep once mis-measured the knee at 1
    and strangled the coalescer into serialized micro-batches (r6 bench,
    −20% at 8-way).

    Returns ``{"knee": b, "amortization": t(1)·b / t(b)}`` — the
    amortization factor is how many single-dispatch service times b
    batched requests cost; ~b on a dispatch-dominated device (flat
    service curve), ~1 when service scales linearly with batch (CPU
    compute-bound), where batching cannot pay at ANY size.  None when the
    fleet has no stacked bucket (nothing to batch into).

    Cost: ~3 dispatches per size, log2(max_batch) sizes — seconds, and
    every dispatch doubles as program warmup for the sizes coalesced
    rounds will actually run at.
    """
    buckets = getattr(fleet, "buckets", None)
    if not buckets:
        return None
    bucket = max(buckets, key=lambda b: len(b.names))
    names = bucket.names
    n_feat = bucket.n_features or 1
    rows = max(int(rows), bucket.lookback + 1)
    X = np.zeros((rows, n_feat), np.float32)
    knee = 1
    t1: Optional[float] = None
    prev_t: Optional[float] = None
    size = 1
    limit = min(int(max_batch), len(names))
    while size <= limit:
        sub = {n: X for n in names[:size]}
        fleet.score_all(sub)  # compile/warm — excluded from the timing
        t = float("inf")
        for _ in range(2):  # min-of-2: timing noise only ever ADDS
            t0 = time.perf_counter()
            fleet.score_all(sub)
            t = min(t, time.perf_counter() - t0)
        if size == 1:
            t1 = t
        if prev_t is not None and t * min_gain > 2.0 * prev_t:
            break  # throughput gain from doubling fell under min_gain
        knee, prev_t = size, t
        size *= 2
    return {
        "knee": knee,
        "amortization": (t1 * knee / prev_t) if prev_t else 1.0,
    }


class CoalescingScorer:
    """Queue single-machine anomaly requests; a worker drains them
    continuously and runs one ``FleetScorer`` dispatch per drained batch.

    ``fleet_provider`` is called per batch (not cached) so a collection
    rescan's scorer reset takes effect on the next dispatch.
    """

    def __init__(
        self,
        fleet_provider: Callable[[], Any],
        max_wait_s: float = 0.002,
        max_batch: int = 512,
        min_concurrency: int = 2,
        knee_batch: int = 0,
        min_amortization: float = 2.0,
        standdown_ratio: float = 4.0,
        standdown_cooldown_s: float = 0.5,
        standdown_max_s: float = 8.0,
        signal_window: int = 64,
    ):
        self._provider = fleet_provider
        #: single-rider grace: a batch of 1 gains nothing from the stacked
        #: gather, so when peers are in flight the drain waits up to this
        #: long for a second rider.  This is the ONLY wait left from the
        #: r5 windowed design — a queue with >=2 entries dispatches
        #: immediately.
        self.max_wait_s = float(max_wait_s)
        self.max_batch = int(max_batch)
        #: adaptive bypass: coalescing only ever wins when requests overlap
        #: (≥2 riders share a dispatch); below this many in-flight
        #: single-machine requests the route scores directly, so an idle or
        #: lightly-loaded server pays neither the rider wait nor the
        #: gather-dispatch overhead (r4 driver bench: coalescing at low
        #: concurrency cost 23% throughput / +66% p99)
        self.min_concurrency = int(min_concurrency)
        #: explicit batch cap (0 = auto-estimate the knee on first use)
        self.knee_batch = int(knee_batch)
        #: batching must amortize at least this many single-dispatch
        #: service times at the knee, or the sweep DISABLES coalescing
        #: outright: an amortization of ~1 (service linear in batch — the
        #: CPU compute-bound regime) means sharing a dispatch saves
        #: nothing and queueing can only add latency.  An explicit
        #: ``knee_batch`` skips the sweep and this check.
        self.min_amortization = float(min_amortization)
        self._knee_no_gain = False
        self.standdown_ratio = float(standdown_ratio)
        #: first stand-down lasts this long; CONSECUTIVE ones double it up
        #: to ``standdown_max_s`` — a regime where batching structurally
        #: loses converges to ~all-direct with rare short probes, instead
        #: of spending half its time in losing re-probes
        self.standdown_cooldown_s = float(standdown_cooldown_s)
        self.standdown_max_s = float(standdown_max_s)
        self._standdown_streak = 0
        self.signal_window = int(signal_window)
        #: in-flight single-machine anomaly requests, maintained by the
        #: route handler on the event loop (single-threaded increments)
        self.inflight = 0
        self.n_bypassed = 0
        self.n_queue_full = 0
        self.n_standdowns = 0
        self._standdown_until = 0.0
        #: latest saturation-signal evaluation (drain-thread writes;
        #: scrape/shed reads): p99 queue wait, and its ratio over median
        #: service time — the overload/HPA telemetry and the observed
        #: basis of a shed response's Retry-After
        self.last_wait_p99 = 0.0
        self.wait_service_ratio = 0.0
        self._knee: Optional[int] = None
        self._knee_started = False
        self._cv = threading.Condition()
        #: (name, X, future, enqueue time, trace id) — the trace id rides
        #: the queue so dispatch spans can name every rider they carried
        self._queue: List[
            Tuple[str, np.ndarray, Future, float, Optional[str],
                  Optional[float]]
        ] = []
        self._closed = False
        self.n_dispatches = 0
        self.n_requests = 0
        self.n_fallback = 0
        #: saturation signal state (drain-thread writes, stats reads)
        self._waits: deque = deque(maxlen=self.signal_window)
        self._services: deque = deque(maxlen=32)
        # machines the fleet scorer can't stack run its slow host-side
        # fallback; they score HERE instead, so one slow machine can't
        # head-of-line-block the stacked batches on the worker thread
        self._fallback_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="gordo-coalesce-fb"
        )
        #: result assembly + future resolution run here, NOT on the drain
        #: thread — the drain thread starts gathering the next batch the
        #: moment the device dispatch returns
        self._finish_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="gordo-coalesce-fin"
        )
        self._thread = threading.Thread(
            target=self._run, name="gordo-coalescer", daemon=True
        )
        self._thread.start()

    #: pre-knee batch cap: until the sweep lands, dispatches are bounded
    #: here rather than at max_batch — the r5 64-way loss was exactly
    #: uncapped saturated dispatches, and the estimate arrives within the
    #: first seconds of load
    PRE_KNEE_CAP = 64

    # -- batching policy -----------------------------------------------------
    @property
    def batch_cap(self) -> int:
        """Effective per-dispatch batch bound: the explicit ``knee_batch``,
        else the estimated knee, else a conservative pre-knee cap."""
        cap = (
            self.knee_batch
            or self._knee
            or min(self.max_batch, self.PRE_KNEE_CAP)
        )
        return max(1, min(cap, self.max_batch))

    def ensure_knee(self, rows: int = 1024) -> Optional[int]:
        """Estimate the knee once (idempotent; safe from any thread).
        Called from the server's warmup task when warmup is enabled, from
        the replay harness's warmup phase, and lazily (in the background)
        on the first live dispatch otherwise.

        When the sweep finds no amortization (service time ~linear in
        batch size), coalescing is DISABLED for this scorer's lifetime:
        batching that saves nothing can only add queueing latency, so the
        honest adaptive answer is to get out of the way entirely."""
        if self.knee_batch or self._knee is not None or self._knee_no_gain:
            return self._knee
        self._knee_started = True
        try:
            est = estimate_knee(
                self._provider(), rows=rows, max_batch=self.max_batch
            )
        except Exception:
            _KNEE_ESTIMATES_TOTAL.inc(1.0, "failed")
            logger.exception(
                "Knee estimation failed; batch cap stays at the pre-knee "
                "bound"
            )
            return None
        if est is None:
            _KNEE_ESTIMATES_TOTAL.inc(1.0, "no_bucket")
            return None
        if est["amortization"] < self.min_amortization:
            self._knee_no_gain = True
            _KNEE_ESTIMATES_TOTAL.inc(1.0, "no_gain")
            # one structured line: batching saves nothing on this backend,
            # every future request routes direct for this scorer's lifetime
            telemetry.log_event(
                logger, "coalescer_knee_no_gain",
                amortization=round(est["amortization"], 2),
                min_amortization=self.min_amortization,
                knee=int(est["knee"]),
            )
            return None
        self._knee = int(est["knee"])
        _KNEE_ESTIMATES_TOTAL.inc(1.0, "estimated")
        telemetry.log_event(
            logger, "coalescer_knee_estimated", level=logging.INFO,
            knee=self._knee, amortization=round(est["amortization"], 2),
        )
        return self._knee

    def _note_dispatch_signal(self, waits: List[float], service: float) -> None:
        """Record queue waits + service time; stand down when p99 wait says
        batching is losing (requests queue faster than dispatches clear)."""
        self._waits.extend(waits)
        self._services.append(service)
        if (
            len(self._waits) < max(4, self.signal_window // 4)
            or len(self._services) < 4
        ):
            return
        wait_p99 = float(np.percentile(np.asarray(self._waits), 99))
        med_service = float(np.median(np.asarray(self._services)))
        self.last_wait_p99 = wait_p99
        self.wait_service_ratio = wait_p99 / max(med_service, 1e-6)
        if wait_p99 > self.standdown_ratio * max(med_service, 1e-6):
            cooldown = min(
                self.standdown_cooldown_s * (2 ** self._standdown_streak),
                self.standdown_max_s,
            )
            self._standdown_streak += 1
            self._standdown_until = time.monotonic() + cooldown
            self.n_standdowns += 1
            _STANDDOWNS_TOTAL.inc()
            # waits reset (they describe the regime we just left); service
            # times stay — they remain valid and let a post-cooldown probe
            # re-evaluate after only ~signal_window/4 fresh waits
            self._waits.clear()
            # one structured line per stand-down (the satellite contract:
            # these transitions were previously invisible at runtime)
            telemetry.log_event(
                logger, "coalescer_standdown",
                cooldown_s=round(cooldown, 2),
                wait_p99_ms=round(wait_p99 * 1e3, 1),
                service_median_ms=round(med_service * 1e3, 1),
                streak=self._standdown_streak,
            )
        else:
            # a healthy evaluation ends the escalation: the next
            # stand-down (if any) starts from the base cooldown again
            self._standdown_streak = 0

    @property
    def standing_down(self) -> bool:
        return time.monotonic() < self._standdown_until

    # -- producer side -------------------------------------------------------
    def should_coalesce(self) -> bool:
        """True when enough requests are in flight for a shared dispatch to
        pay for itself, the saturation signal isn't standing the coalescer
        down, AND the queue isn't already saturated; callers score
        directly otherwise (and count the bypass for the stats endpoint).

        The queue-depth backpressure is the per-request loss bound: once
        the queue holds two knee-capped dispatches' worth, a new rider
        would wait >= 2 service times with no amortization gain, so it
        dispatches direct instead — under saturation the combined path
        degrades to ~direct continuously, without waiting for the
        stand-down signal to accumulate."""
        if self._knee_no_gain or self.standing_down:
            self.n_bypassed += 1
            _BYPASSED_TOTAL.inc(
                1.0, "no_gain" if self._knee_no_gain else "standdown"
            )
            return False
        if compile_plane.warming():
            # startup warmup still compiling: queue behind it rather than
            # dispatch direct — a direct dispatch would block an executor
            # thread on its own cold compile of the very program the
            # warmup is about to land, while queued riders share ONE
            # compile when the drain gets to them
            return True
        if self.inflight < self.min_concurrency:
            self.n_bypassed += 1
            _BYPASSED_TOTAL.inc(1.0, "low_concurrency")
            return False
        # len() on the queue list is GIL-atomic; a stale read only shifts
        # one request between two correct paths
        if len(self._queue) >= 2 * self.batch_cap:
            self.n_queue_full += 1
            self.n_bypassed += 1
            _BYPASSED_TOTAL.inc(1.0, "queue_full")
            return False
        return True

    def reset_stats(self) -> None:
        """Zero the counters (requests/dispatches/bypasses) without
        touching the learned policy state (knee, no-gain flag, stand-down
        escalation) — benches call this after their warmup phase so the
        reported stats describe only the measured window."""
        self.n_requests = 0
        self.n_dispatches = 0
        self.n_fallback = 0
        self.n_bypassed = 0
        self.n_queue_full = 0
        self.n_standdowns = 0

    def submit(
        self, name: str, X: np.ndarray, trace_id: Optional[str] = None,
        deadline: Optional[float] = None,
    ) -> Future:
        """Enqueue one machine's rows; the Future resolves to the same
        arrays dict ``CompiledScorer.anomaly_arrays`` returns.
        ``trace_id`` (the request's propagated id) tags the dispatch span
        this request ends up riding.  ``deadline`` (a ``time.monotonic()``
        timestamp from the propagated budget) lets the drain drop this
        rider with :class:`DeadlineExpired` instead of dispatching work
        the client already abandoned."""
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise RuntimeError("CoalescingScorer is closed")
            self._queue.append(
                (name, X, fut, time.monotonic(), trace_id, deadline)
            )
            self._cv.notify()
        return fut

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout=5)
        # drain thread no longer submits; let in-flight assemblies resolve
        # their futures before the pool dies
        self._finish_pool.shutdown(wait=True)
        self._fallback_pool.shutdown(wait=False)

    # -- worker side ---------------------------------------------------------
    def _drain(
        self,
    ) -> List[Tuple[str, np.ndarray, Future, float, Optional[str],
                    Optional[float]]]:
        """Continuous drain: block for work, take what's queued (up to the
        knee cap) NOW.  The only wait is the single-rider grace — one
        queued request with peers still in flight holds ``max_wait_s`` for
        a second rider, because a batch of 1 cannot amortize anything.
        A rider carrying a propagated deadline caps the grace at its own
        remaining budget (deadline-aware admission: holding a request
        past the point its client gives up turns the grace into a 504)."""
        with self._cv:
            while not self._queue and not self._closed:
                self._cv.wait()
            if not self._queue:
                return []
            if (
                len(self._queue) == 1
                and self.inflight > 1
                and self.max_wait_s > 0
            ):
                deadline = time.monotonic() + self.max_wait_s
                rider_deadline = self._queue[0][5]
                if rider_deadline is not None:
                    deadline = min(deadline, rider_deadline)
                while len(self._queue) == 1 and not self._closed:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(remaining)
            # hand over at most batch_cap; the rest stays queued for the
            # IMMEDIATE next iteration (no idle window between dispatches)
            cap = self.batch_cap
            batch = self._queue[:cap]
            self._queue = self._queue[cap:]
            return batch

    def _run(self) -> None:
        while True:
            try:
                batch = self._drain()
                if not batch:
                    if self._closed:
                        return
                    continue
                t_dispatch = time.monotonic()
                # expired riders resolve with DeadlineExpired BEFORE the
                # dispatch: their clients already gave up, and dropping
                # them here frees the batch slot for live work
                live = []
                for item in batch:
                    dl = item[5]
                    if dl is not None and t_dispatch >= dl:
                        _EXPIRED_TOTAL.inc()
                        self._resolve(item[2], exc=DeadlineExpired(
                            f"rider for {item[0]!r} expired "
                            f"{t_dispatch - dl:.3f}s before dispatch"
                        ))
                    else:
                        live.append(item)
                batch = live
                if not batch:
                    continue
                waits = [
                    t_dispatch - t_enq for _, _, _, t_enq, _, _ in batch
                ]
                for w in waits:
                    _QUEUE_WAIT_SECONDS.observe(w)
                _BATCH_SIZE.observe(len(batch))
                # score_all keys by machine name, so duplicate-name requests
                # split into successive rounds (each round has unique names)
                rounds: List[
                    Dict[str, Tuple[np.ndarray, Future, Optional[str]]]
                ] = []
                for name, X, fut, _, tid, _ in batch:
                    for rnd in rounds:
                        if name not in rnd:
                            rnd[name] = (X, fut, tid)
                            break
                    else:
                        rounds.append({name: (X, fut, tid)})
                service = 0.0
                for rnd in rounds:
                    service += self._score_round(rnd)
                if service > 0:
                    self._note_dispatch_signal(waits, service)
            except Exception:
                # the worker must be unkillable: a dead worker would leave
                # every future unresolved and the route hanging forever
                logger.exception("Coalescer worker iteration failed")

    @staticmethod
    def _resolve(fut: Future, res: Any = None, exc: Optional[Exception] = None) -> None:
        """Resolve a future that a disconnecting client may cancel at any
        moment: set_running_or_notify_cancel() closes the PENDING->cancel
        race (a RUNNING future cannot be cancelled), and the InvalidState
        guard keeps the worker alive no matter what."""
        try:
            if not fut.set_running_or_notify_cancel():
                return  # cancelled before scoring completed
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(res)
        except Exception:
            logger.exception("Failed to resolve coalesced future")

    def _score_one(self, scorer: Any, name: str, X: np.ndarray, fut: Future) -> None:
        """Score a non-stackable machine on the fallback pool."""
        try:
            out = scorer.score_all({name: X})
        except Exception as exc:
            self._resolve(fut, exc=exc)
            return
        self._finish(name, fut, out)

    def _score_round(
        self, rnd: Dict[str, Tuple[np.ndarray, Future, Optional[str]]]
    ) -> float:
        """Dispatch one unique-name round; returns the device service time
        (0.0 when nothing reached a stacked dispatch)."""
        self.n_requests += len(rnd)
        _REQUESTS_TOTAL.inc(len(rnd))
        try:
            scorer = self._provider()
        except Exception as exc:
            for _, fut, _ in rnd.values():
                self._resolve(fut, exc=exc)
            return 0.0
        if not self._knee_started and not self.knee_batch:
            # lazy knee estimation off the drain thread: until it lands the
            # cap is max_batch (the r5 behavior); the sweep doubles as
            # subset-program warmup.  Row hint: this round's request shape.
            self._knee_started = True
            rows = max(x.shape[0] for x, _, _ in rnd.values())
            self._fallback_pool.submit(self.ensure_knee, rows)
        # machines outside the stacked buckets run FleetScorer's host-side
        # fallback (potentially 100s of ms each) — push those off the
        # worker so they can't head-of-line-block the fast stacked batch
        stacked = {}
        for name, (X, fut, tid) in rnd.items():
            if name in scorer.machine_bucket or name not in scorer.models:
                stacked[name] = (X, fut, tid)  # unknown names error in-slot
            else:
                self.n_fallback += 1
                self._fallback_pool.submit(
                    self._score_one, scorer, name, X, fut
                )
        if not stacked:
            return 0.0
        rnd = stacked
        self.n_dispatches += 1
        _DISPATCHES_TOTAL.inc()
        t0 = time.monotonic()
        # the dispatch span carries every rider's propagated trace id, so
        # a request's timeline can be followed INTO the shared dispatch
        riders = sorted(
            {tid for _, _, tid in rnd.values() if tid is not None}
        )
        with telemetry.span(
            "coalesce.dispatch", batch=len(rnd), traces=riders
        ):
            try:
                # dispatch_all runs the device work (stack → dispatch →
                # device_get) and defers per-machine assembly; scorers
                # without the split API (tests, exotic providers) do both
                # here
                dispatch = getattr(scorer, "dispatch_all", None)
                X_map = {n: x for n, (x, _, _) in rnd.items()}
                pending = dispatch(X_map) if dispatch is not None else (
                    scorer.score_all(X_map)
                )
            except Exception as exc:  # whole-dispatch failure: fail futures
                logger.exception("Coalesced dispatch failed")
                for _, fut, _ in rnd.values():
                    self._resolve(fut, exc=exc)
                service = time.monotonic() - t0
                _DISPATCH_SECONDS.observe(service)
                return service
        service = time.monotonic() - t0
        _DISPATCH_SECONDS.observe(service)
        # per-request result assembly + future resolution run on the
        # finish pool: the drain thread is free to gather the next batch
        self._finish_pool.submit(self._finish_round, rnd, pending)
        return service

    def _finish_round(
        self,
        rnd: Dict[str, Tuple[np.ndarray, Future, Optional[str]]],
        pending: Any,
    ) -> None:
        """Assemble per-machine results (host-side numpy slicing) and
        resolve the round's futures — off the drain thread.

        This stays the NON-columnar ``assemble``: a coalesced round
        fans out to many single-machine responses, each negotiated and
        encoded for its own requester, so the per-machine split happens
        here regardless of wire format.  The GSB1 columnar path
        (``assemble_columnar`` + ``encode_columnar``) belongs to the
        ``_bulk`` route, which bypasses the coalescer entirely — one
        requester consumes the whole stacked result."""
        try:
            assemble = getattr(pending, "assemble", None)
            out = assemble() if assemble is not None else pending
        except Exception as exc:
            logger.exception("Coalesced result assembly failed")
            for _, fut, _ in rnd.values():
                self._resolve(fut, exc=exc)
            return
        for name, (_, fut, _) in rnd.items():
            self._finish(name, fut, out)

    def _finish(self, name: str, fut: Future, out: Dict[str, Any]) -> None:
        res = out.get(name)
        if res is None:
            self._resolve(
                fut, exc=RuntimeError(f"No result for machine {name!r}")
            )
        elif "error" in res and "model-output" not in res:
            # same exception surface as the per-machine scorer path:
            # client-input problems raise ValueError (-> HTTP 400),
            # everything else RuntimeError (-> 500)
            exc_cls = (
                ValueError if res.get("client-error") else RuntimeError
            )
            self._resolve(fut, exc=exc_cls(str(res["error"])))
        else:
            self._resolve(fut, res=res)


def stats(coalescer: Optional[CoalescingScorer]) -> Dict[str, Any]:
    if coalescer is None:
        return {"enabled": False}
    stacked = coalescer.n_requests - coalescer.n_fallback
    return {
        "enabled": True,
        "requests": coalescer.n_requests,
        "fallback_requests": coalescer.n_fallback,
        "bypassed_requests": coalescer.n_bypassed,
        "min_concurrency": coalescer.min_concurrency,
        "dispatches": coalescer.n_dispatches,
        # amortization of the STACKED path only — fallback-routed requests
        # never ride a dispatch and must not inflate the ratio
        "mean_batch": (
            round(stacked / coalescer.n_dispatches, 2)
            if coalescer.n_dispatches
            else None
        ),
        # r6 adaptive policy state
        "batch_cap": coalescer.batch_cap,
        "knee_batch": coalescer.knee_batch or None,
        "knee_estimated": coalescer._knee,
        "knee_no_gain": coalescer._knee_no_gain,
        "queue_full_bypassed": coalescer.n_queue_full,
        "standdowns": coalescer.n_standdowns,
        "standing_down": coalescer.standing_down,
        "shedding": shed_retry_after(coalescer) is not None,
        "wait_service_ratio": round(coalescer.wait_service_ratio, 2),
    }
