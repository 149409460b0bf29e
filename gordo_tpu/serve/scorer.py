"""Fused serving scorer: one jitted program per (model, shape-bucket).

Reference equivalent: the server view path
``server/views/base.py -> model.predict`` /
``views/anomaly.py -> DiffBasedAnomalyDetector.anomaly`` — there a chain of
host-side sklearn transforms, a Keras predict, and pandas frame assembly
per request.

Here the entire scoring pipeline — scaler chain, windowing, network apply,
detector scaling, |diff|, L2 total, threshold comparison — is ONE XLA
program of ``(X,) -> arrays``.  Request row counts are padded up to
power-of-two buckets so the jit cache stays small (a handful of compiles
serve any stream); padded rows are sliced off before response assembly.

The structural requirements are the same as the fleet engine's
(``parallel/anomaly.py``): pure-stats scalers + a BaseJaxEstimator.  Models
that don't match run through their own (slower, host-side) ``.anomaly`` /
``.predict`` methods transparently.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import compile as compile_plane
from gordo_tpu import telemetry
from gordo_tpu.anomaly.base import AnomalyDetectorBase
from gordo_tpu.anomaly.diff import DiffBasedAnomalyDetector, scores_fn
from gordo_tpu.models.estimator import (
    BaseJaxEstimator,
    LSTMAutoEncoder,
    LSTMForecast,
    SequenceForecast,
)
from gordo_tpu.ops.windows import make_windows
from gordo_tpu.pipeline import Pipeline
from gordo_tpu.serve import precision

# -- telemetry instruments (docs/observability.md "Serving dispatch") -------
#: the single-dispatch attestation pair: on the fused request path a
#: request is decode → ONE input transfer → ONE device dispatch → encode,
#: and these counters are the evidence (bench serving_precision asserts
#: deltas == request counts; divergence means host-side work crept back in)
_DISPATCHES = telemetry.counter(
    "gordo_serve_dispatches_total",
    "Device dispatches issued by the serving scorers, by program",
    labels=("program",),
)
_H2D = telemetry.counter(
    "gordo_serve_input_transfers_total",
    "Host-to-device input transfers on the serving request path, "
    "by program",
    labels=("program",),
)

#: smallest compile bucket; requests below this pad up to it: 256 halves
#: jit-cache entries vs 64 while keeping small-request compute waste
#: bounded.  Not re-measured on an attached chip.
MIN_BUCKET = 256

#: one-shot smoothing windows-tensor ceiling (elements) — past this, the
#: scorer switches to the blocked rolling median rather than leaving the
#: device.  Not re-measured on an attached chip.
SMOOTH_ONE_SHOT_BOUND = 2 ** 27
#: per-block windows-tensor size the blocked median aims for (~64MB f32)
SMOOTH_BLOCK_TARGET = 2 ** 24


def short_rows_message(offset: int, rows: int) -> str:
    """The one short-rows client-error text — the direct, bulk, and
    coalesced transports must emit identical 400 bodies."""
    return (
        f"needs more than {offset} rows (lookback window), got {rows}"
    )


def _bucket_rows(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def _fused_enabled() -> bool:
    """``GORDO_SERVE_FUSED=off`` routes the diff-anomaly epilogue
    (threshold/confidence math) and request padding back through host
    numpy — the r11 request path, kept ONLY as the measured baseline for
    ``bench --stage serving_precision`` and the fused-vs-host parity pin.
    Production serving never turns this off."""
    return os.environ.get("GORDO_SERVE_FUSED", "on").strip().lower() not in (
        "off", "0", "false",
    )


def _legacy_pad(X: np.ndarray, bucket: int) -> np.ndarray:
    """The r11 host-side repeat-last pad (double copy: concatenate then
    the transfer).  Only reachable with ``GORDO_SERVE_FUSED=off``; the
    fused path writes into a pinned pad buffer instead."""
    return np.concatenate([X, np.tile(X[-1:], (bucket - X.shape[0], 1))])


class SequenceModelUnsupported(NotImplementedError):
    """A plane that would have to carry a sequence model's state (per-layer
    recurrent state, a latent cache) or stack such models was handed one."""


def refuse_sequence_model(model, machine: str, plane: str) -> None:
    """Raise for a model whose estimator is a ``SequenceForecast``: ``plane``
    (the stacked scorer, the streaming scorer, the backfill runner) scores
    windowed and row-wise estimators only, and would score this one wrongly.
    Its own ``anomaly()`` (``CompiledScorer``'s fallback) does score it."""
    base = getattr(model, "base_estimator", model)
    est = base._final if isinstance(base, Pipeline) else base
    if isinstance(est, SequenceForecast):
        raise SequenceModelUnsupported(
            f"{plane} cannot score machine {machine!r}: its estimator "
            f"{type(est).__name__} (kind {est.kind!r}) reads the series as "
            "sequences; score it through the detector's own anomaly()"
        )


def _extract_chain(model) -> Optional[Dict[str, Any]]:
    """Pull the pure pieces out of a detector/pipeline/estimator, or None
    (a ``SequenceForecast`` has no fused chain: its detector's own
    ``anomaly()`` scores it)."""
    detector = None
    base = model
    if isinstance(model, DiffBasedAnomalyDetector):
        detector = model
        base = model.base_estimator

    scalers: List[Tuple[type, dict]] = []
    if isinstance(base, Pipeline):
        for _, step in base.steps[:-1]:
            stats = getattr(step, "stats_", None)
            if stats is None or type(step).apply.__qualname__.startswith(
                "BaseTransform"
            ):
                return None
            scalers.append((type(step), stats))
        est = base._final
    else:
        est = base
    if not isinstance(est, BaseJaxEstimator) or est.params_ is None:
        return None
    if isinstance(est, SequenceForecast):
        return None
    if est.module_ is None:
        est._rebuild_module()

    if isinstance(est, LSTMForecast):
        mode, lookback = "forecast", est.lookback_window
    elif isinstance(est, LSTMAutoEncoder):
        mode, lookback = "ae", est.lookback_window
    else:
        mode, lookback = "none", 1

    chain: Dict[str, Any] = {
        "scalers": scalers,
        "module": est.module_,
        "params": est.params_,
        "mode": mode,
        "lookback": lookback,
        "detector": None,
    }
    if detector is not None:
        if detector.scaler is None or getattr(detector.scaler, "stats_", None) is None:
            return None
        chain["detector"] = {
            "scaler_cls": type(detector.scaler),
            "scaler_stats": detector.scaler.stats_,
            "feature_thresholds": detector.feature_thresholds_,
            "aggregate_threshold": detector.aggregate_threshold_,
            "require_thresholds": detector.require_thresholds,
            "window": int(detector.window or 0),
        }
    return chain


def _rolling_median(a: jnp.ndarray, window: int) -> jnp.ndarray:
    """Trailing rolling median with ``min_periods=1`` — matches the pandas
    smoothing in ``DiffBasedAnomalyDetector.anomaly`` exactly (early rows
    take the median of however many samples exist)."""
    squeeze = a.ndim == 1
    if squeeze:
        a = a[:, None]
    pad = jnp.full((window - 1,) + a.shape[1:], jnp.nan, a.dtype)
    windows = make_windows(jnp.concatenate([pad, a], axis=0), window)
    out = jnp.nanmedian(windows, axis=1)
    return out[:, 0] if squeeze else out


def _rolling_median_blocked(
    a: jnp.ndarray, window: int, block_rows: int
) -> jnp.ndarray:
    """:func:`_rolling_median` with the windows tensor materialized only
    ``block_rows`` rows at a time (``lax.map`` over row blocks, each block
    sliced with ``window - 1`` rows of preceding context).

    Bit-identical to the one-shot version; memory drops from
    ``n x window x tags`` to ``block_rows x window x tags`` per step.
    Exists because the one-shot tensor has a hard compile ceiling on TPU
    (measured r4: 2^27.5 elements OK, 2^28.5 fails XLA) — beyond it, huge
    smoothed requests previously fell off the device entirely.
    """
    squeeze = a.ndim == 1
    if squeeze:
        a = a[:, None]
    n, f = a.shape
    n_blocks = -(-n // block_rows)
    ctx = jnp.full((window - 1, f), jnp.nan, a.dtype)
    tail = jnp.full((n_blocks * block_rows - n, f), jnp.nan, a.dtype)
    buf = jnp.concatenate([ctx, a, tail], axis=0)

    def one(start):
        blk = jax.lax.dynamic_slice(
            buf, (start, 0), (block_rows + window - 1, f)
        )
        return jnp.nanmedian(make_windows(blk, window), axis=1)

    out = jax.lax.map(one, jnp.arange(n_blocks) * block_rows)
    out = out.reshape(n_blocks * block_rows, f)[:n]
    return out[:, 0] if squeeze else out


def _score_program_fn(
    module,
    scaler_classes,
    mode,
    lookback,
    det_cls,
    with_anomaly,
    smooth_window,
    dtype,
    with_confidence,
    scaler_stats,
    params,
    det_stats,
    agg_threshold,
    X,
    smooth_block=0,
):
    """(X padded to bucket) -> dict of arrays; the whole pipeline fused —
    scaler chain, windowing, network apply, detector scaling, |diff|, L2
    total, smoothing, AND the confidence epilogue — at the serving
    precision ``dtype`` (a static: it keys the compiled executable).
    Outputs always leave the program as float32, so the response schema
    is dtype-invariant; reduced precision is an internal compute matter
    gated by the fp32 parity suite."""
    Xc = precision.cast_input(X, dtype)
    scaler_stats = precision.cast_params(scaler_stats, dtype)
    params = precision.cast_params(params, dtype)
    det_stats = precision.cast_params(det_stats, dtype)
    Xs = Xc
    for cls, stats in zip(scaler_classes, scaler_stats):
        Xs = cls.apply(stats, Xs)

    if mode == "none":
        inputs = Xs
    elif mode == "ae":
        inputs = make_windows(Xs, lookback)
    else:  # forecast
        inputs = make_windows(Xs[:-1], lookback)

    pred = module.apply({"params": params}, inputs)
    out = {"model-output": pred.astype(jnp.float32)}
    if with_anomaly:
        offset = X.shape[0] - pred.shape[0]
        y_al = Xc[offset:]
        tag, total = scores_fn(det_cls, det_stats, y_al, pred)
        if smooth_window and smooth_block:
            tag = _rolling_median_blocked(tag, smooth_window, smooth_block)
            total = _rolling_median_blocked(
                total, smooth_window, smooth_block
            )
        elif smooth_window:
            tag = _rolling_median(tag, smooth_window)
            total = _rolling_median(total, smooth_window)
        tag = tag.astype(jnp.float32)
        total = total.astype(jnp.float32)
        out["tag-anomaly-scores"] = tag
        out["total-anomaly-score"] = total
        if with_confidence:
            # the diff-anomaly epilogue, fused: confidence is computed on
            # device in f32 (thresholds never quantize) — the last piece
            # of host numpy the request path used to pay per request
            out["anomaly-confidence"] = total / jnp.maximum(
                agg_threshold.astype(jnp.float32), 1e-12
            )
    return out


#: the per-machine fused serving program, owned by the compile plane: the
#: server's startup warmup AOT-compiles it per (signature, row bucket,
#: serving dtype) before the readiness flip, so the first request never
#: traces
_score_program = compile_plane.program(
    "serve.score",
    _score_program_fn,
    static_argnames=(
        "module", "scaler_classes", "mode", "lookback", "det_cls",
        "with_anomaly", "smooth_window", "dtype", "with_confidence",
        "smooth_block",
    ),
)


def _program_args(
    c: Dict[str, Any],
    X: Any,
    with_anomaly: bool,
    smooth_block: int,
    dtype: str,
    with_confidence: bool,
) -> Tuple[Tuple, Dict[str, Any]]:
    """The ONE assembly of ``_score_program``'s arguments — the dispatch
    path (``_run``) and the AOT warmup (``warm_programs``) must agree on
    every static value and pytree layout, or the warmed executable would
    never be the one a request looks up."""
    det = c["detector"]
    args = (
        c["module"],
        tuple(cls for cls, _ in c["scalers"]),
        c["mode"],
        c["lookback"],
        det["scaler_cls"] if det else None,
        bool(with_anomaly and det),
        det["window"] if (det and with_anomaly) else 0,
        dtype,
        with_confidence,
        tuple(stats for _, stats in c["scalers"]),
        c["params"],
        det["scaler_stats"] if det else None,
        # a () f32 leaf, not a python float: its signature must be
        # identical between warm (ShapeDtypeStruct-adjacent) and dispatch
        np.float32(det["aggregate_threshold"]) if with_confidence else None,
        X,
    )
    return args, {"smooth_block": smooth_block}


class CompiledScorer:
    """Callable scoring surface over one model; jitted when possible.

    ``dtype``: the serving precision this scorer dispatches at
    (``None`` resolves ``GORDO_SERVE_DTYPE`` per call — the env knob is
    live for tests and embedding callers; collections resolve once and
    pass it explicitly so a whole fleet serves one precision).

    ``machine``: the fleet machine name this scorer serves, when known
    (``ModelEntry`` and the fleet scorer's per-machine paths set it).
    With a name, every anomaly response's total-anomaly-score array
    folds into that machine's fleet-health sketch
    (:mod:`gordo_tpu.telemetry.fleet_health`) — accumulated from the
    host arrays already fetched for response encoding, so the hot path
    pays one vectorized bincount and no extra D2H.  Nameless scorers
    (ad-hoc/bench embedding) record nothing.
    """

    #: max retained pinned pad buffers (power-of-two row bucketing keeps
    #: distinct request shapes log-few; mirrors _Bucket.MAX_STACK_BUFS)
    MAX_PAD_BUFS = 4

    def __init__(
        self,
        model,
        dtype: Optional[str] = None,
        machine: Optional[str] = None,
    ):
        self.model = model
        self.chain = _extract_chain(model)
        self.is_anomaly = isinstance(model, AnomalyDetectorBase)
        self.offset = getattr(model, "offset", 0)
        self.machine = machine
        self._dtype = precision.canonical(dtype) if dtype else None
        #: pinned host pad buffers keyed by (bucket_rows, n_features),
        #: reused while request shapes repeat: padding writes ONE copy
        #: into the buffer and the transfer is the only other touch —
        #: the r11 path concatenated a fresh padded array first (two
        #: copies per request).  Guarded by _pad_lock: concurrent
        #: requests for one machine run _run from executor threads.
        self._pad_bufs: "OrderedDict[Tuple[int, int], np.ndarray]" = (
            OrderedDict()
        )
        self._pad_lock = threading.Lock()

    @property
    def fused(self) -> bool:
        return self.chain is not None

    @property
    def dtype(self) -> str:
        return self._dtype or precision.serve_dtype()

    def _pad_buffer(self, shape: Tuple[int, int]) -> np.ndarray:
        """Pinned pad buffer for ``shape`` (call with ``_pad_lock`` held)."""
        buf = self._pad_bufs.get(shape)
        if buf is None:
            buf = self._pad_bufs[shape] = np.empty(shape, np.float32)
            while len(self._pad_bufs) > self.MAX_PAD_BUFS:
                self._pad_bufs.popitem(last=False)
        else:
            self._pad_bufs.move_to_end(shape)
        return buf

    # -- fused path ----------------------------------------------------------
    def _run(
        self, X: np.ndarray, with_anomaly: bool, smooth_block: int = 0
    ) -> Dict[str, np.ndarray]:
        c = self.chain
        det = c["detector"]
        dtype = self.dtype
        fused = _fused_enabled()
        with_confidence = bool(
            with_anomaly and fused and det
            and det["feature_thresholds"] is not None
        )
        n = X.shape[0]
        bucket = _bucket_rows(n)
        if bucket != n and not fused:
            X = _legacy_pad(X, bucket)
        if bucket != n and fused:
            # single-copy repeat-last padding into the pinned buffer; the
            # lock spans fill -> transfer so a concurrent request can't
            # overwrite rows mid-copy.  jnp.array (copy=True), NOT
            # jnp.asarray: on the CPU backend asarray may ZERO-COPY ALIAS
            # the numpy buffer, and the next same-bucket request would
            # then rewrite this request's live device array after the
            # lock drops (observed as coalesced-vs-direct mismatches
            # under concurrency).  On real accelerators the H2D DMA is
            # the copy either way.  The input transfer stays f32 (the
            # client's precision); reduced-precision casts happen inside
            # the program, where they are free.
            with self._pad_lock:
                buf = self._pad_buffer((bucket, X.shape[1]))
                buf[:n] = X
                buf[n:] = X[-1:]
                _H2D.inc(1.0, "serve.score")
                Xd = jnp.array(buf, jnp.float32)
        else:
            _H2D.inc(1.0, "serve.score")
            Xd = jnp.asarray(X, jnp.float32)
        args, kw = _program_args(
            c, Xd, with_anomaly, smooth_block, dtype, with_confidence
        )
        # the ONE device dispatch of this request (attested by bench
        # serving_precision: counter delta == request count)
        _DISPATCHES.inc(1.0, "serve.score")
        out = _score_program(*args, **kw)
        n_valid = n - self.offset
        return {k: np.asarray(v)[:n_valid] for k, v in out.items()}

    def warm_programs(
        self, rows: int, n_features: int, dtype: Optional[str] = None
    ) -> List[Tuple[str, float]]:
        """AOT-compile this machine's fused program(s) for one row bucket
        — shape structs only, nothing executes.  ``dtype`` defaults to
        this scorer's serving dtype, so warmed executables are the ones
        dispatch looks up.  Returns ``[(label, compile_seconds), ...]``
        (0.0 = already compiled)."""
        if not self.fused:
            return []
        dtype = precision.canonical(dtype) if dtype else self.dtype
        X = jax.ShapeDtypeStruct((int(rows), int(n_features)), jnp.float32)
        det = self.chain["detector"]
        out: List[Tuple[str, float]] = []
        variants = [("serve.score/predict", False)]
        if self.is_anomaly and det is not None and not (
            det["feature_thresholds"] is None and det["require_thresholds"]
        ):
            variants.append(("serve.score/anomaly", True))
        for label, with_anomaly in variants:
            with_confidence = bool(
                with_anomaly and _fused_enabled() and det
                and det["feature_thresholds"] is not None
            )
            args, kw = _program_args(
                self.chain, X, with_anomaly, 0, dtype, with_confidence
            )
            out.append((label, _score_program.warm(*args, **kw)))
        return out

    def _require_rows(self, X: np.ndarray) -> None:
        """Windowed models consume ``offset`` rows; fewer input rows than
        that would slice the padded output with a NEGATIVE bound and return
        silently wrong arrays — reject as a client error instead."""
        if X.shape[0] <= self.offset:
            raise ValueError(short_rows_message(self.offset, X.shape[0]))

    def _input_windows_within_bound(self, X: np.ndarray) -> bool:
        """The fused program materializes the model-input windows tensor
        ``(n, lookback, tags)`` one-shot; past the measured compile
        ceiling there is no blocked variant (inference consumes the
        windows), so callers route such requests to the host path."""
        if self.chain["mode"] == "none":
            return True
        n_feat = max(X.shape[1], 1)
        return (
            _bucket_rows(X.shape[0]) * self.chain["lookback"] * n_feat
            <= SMOOTH_ONE_SHOT_BOUND
        )

    # -- public surface ------------------------------------------------------
    def predict(self, X) -> np.ndarray:
        X = np.asarray(X, np.float32)
        self._require_rows(X)
        if self.fused and self._input_windows_within_bound(X):
            return self._run(X, with_anomaly=False)["model-output"]
        return np.asarray(self.model.predict(X))

    def anomaly_arrays(self, X, y: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Anomaly scoring as plain arrays (no pandas on the hot path)."""
        if not self.is_anomaly:
            raise TypeError(
                f"{type(self.model).__name__} is not an anomaly detector"
            )
        X = np.asarray(X, np.float32)
        self._require_rows(X)
        use_fused = (
            self.fused
            and (y is None or y is X)
            and self._input_windows_within_bound(X)
        )
        smooth_block = 0
        if use_fused and self.chain["detector"]["window"]:
            # the one-shot smoothing path materializes an (n, window, tags)
            # windows tensor; past the measured device bound, switch to the
            # blocked rolling median (identical results, lax.map over row
            # blocks) instead of leaving the device
            det_w = self.chain["detector"]["window"]
            n_feat = max(X.shape[1], 1)
            if (
                _bucket_rows(X.shape[0]) * det_w * n_feat
                > SMOOTH_ONE_SHOT_BOUND
            ):
                smooth_block = max(
                    1, SMOOTH_BLOCK_TARGET // (det_w * n_feat)
                )
        if use_fused:
            det = self.chain["detector"]
            if det["feature_thresholds"] is None and det["require_thresholds"]:
                # same contract as DiffBasedAnomalyDetector.anomaly: refuse
                # to emit unthresholded scores.
                raise AttributeError(
                    "DiffBasedAnomalyDetector.anomaly called with "
                    "require_thresholds=True but cross_validate() has not "
                    "been run to derive thresholds"
                )
            out = self._run(X, with_anomaly=True, smooth_block=smooth_block)
            result = {
                "model-output": out["model-output"],
                "tag-anomaly-scores": out["tag-anomaly-scores"],
                "total-anomaly-score": out["total-anomaly-score"],
            }
            if det["feature_thresholds"] is not None:
                # thresholds are per-model constants: attaching them is
                # response assembly, not per-row compute — the confidence
                # SERIES rides out of the fused program already computed
                result["tag-anomaly-thresholds"] = np.asarray(
                    det["feature_thresholds"]
                )
                result["total-anomaly-threshold"] = float(
                    det["aggregate_threshold"]
                )
                if "anomaly-confidence" in out:
                    result["anomaly-confidence"] = out["anomaly-confidence"]
                else:  # GORDO_SERVE_FUSED=off: the r11 host-side epilogue
                    result["anomaly-confidence"] = result[
                        "total-anomaly-score"
                    ] / max(float(det["aggregate_threshold"]), 1e-12)
            # fleet-health sketch: fold the response's (already host-
            # resident) total scores into this machine's live window
            telemetry.FLEET_HEALTH.record(
                self.machine, result["total-anomaly-score"]
            )
            return result
        # fallback: the model's own pandas path
        frame = self.model.anomaly(X, y)
        result = {
            "model-output": frame["model-output"].to_numpy(),
            "tag-anomaly-scores": frame["tag-anomaly-scores"].to_numpy(),
            "total-anomaly-score": frame[("total-anomaly-score", "")].to_numpy(),
        }
        if ("total-anomaly-threshold", "") in frame.columns:
            result["tag-anomaly-thresholds"] = frame[
                "tag-anomaly-thresholds"
            ].to_numpy()[0]
            result["total-anomaly-threshold"] = float(
                frame[("total-anomaly-threshold", "")].iloc[0]
            )
            result["anomaly-confidence"] = frame[
                ("anomaly-confidence", "")
            ].to_numpy()
        telemetry.FLEET_HEALTH.record(
            self.machine, result["total-anomaly-score"]
        )
        return result


def compile_scorer(model, dtype: Optional[str] = None) -> CompiledScorer:
    """Build (and warm up lazily) the serving scorer for ``model``."""
    return CompiledScorer(model, dtype=dtype)
