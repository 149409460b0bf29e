"""Whole-fleet anomaly-detector builds as stacked device programs.

Reference equivalent: running ``gordo_components/builder/build_model.py``
once per machine in its own Argo pod, each doing sklearn
``cross_val_predict`` + threshold derivation + a final Keras fit
(``model/anomaly/diff.py::DiffBasedAnomalyDetector``).

Here the entire bucket of M homogeneous machines — scaler stats, K CV folds
PLUS the final fit (folds ride a second vmap axis as weight masks),
out-of-fold scoring, per-tag/aggregate threshold derivation — compiles into
a few jitted dispatches, sharded over the mesh ``"models"`` axis.  Output is
M individually fitted :class:`DiffBasedAnomalyDetector` objects, artifact-
and metadata-compatible with the single-machine path.

Equivalence contract (tests/test_fleet.py): in the default exact mode,
EVERY machine's result — CV-fold fits, fold metrics, thresholds, scaler
stats, final params — is numerically identical to the single-machine path
(same RNG derivation, same materialized fold rows, same per-fold batch
geometry and shuffle).  This is achieved by grouping machines by row count
inside each bucket: within a length-group, fold indices and batch geometry
are shared static values, so each fold is materialized exactly as
``train.cv.cross_validate`` would (gather fold rows → fit scalers on them →
window → pad to the fold's own ``steps × bs``), then vmapped over machines.
A ragged bucket simply yields several length-groups, each exact — no
weight-mask approximation anywhere.  The ONE exception is the opt-in
``pad_lengths`` mode (:func:`_padded_fleet_program`), which deliberately
trades that exactness for O(1) compiles on ragged buckets: rows are
weight-masked rather than dropped, and fold/batch geometry derives from
the padded length (see docs/fleet.md for the contract).

Fleetability is *checked, not assumed*: :func:`analyze_definition` inspects
a prototype built from the model-config definition and returns a spec only
for the supported shape — ``DiffBasedAnomalyDetector`` wrapping
``Pipeline([*pure-stats scalers, BaseJaxEstimator])`` — everything else
falls back to the per-machine builder.
"""

from __future__ import annotations

import contextvars
import dataclasses
import hashlib
import logging
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import compile as compile_plane
from gordo_tpu import telemetry
from gordo_tpu.anomaly.diff import SMOOTHING_WINDOW, DiffBasedAnomalyDetector
from gordo_tpu.models.estimator import BaseJaxEstimator
from gordo_tpu.ops.scalers import (
    BaseTransform,
    MinMaxScaler,
    RobustScaler,
    StandardScaler,
)
from gordo_tpu.mesh import (
    MODEL_AXIS,
    Mesh,
    array_devices,
    model_sharding,
    pad_to_multiple,
)
from gordo_tpu.ingest.plane import owned_stack_base, stack_live_slots
from gordo_tpu.parallel import fleet as fleet_mod
from gordo_tpu.pipeline import Pipeline
from gordo_tpu.registry import lookup_factory
from gordo_tpu.train.cv import build_splitter
from gordo_tpu.train.fit import TrainConfig, make_fit_fn
from gordo_tpu.utils.trees import start_fetch, to_host

logger = logging.getLogger(__name__)

_MOE_TOKENS = telemetry.counter(
    "gordo_moe_tokens_total",
    "Positions each held expert computed in the optimiser steps of the fleet "
    "programs' final fits, by expert layer (the source's layer number) and "
    "expert",
    labels=("layer", "expert"),
)
_MOE_HELD_PAIRS = telemetry.counter(
    "gordo_moe_held_pairs_total",
    "Selected (position, expert) pairs that fell on experts held here",
)
_MOE_SELECTED_PAIRS = telemetry.counter(
    "gordo_moe_selected_pairs_total",
    "(position, expert) pairs the routers selected, held here or not",
)
_MOE_ROW_BLOCKS = telemetry.counter(
    "gordo_moe_row_blocks_total",
    "Row blocks of the expert layers' sorted order, by state: run (the blocks "
    "that held a pair on a held expert, which the layers' loops ran), full "
    "(the blocks that hold every selected pair)",
    labels=("state",),
)

_MTP_POSITIONS = telemetry.counter(
    "gordo_mtp_positions_total",
    "Positions whose row after next a multi-token-prediction module was "
    "trained on (weight above 0 in the loss's second term), in the optimiser "
    "steps of the fleet programs' final fits",
)

#: scalers whose stats are computable by a static pure function (vmappable).
FLEETABLE_SCALERS = (MinMaxScaler, StandardScaler, RobustScaler)

METRIC_NAMES = (
    "explained_variance_score",
    "r2_score",
    "mean_squared_error",
    "mean_absolute_error",
)


# ---------------------------------------------------------------------------
# Definition analysis
# ---------------------------------------------------------------------------

def _freeze(value: Any) -> Any:
    """A hashable stand-in for an estimator argument: lists (YAML's
    ``dims: [256, 128, 64]``) become tuples, dicts sorted item tuples."""
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    return value


@dataclasses.dataclass
class FleetSpec:
    """Everything needed to run one homogeneous bucket as a fleet program."""

    detector_proto: DiffBasedAnomalyDetector
    scaler_protos: List[BaseTransform]      # pipeline scalers, in order
    estimator_proto: BaseJaxEstimator
    train_cfg: TrainConfig
    factory_kwargs: Dict[str, Any]
    seed: int

    @property
    def signature(self) -> Tuple:
        """Bucket key: machines with equal signatures share one program."""
        return (
            type(self.detector_proto).__name__,
            self.detector_proto.window,
            tuple(
                (type(s).__name__, tuple(sorted(s._stat_options().items())))
                for s in self.scaler_protos
            ),
            (
                type(self.detector_proto.scaler).__name__,
                tuple(sorted(self.detector_proto.scaler._stat_options().items())),
            ),
            type(self.estimator_proto).__name__,
            self.estimator_proto.kind,
            self.train_cfg,
            _freeze(self.factory_kwargs),
        )


def analyze_definition(model) -> Optional[FleetSpec]:
    """Return a :class:`FleetSpec` if ``model`` (a built-but-unfitted
    prototype) matches the fleetable shape, else None."""
    if not isinstance(model, DiffBasedAnomalyDetector):
        return None
    if not isinstance(model.scaler, FLEETABLE_SCALERS):
        return None

    base = model.base_estimator
    scalers: List[BaseTransform] = []
    if isinstance(base, Pipeline):
        for _, step in base.steps[:-1]:
            if not isinstance(step, FLEETABLE_SCALERS):
                return None
            scalers.append(step)
        est = base._final
    else:
        est = base
    if not isinstance(est, BaseJaxEstimator):
        return None
    if est.params_ is not None:  # already fitted — not a prototype
        return None

    cfg, factory_kwargs = TrainConfig.from_kwargs(dict(est.kwargs))
    seed = int(factory_kwargs.get("seed", 0) or 0)
    return FleetSpec(
        detector_proto=model,
        scaler_protos=scalers,
        estimator_proto=est,
        train_cfg=cfg,
        factory_kwargs=factory_kwargs,
        seed=seed,
    )


# ---------------------------------------------------------------------------
# Pure device-side pieces
# ---------------------------------------------------------------------------

def _trailing_rolling_min(err: jnp.ndarray, window: int) -> jnp.ndarray:
    """Trailing rolling-min with ``min_periods=1`` semantics, (N, F)->(N, F)
    (pandas ``rolling(window, min_periods=1).min()`` as a static-shape op)."""
    return -jax.lax.reduce_window(
        -err,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(window, 1),
        window_strides=(1, 1),
        padding=((window - 1, 0), (0, 0)),
    )


def _smoothed_max(err: jnp.ndarray, window: int) -> jnp.ndarray:
    """Max over rows of the trailing rolling-min of ``err``.

    Matches ``anomaly.diff._rolling_min_max`` (pandas ``rolling(window,
    min_periods=1).min()`` then ``max()``) as a pure static-shape function.
    ``err``: (N, F) — returns (F,).
    """
    return jnp.max(_trailing_rolling_min(err, window), axis=0)


def _masked_smoothed_max(err: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(N, F) errors, (N,) row validity -> (F,): like :func:`_smoothed_max`
    but rolling-min windows that END on a masked row are excluded from the
    max.  With suffix padding every window ending on a real row contains
    only real rows, so this is exact for the pad-up program."""
    sm = _trailing_rolling_min(err, SMOOTHING_WINDOW)
    sm = jnp.where(mask[:, None] > 0, sm, -jnp.inf)
    mx = jnp.max(sm, axis=0)
    return jnp.where(jnp.isfinite(mx), mx, 0.0)


def _make_scale_chain(scaler_opts):
    """``X_f (M, n, F) -> (stats_list, transformed)`` for the pipeline's
    scaler chain: step i's stats are computed on step i-1's output
    (pipeline semantics).  On NaN-padded rows the nan-aware stat
    reductions exclude padding, and NaN propagates through apply so later
    steps' stats exclude it too."""

    def scale_chain(X_f):
        stats_list = []
        cur = X_f
        for scaler_cls, opts in scaler_opts:
            st = jax.vmap(
                lambda xm: scaler_cls.compute_stats(xm, **dict(opts))
            )(cur)
            stats_list.append(st)
            cur = jax.vmap(scaler_cls.apply)(st, cur)
        return stats_list, cur

    return scale_chain


def _make_apply_chain(scaler_opts):
    def apply_chain(stats_list, X_f):
        cur = X_f
        for (scaler_cls, _), st in zip(scaler_opts, stats_list):
            cur = jax.vmap(scaler_cls.apply)(st, cur)
        return cur

    return apply_chain


def _make_windowize(window_mode: str, lookback: int, stride: int = 1):
    """Estimator windowing semantics on already-scaled inputs (see the
    estimator classes: "none"=row-wise, "ae"=reconstruct window end,
    "forecast"=t+1, "sequence"=every position of a ``lookback``-row
    sequence forecasts its next row; its padding's weights come from
    :func:`_sequence_weights`)."""
    from gordo_tpu.ops.windows import make_sequences, make_windows

    def windowize(Xt, y_f):
        if window_mode == "sequence":
            return jax.vmap(
                lambda a, b: make_sequences(a, b, lookback, stride)[:2]
            )(Xt, y_f)
        if window_mode == "none":
            return Xt, y_f
        if window_mode == "ae":
            inputs = jax.vmap(lambda a: make_windows(a, lookback))(Xt)
            return inputs, y_f[:, lookback - 1:]
        if window_mode == "forecast":
            inputs = jax.vmap(lambda a: make_windows(a[:-1], lookback))(Xt)
            return inputs, y_f[:, lookback:]
        raise ValueError(f"Unknown window_mode {window_mode!r}")

    return windowize


def _sequence_weights(n_rows: int, context: int, stride: int) -> np.ndarray:
    """``(S, T)`` weights of the positions :func:`ops.windows.make_sequences`
    cuts ``n_rows`` rows into: 1 where a position reads a real row, 0 on the
    last sequence's padding.  Geometry alone, so a length-group shares it."""
    from gordo_tpu.ops.windows import num_sequences

    s = num_sequences(n_rows, context, stride)
    idx = np.arange(s)[:, None] * stride + np.arange(context)[None, :]
    return (idx < n_rows - 1).astype(np.float32)


def _over_machines(module, fn: Callable) -> Callable:
    """``fn`` over the leading machine axis of its arguments.  Side by side
    under ``vmap``, as the fleet engine is built; one after another
    (``lax.map``; a single machine is called as it is) where the module says
    ``fleet_axis = "map"``: one such model fills the chip, and a ``lax.cond``
    or a ragged matmul under ``vmap`` would compute every branch and row."""
    if getattr(module, "fleet_axis", "vmap") != "map":
        return jax.vmap(fn)

    def mapped(*args):
        if jax.tree.leaves(args)[0].shape[0] == 1:
            out = fn(*jax.tree.map(lambda a: a[0], args))
            return jax.tree.map(lambda a: a[None], out)
        return jax.lax.map(lambda one: fn(*one), args)

    return mapped


def _sequence_fits(module, cfg: TrainConfig, context: int, stride: int,
                   counted: bool, start: Callable, starts, fit_keys,
                   fits: List[Tuple]):
    """Every fit of a length-group of sequence models (the folds' fits,
    then the final fit) through ONE traced optimiser step and ONE traced
    forecast: a ``lax.scan`` over the fits, machines one after another.

    ``fits``: per fit ``(inputs (M, S, T, F), targets, n_rows, held-out
    inputs (M, S_te, T, F) or None)``; the last is the final fit.  A fit's
    sequences and minibatches are padded up to the largest fit's with slots
    that weigh nothing, and the loop over steps runs each fit's own number, so a
    fold trains exactly on what ``train.fit.make_fit_fn`` would give it (the
    same shuffle of its own ``steps x bs`` slots, the same loss weights); only
    the order of a sum's zeros differs.  One model's step is a program of
    its own to compile: four geometries traced apart cost four times that.

    ``start(starts[m])`` gives machine m's initial parameters and is called
    where each fit begins: a cold program draws them again from the
    machine's key for every fit and so holds no copy of them beside the
    running fit's weights, gradient and two moments (a warm program's are
    an argument it was handed, and stay).

    Returns ``(final_params, final_history (M, epochs), forecasts per fold
    [(M, S_te, T, F_out)], what the final fit's steps routed or None)``.
    """
    import optax

    from gordo_tpu.train.fit import (
        _FIT_LAYOUT as fit_layout_counter,
        batch_geometry, make_loss_fn, make_optimizer, pad_weights, training_pass,
    )

    geometry = [batch_geometry(f[0].shape[1], cfg.batch_size) for f in fits]
    n_cap = max(f[0].shape[1] for f in fits)
    steps_cap = max(g[0] for g in geometry)
    bs_cap = max(g[1] for g in geometry)
    blank = n_cap                      # one more slot: zeros that weigh 0
    held = [f[3] for f in fits if f[3] is not None]
    if len({h.shape for h in held}) > 1:
        raise NotImplementedError(
            "the folds' held-out blocks cut into different numbers of "
            "sequences; give the splitter blocks of one length"
        )

    def padded(a):
        pad = [(0, 0), (0, n_cap + 1 - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, pad)

    xs = jnp.stack([padded(f[0]) for f in fits], axis=1)      # (M, K, S+1, T, F)
    ys = jnp.stack([padded(f[1]) for f in fits], axis=1)
    ws = jnp.stack([
        pad_weights(f[0].shape[1], n_cap + 1 - f[0].shape[1],
                    _sequence_weights(f[2], context, stride))
        for f in fits
    ])                                                         # (K, S+1, T)
    te = jnp.stack([
        f[3] if f[3] is not None else jnp.zeros_like(held[0]) for f in fits
    ], axis=1)                                                 # (M, K, S_te, T, F)
    is_fold = jnp.asarray([f[3] is not None for f in fits])
    live = jnp.asarray([g[0] for g in geometry], jnp.int32)
    totals = jnp.maximum(jnp.sum(ws, axis=(1, 2)), 1.0)

    tx = make_optimizer(cfg)
    apply_fn, second = training_pass(module, counts=counted)
    if counted:
        grad_fn = jax.value_and_grad(make_loss_fn(
            apply_fn, cfg.loss, aux=True, second=second), has_aux=True)
    else:
        plain = jax.value_and_grad(make_loss_fn(apply_fn, cfg.loss, second=second))

        def grad_fn(*args):
            loss, grads = plain(*args)
            return (loss, ()), grads

    def schedule(fit_key):
        """``(K, epochs, steps_cap, bs_cap)`` slot indices: each fit's own
        shuffles of its own ``steps x bs`` slots, filled up with the blank."""
        out = []
        for steps, bs, _ in geometry:
            per_epoch = []
            for key in jax.random.split(fit_key, cfg.epochs):
                order = (jax.random.permutation(key, steps * bs) if cfg.shuffle
                         else jnp.arange(steps * bs))
                per_epoch.append(jnp.pad(
                    order.reshape(steps, bs),
                    ((0, steps_cap - steps), (0, bs_cap - bs)),
                    constant_values=blank))
            out.append(jnp.stack(per_epoch))
        return jnp.stack(out)

    def forecast(params, x):
        # the one name that wraps mixers: it marks the pass (a held-out
        # forecast's operations keep their innermost backbone.* names)
        with jax.named_scope("fit.forecast"):
            return module.apply({"params": params}, x)

    def machine(start_m, xs_m, ys_m, te_m, fit_key):
        def one_fit(_, fit):
            x, y, w, slots, n_live, total, te_x, fold = fit

            def epoch(carry, slots_e):
                def step(i, c):
                    p, s, routed, seen = c
                    slot = slots_e[i]
                    bw = w[slot]
                    (loss, counts), grads = grad_fn(p, x[slot], y[slot], bw)
                    with jax.named_scope("fit.optimizer"):
                        updates, s = tx.update(grads, s, p)
                        routed = jax.tree.map(jnp.add, routed, counts)
                        p = optax.apply_updates(p, updates)
                    return p, s, routed, seen + loss * jnp.sum(bw)

                # this fit's own number of steps: a loop with a bound read
                # from the data, not steps that are skipped
                p, s, routed, seen = jax.lax.fori_loop(
                    0, n_live, step, (*carry, jnp.zeros((), jnp.float32)))
                return (p, s, routed), seen / total

            # the scan's carry is the running fit's state, started anew for
            # every fit: the last fit leaves the final parameters (and what
            # its steps routed) in it and no fit's result is held beside the
            # next one's
            with jax.named_scope("fit.draw"):
                params0_m = start(start_m)
                state0_m = tx.init(params0_m)
            (params, opt_state, routed), history = jax.lax.scan(
                epoch, (params0_m, state0_m, no_counts), slots)
            pred = jax.lax.cond(
                fold, forecast,
                lambda p, x: jnp.zeros(pred_shape.shape, pred_shape.dtype),
                params, te_x)
            return (params, opt_state, routed), (history, pred)

        like = jax.eval_shape(start, start_m)
        blank_params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), like)
        pred_shape = jax.eval_shape(forecast, like, te_m[0])
        no_counts = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(lambda p: apply_fn(
                {"params": p}, xs_m[0, :bs_cap])[1], like),
        ) if counted else ()
        if counted and second:
            # the two loss terms' sums, carried beside the routed counts
            no_counts = {**no_counts, "loss_terms": jnp.zeros((4,), jnp.float32)}
        (final, _, routed), (history, preds) = jax.lax.scan(
            one_fit, (blank_params, tx.init(blank_params), no_counts),
            (xs_m, ys_m, ws, schedule(fit_key), live, totals, te_m, is_fold))
        return final, history[-1], preds, routed

    # runs where the program is traced: once for all the fits
    fit_layout_counter.inc(1.0, "public")
    telemetry.add_to_span(fit_traces=1)
    final_params, final_history, preds, routed = _over_machines(
        module, machine)(starts, xs, ys, te, fit_keys)
    return (
        final_params, final_history,
        [preds[:, k] for k in range(len(held))],
        routed if counted else None,
    )


def _model_axis_pad(m: int, mesh) -> int:
    """Pad target for the stacked machine axis: next power of two, then
    the mesh's ``models``-axis multiple.

    The fleet program is a pure vmap over machines, so dummy lanes are
    free parity-wise (``_assemble`` slices ``[:m]``) and nearly free on
    device — but every DISTINCT machine count is a fresh XLA lowering of
    the same program (~88s cold for the LSTM CV+fit).  Power-of-two
    padding collapses all counts onto log-many compiled shapes: a 10k-
    machine project's 272-machine tail chunk reuses the 512-chunk
    program, and warm re-runs with slightly different counts recompile
    nothing."""
    m_pad = 1 << max(m - 1, 0).bit_length() if m > 1 else 1
    if mesh is not None:
        m_pad = pad_to_multiple(m_pad, mesh.shape[MODEL_AXIS])
    return m_pad


def _stack_machine_axis(arrs: Sequence[np.ndarray]) -> np.ndarray:
    """``np.stack`` along a new leading machine axis — except when the
    arrays are, in order, a consecutive run of leading-axis slots of ONE
    ingest-owned stacked buffer (``gordo_tpu/ingest/plane.py``): then the
    buffer slice is adopted with no copy.  The ingest plane preallocates
    that buffer at model-axis capacity precisely so this stacking copy
    (and the padding copy in :meth:`_dispatch_group`) disappears; any
    deviation — a fallback-loaded machine in the group, dedup slots out
    of machine order, a foreign array — falls back to the copy."""
    base = owned_stack_base(arrs[0])
    if base is None or any(a.shape != base.shape[1:] for a in arrs):
        return np.stack(arrs)
    b0 = base.__array_interface__["data"][0]
    stride = base.strides[0]
    off = arrs[0].__array_interface__["data"][0] - b0
    if stride <= 0 or off % stride:
        return np.stack(arrs)
    s0 = off // stride
    if s0 + len(arrs) > base.shape[0]:
        return np.stack(arrs)
    for j, a in enumerate(arrs):
        if (
            owned_stack_base(a) is not base
            or a.strides != base.strides[1:]
            or a.__array_interface__["data"][0] != b0 + (s0 + j) * stride
        ):
            return np.stack(arrs)
    return base[s0 : s0 + len(arrs)]


def _pad_models_capacity(X: np.ndarray, m_pad: int) -> np.ndarray:
    """:func:`fleet._pad_models` without the copy when ``X`` is the FULL
    live prefix of an ingest-owned buffer with spare capacity: the dummy
    pad lanes (repeats of the last machine; results discarded) are
    written into the buffer's scratch rows in place.  Requiring ``X`` to
    start at slot 0 and cover every live slot guarantees no other
    machine's data occupies the rows being overwritten."""
    m = X.shape[0]
    base = owned_stack_base(X)
    if (
        base is not None
        and m_pad <= base.shape[0]
        and m == stack_live_slots(base)
        and X.strides == base.strides
        and X.__array_interface__["data"][0]
        == base.__array_interface__["data"][0]
    ):
        base[m:m_pad] = base[m - 1]
        return base[:m_pad]
    return fleet_mod._pad_models(X, m_pad)


def _stack_warm_params(params_list: Sequence[Any], m_pad: int):
    """Stack per-machine param pytrees into the fleet layout: leading
    machine axis, padded to ``m_pad`` by repeating the last machine (the
    padded lanes are dummies whose results ``_assemble`` discards).

    A length-group shares one module, so every tree must agree in
    structure and leaf shapes; a mismatch (a stale artifact predating a
    model-config change, say) raises ``ValueError`` so the caller can
    fall back to a cold build instead of feeding XLA garbage."""
    treedef0 = None
    leaves0: List[Any] = []
    flats: List[List[np.ndarray]] = []
    for i, params in enumerate(params_list):
        leaves, treedef = jax.tree.flatten(params)
        leaves = [np.asarray(leaf) for leaf in leaves]
        if treedef0 is None:
            treedef0, leaves0 = treedef, leaves
        elif treedef != treedef0 or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(leaves, leaves0)
        ):
            raise ValueError(
                f"warm-start params for machine {i} break the group's "
                "shared leaf signature — the previous artifact predates "
                "a model-config change; rebuild cold"
            )
        flats.append(leaves)
    stacked = [
        fleet_mod._pad_models(
            np.stack([flat[j] for flat in flats]), m_pad
        )
        for j in range(len(leaves0))
    ]
    return jax.tree.unflatten(treedef0, stacked)


# ---------------------------------------------------------------------------
# The fleet builder
# ---------------------------------------------------------------------------

SPAN_PREFIX = "gordo.build."
PROGRAM_WAIT_JOIN_SECONDS = 5.0


def _watch_program(out: Any, clock: Any,
                   enqueued_at: float) -> threading.Thread:
    """Start the thread that stamps a just-enqueued program's end for the
    chunk's ``clock``.  It holds the program's SMALLEST output leaf and nothing else of ``out``,
    so the collect side's ``g.out = None`` still frees the buffers; on a
    mesh that leaf is sharded like the rest and is ready when every
    device's part is.  The wait enqueues nothing on the device, so unlike
    the fetch's on-device slices it does not queue behind the next
    program.  Started in a copy of the caller's context: the span it
    opens shares the build's trace id and parent."""
    leaf = min(jax.tree.leaves(out), key=lambda a: a.size)
    thread = threading.Thread(
        target=contextvars.copy_context().run,
        args=(_await_leaf, leaf, clock, clock.enqueued(enqueued_at)),
        name="gordo-program-wait", daemon=True,
    )
    thread.start()
    return thread


def _await_leaf(leaf: jax.Array, clock: Any,
                on_ready: Callable[[float], None]) -> None:
    with clock.span("program_wait") as sp:
        try:
            leaf.block_until_ready()
        except Exception as exc:  # surfaces at collect, which demotes
            sp["error"] = type(exc).__name__
    on_ready(sp.get("end", time.time()))


@dataclasses.dataclass
class _GroupContext:
    """Static per-group program context shared by dispatch and warm."""

    folds: Tuple
    k_folds: int
    module: Any
    built_kwargs: Dict[str, Any]
    scaler_opts: Tuple
    det_scaler_opts: Tuple
    window_mode: str
    lookback: int
    offset: int
    stride: int


@dataclasses.dataclass
class _PendingGroup:
    """One length-group's in-flight device program + assembly context."""

    indices: List[int]
    out: Any                      # device-side result tree until collected
    m: int
    built_kwargs: Dict[str, Any]
    k_folds: int
    t0: float
    pad_built: bool = False
    #: the group's module (its configuration names what the program counted)
    module: Any = None
    #: the thread that stamps this program's end (None: nobody asked)
    watcher: Optional[threading.Thread] = None
    #: fetched HOST result tree, kept after collect — the stacked arrays
    #: the per-machine detectors hold views into, re-exposed whole so a
    #: downstream consumer (fleet-health baseline scoring) can adopt them
    #: without re-stacking per-machine slices leaf by leaf
    host: Optional[Dict[str, Any]] = None


class PendingFleetBuild:
    """An in-flight fleet build: every group's program has been DISPATCHED
    (inputs staged async, device futures in hand) but nothing has been
    fetched — the build-plane analogue of ``FleetScorer.dispatch_all`` /
    ``FleetFitResult``.

    :meth:`collect` blocks on the device results, runs the (partial) D2H
    fetch and per-machine assembly, and caches the detectors — idempotent,
    so the drive loop can hold one of these per chunk and collect behind
    the next chunk's dispatch.  Where collect time went is on the
    ``gordo.build.fetch`` and ``gordo.build.assemble`` spans.
    """

    def __init__(
        self,
        builder: "FleetDiffBuilder",
        n: int,
        groups: List[_PendingGroup],
    ):
        self._builder = builder
        self._n = n
        self._groups = groups
        self._detectors: Optional[List[DiffBasedAnomalyDetector]] = None
        #: devices that held the groups' result arrays (filled by collect;
        #: the build summary's ``device`` object reads it)
        self.devices: set = set()

    def collect(self) -> List[DiffBasedAnomalyDetector]:
        """Fetch + assemble every dispatched group (blocking; an async XLA
        failure from dispatch surfaces here).  Returns detectors in the
        original ``Xs`` input order; repeat calls return the cached list."""
        if self._detectors is None:
            detectors: List[Optional[DiffBasedAnomalyDetector]] = (
                [None] * self._n
            )
            for g in self._groups:
                self.devices |= array_devices(g.out)
                for i, det in zip(g.indices, self._builder._collect_group(g)):
                    detectors[i] = det
            self._detectors = detectors  # type: ignore[assignment]
        return self._detectors  # type: ignore[return-value]

    def settle(self, timeout: float = PROGRAM_WAIT_JOIN_SECONDS) -> bool:
        """Wait for the groups' ready stamps (their watcher threads).  After
        :meth:`collect` has returned or raised the programs have ended, so
        this returns at once; the timeout is for a watcher that hangs all
        the same.  True when every stamp is in."""
        for g in self._groups:
            if g.watcher is not None:
                g.watcher.join(timeout)
        return not any(
            g.watcher is not None and g.watcher.is_alive()
            for g in self._groups
        )

    def prestacked(self, names: List[str]) -> Optional[Dict[str, Any]]:
        """The collected groups' stacked host arrays as a serving
        prestack hint (``FleetScorer.from_models(prestacked_hint=...)``).

        ``names`` lists the chunk's machine names in the original input
        order (``names[i]`` ↔ detector ``i``).  The returned dict carries
        one pack per dispatched group — pad rows sliced off, rows in
        group-dispatch order, ``"names"`` reordered to match — all
        zero-copy basic slices of the arrays the detectors already hold
        views into.  The fleet-health baseline scorer adopts it instead
        of re-stacking per-machine slices leaf by leaf (one tiny jitted
        stack dispatch per leaf otherwise — the dominant host cost of
        baseline sketching at bucket-512 scale).  Returns None before
        :meth:`collect` or when any group's host tree was not retained.
        """
        if self._detectors is None:
            return None
        packs: List[Tuple] = []
        thr_parts: List[np.ndarray] = []
        agg_parts: List[np.ndarray] = []
        order: List[int] = []
        for g in self._groups:
            host = g.host
            if host is None:
                return None
            m = g.m
            packs.append((
                jax.tree.map(lambda a: a[:m], host["final_params"]),
                tuple(
                    {k: v[:m] for k, v in step.items()}
                    for step in host["scaler_stats"]
                ),
                {k: v[:m] for k, v in host["det_scaler_stats"].items()},
            ))
            thr_parts.append(host["feature_thresholds"][:m])
            agg_parts.append(host["aggregate_threshold"][:m])
            order.extend(g.indices)
        return {
            "names": [names[i] for i in order],
            "packs": packs,
            "feature_thresholds": (
                thr_parts[0] if len(thr_parts) == 1
                else np.concatenate(thr_parts)
            ),
            "agg": np.asarray(
                agg_parts[0] if len(agg_parts) == 1
                else np.concatenate(agg_parts),
                np.float32,
            ).reshape(-1),
        }


class FleetDiffBuilder:
    """Build M homogeneous ``DiffBasedAnomalyDetector`` machines at once.

    One instance per bucket; ``build(Xs, ys)`` returns fitted detectors in
    input order.
    """

    def __init__(
        self,
        spec: FleetSpec,
        cv: Any = None,
        mesh: Optional[Mesh] = None,
        pad_lengths: Optional[int] = None,
        clock: Optional[Any] = None,
    ):
        self.spec = spec
        self.splitter = build_splitter(cv)
        self.mesh = mesh
        #: the drive loop's recorder for this chunk
        #: (``builder.timeline.ChunkClock``): the phase spans land on its
        #: row and every enqueued program gets a watcher thread that
        #: stamps its end.  None (everyone but ``build_project`` with
        #: telemetry on): plain spans, no thread
        self.clock = clock
        #: pad-up mode: machines grouped by row count rounded UP to a
        #: multiple of this, padded with weight-masked rows — every real
        #: row trains, and a ragged bucket needs one program per ALIGNED
        #: length instead of one per distinct length.  See
        #: :func:`_padded_fleet_program` for the (documented) CV-semantics
        #: difference vs the exact per-length mode.
        self.pad_lengths = int(pad_lengths) if pad_lengths else None

    # -- host-side orchestration --------------------------------------------
    def _validate_inputs(self, Xs, ys, warm_params):
        """Length/shape validation + one-time host dtype normalization (so
        the dispatch window below never needs ``np.asarray``)."""
        if ys is not None and len(ys) != len(Xs):
            raise ValueError(
                f"Got {len(Xs)} input series but {len(ys)} target series"
            )
        if warm_params is not None and len(warm_params) != len(Xs):
            raise ValueError(
                f"Got {len(Xs)} input series but {len(warm_params)} "
                "warm-start param trees"
            )
        Xs = [np.asarray(x, np.float32) for x in Xs]
        if ys is not None:
            for i, (x, yy) in enumerate(zip(Xs, ys)):
                if len(yy) != len(x):
                    raise ValueError(
                        f"Target row count differs from input for machine {i}: "
                        f"{len(yy)} != {len(x)}"
                    )
            ys = [np.asarray(yy, np.float32) for yy in ys]
        return Xs, ys

    def build(
        self,
        Xs: Sequence[np.ndarray],
        ys: Optional[Sequence[np.ndarray]] = None,
        warm_params: Optional[Sequence[Any]] = None,
    ) -> List[DiffBasedAnomalyDetector]:
        """Build detectors for ``Xs`` in input order (dispatch + collect
        back to back — see :meth:`dispatch` for the async split).

        Machines are grouped by row count; each length-group runs the exact
        fold-materializing program, so every machine's result matches the
        single-machine path (not just the bucket-max ones).

        ``warm_params`` (one param pytree per machine, aligned with ``Xs``)
        switches every group onto the warm program variant: fits resume
        from the given weights instead of ``fleet_init`` — the incremental
        refresh path.  Callers pair it with a reduced-epoch
        :class:`~gordo_tpu.train.fit.TrainConfig` in the spec.
        """
        return self.dispatch(Xs, ys, warm_params=warm_params).collect()

    def dispatch(
        self,
        Xs: Sequence[np.ndarray],
        ys: Optional[Sequence[np.ndarray]] = None,
        warm_params: Optional[Sequence[Any]] = None,
    ) -> PendingFleetBuild:
        """Launch every length-group's device program and return a
        :class:`PendingFleetBuild` WITHOUT blocking on results.

        Inputs are staged through the mesh placement seam (async
        ``device_put``) and jax's async dispatch returns device futures,
        so this returns as soon as the programs are enqueued — the drive
        loop dispatches chunk k+1 here while chunk k's fetch/assembly/write
        run behind it.  This method and everything it calls form the
        lint-enforced dispatch window: no blocking D2H transfers
        (``scripts/lint.py``'s ``D2H_FORBIDDEN_SCOPES`` gate).
        """
        Xs, ys = self._validate_inputs(Xs, ys, warm_params)
        groups: List[_PendingGroup] = []
        if self.pad_lengths:
            self._dispatch_padded(Xs, ys, warm_params, groups)
            return PendingFleetBuild(self, len(Xs), groups)

        n_lengths = len({int(x.shape[0]) for x in Xs})
        if n_lengths > 1 and n_lengths > len(Xs) // 2:
            # Exact parity requires one program per distinct row count; a
            # bucket where most machines differ in length loses the fleet
            # vmap win and pays one XLA compile per length (still no worse
            # than the per-machine fallback, but worth surfacing).
            logger.warning(
                "Fleet bucket of %d machines has %d distinct row counts; "
                "each length compiles its own program — consider aligning "
                "train windows for fleet efficiency",
                len(Xs), n_lengths,
            )
        self._dispatch_exact_length_groups(
            Xs, ys, range(len(Xs)), groups, warm_params
        )
        return PendingFleetBuild(self, len(Xs), groups)

    def _dispatch_exact_length_groups(
        self, Xs, ys, idxs, groups: List[_PendingGroup], warm_params=None
    ) -> None:
        """Group ``idxs`` by row count and dispatch the exact program per
        length-group, appending the pending groups."""
        by_len: Dict[int, List[int]] = {}
        for i in idxs:
            by_len.setdefault(int(Xs[i].shape[0]), []).append(i)
        for group in by_len.values():
            def stacked(group=group):
                X_g = _stack_machine_axis([Xs[i] for i in group])
                if ys is None or all(ys[i] is Xs[i] for i in group):
                    # the ingest plane hands targets == inputs as the SAME
                    # array object — one stacked buffer serves both
                    return X_g, X_g, None
                return X_g, _stack_machine_axis([ys[i] for i in group]), None

            warm_g = (
                None
                if warm_params is None
                else [warm_params[i] for i in group]
            )
            g = self._dispatch_group(stacked, warm=warm_g)
            g.indices = list(group)
            groups.append(g)

    def _dispatch_padded(
        self,
        Xs: Sequence[np.ndarray],
        ys: Optional[Sequence[np.ndarray]],
        warm_params: Optional[Sequence[Any]],
        groups: List[_PendingGroup],
    ) -> None:
        """Pad-up mode: group by row count rounded UP to ``pad_lengths``,
        NaN-pad each machine's rows to the group length (NaN rows fall out
        of the nan-aware scaler stats; zero-weight rows fall out of the
        loss), and dispatch the masked program once per group.  Every real
        row trains; a 16-length ragged bucket compiles O(1) programs."""
        pad = self.pad_lengths
        offset = int(self.spec.estimator_proto.offset)
        by_pad: Dict[int, List[int]] = {}
        exact_fallback: List[int] = []
        for i, x in enumerate(Xs):
            n_pad = -(-x.shape[0] // pad) * pad
            by_pad.setdefault(n_pad, []).append(i)

        for n_pad, idxs in list(by_pad.items()):
            folds = [
                (list(tr), list(te))
                for tr, te in self.splitter.split(np.empty((n_pad, 1)))
            ]
            # The masked program's exactness rests on padding being a
            # SUFFIX after every fold gather — i.e. fold indices must be
            # sorted contiguous blocks (true for TimeSeriesSplit and
            # unshuffled KFold).  A shuffled/exotic splitter would
            # silently interleave pad rows into training windows, so the
            # whole group demotes to the exact path instead.
            contiguous = all(
                np.array_equal(idx, np.arange(idx[0], idx[-1] + 1))
                for tr, te in folds
                for idx in (tr, te)
            )
            if not contiguous:
                logger.warning(
                    "pad_lengths=%d: CV splitter %s yields non-contiguous "
                    "fold indices — pad-up mode requires contiguous blocks; "
                    "building the %d machine(s) at padded length %d through "
                    "the exact per-length path",
                    pad, type(self.splitter).__name__, len(idxs), n_pad,
                )
                exact_fallback.extend(idxs)
                del by_pad[n_pad]
                continue
            # Every fold's test block must contain real target rows for
            # every machine, or its thresholds/metrics would be computed on
            # nothing (0/0-guarded into silently-wrong zeros).  A machine
            # shorter than the last fold's start (plus window context) can't
            # satisfy that at this padded length — build it exactly instead.
            min_len = max(int(te[0]) for _, te in folds) + offset + 1
            short = [i for i in idxs if Xs[i].shape[0] < min_len]
            if short:
                logger.warning(
                    "pad_lengths=%d: %d machine(s) are shorter than %d rows "
                    "(their real rows would miss a CV test block at padded "
                    "length %d) — building them through the exact per-length "
                    "path instead",
                    pad, len(short), min_len, n_pad,
                )
                exact_fallback.extend(short)
                idxs = [i for i in idxs if i not in set(short)]
                if not idxs:
                    del by_pad[n_pad]
                    continue
                by_pad[n_pad] = idxs

        self._dispatch_exact_length_groups(
            Xs, ys, exact_fallback, groups, warm_params
        )

        for n_pad, idxs in by_pad.items():
            def stacked(n_pad=n_pad, idxs=idxs):
                m = len(idxs)
                n_feat = Xs[idxs[0]].shape[1]
                n_out = n_feat if ys is None else ys[idxs[0]].shape[1]
                X = np.full((m, n_pad, n_feat), np.nan, np.float32)
                y = np.full((m, n_pad, n_out), np.nan, np.float32)
                lens = np.zeros((m,), np.int32)
                for j, i in enumerate(idxs):
                    L = Xs[i].shape[0]
                    lens[j] = L
                    X[j, :L] = Xs[i]
                    y[j, :L] = Xs[i] if ys is None else ys[i]
                return X, y, lens

            warm_g = (
                None
                if warm_params is None
                else [warm_params[i] for i in idxs]
            )
            g = self._dispatch_group(stacked, warm=warm_g)
            g.indices = list(idxs)
            # distinguishes genuinely pad-built artifacts from the
            # exact-fallback ones above (fleet_build stamps metadata
            # from this marker, not from the request flag)
            g.pad_built = True
            groups.append(g)

    def _group_context(
        self, n_rows: int, n_features: int, n_out: int
    ) -> _GroupContext:
        """Everything static a group's program factory needs, derived from
        geometry alone — shared by :meth:`_dispatch_group` (real data) and
        :meth:`warm` (shape structs)."""
        spec = self.spec
        est_proto = spec.estimator_proto

        # Static fold indices — identical to what cross_validate would use.
        folds = tuple(
            (tuple(int(i) for i in tr), tuple(int(i) for i in te))
            for tr, te in self.splitter.split(np.empty((n_rows, 1)))
        )

        # Factory module for this bucket's shapes.
        factory = lookup_factory(est_proto.model_type, est_proto.kind)
        built_kwargs = dict(
            n_features=n_features, n_features_out=n_out, **spec.factory_kwargs
        )
        module = factory(**built_kwargs)

        scaler_opts = tuple(
            (type(s), tuple(sorted(s._stat_options().items())))
            for s in spec.scaler_protos
        )
        det_scaler_opts = (
            type(spec.detector_proto.scaler),
            tuple(sorted(spec.detector_proto.scaler._stat_options().items())),
        )

        # Windowing semantics as static flags (see estimator classes):
        # "none"=row-wise FF AE, "ae"=reconstruct window end, "forecast"=t+1.
        from gordo_tpu.models.estimator import (
            LSTMAutoEncoder, LSTMForecast, SequenceForecast,
        )

        stride = 1
        if isinstance(est_proto, SequenceForecast):
            window_mode, lookback = "sequence", est_proto.context
            stride = est_proto.stride
        elif isinstance(est_proto, LSTMForecast):
            window_mode, lookback = "forecast", est_proto.lookback_window
        elif isinstance(est_proto, LSTMAutoEncoder):
            window_mode, lookback = "ae", est_proto.lookback_window
        else:
            window_mode, lookback = "none", 1

        return _GroupContext(
            folds=folds,
            k_folds=len(folds),
            module=module,
            built_kwargs=built_kwargs,
            scaler_opts=scaler_opts,
            det_scaler_opts=det_scaler_opts,
            window_mode=window_mode,
            lookback=int(lookback),
            offset=int(est_proto.offset),
            stride=int(stride),
        )

    def _group_program(self, ctx: _GroupContext, padded: bool, warm: bool):
        if ctx.window_mode == "sequence":
            if padded:
                raise NotImplementedError(
                    "pad_lengths has no masked program for "
                    f"{type(self.spec.estimator_proto).__name__}: build its "
                    "machines at their exact lengths"
                )
            return _exact_fleet_program(
                ctx.module, ctx.scaler_opts, ctx.det_scaler_opts,
                ctx.window_mode, ctx.lookback, ctx.offset,
                self.spec.train_cfg, ctx.folds, self.mesh, warm=warm,
                stride=ctx.stride,
            )
        fn = _padded_fleet_program if padded else _exact_fleet_program
        return fn(
            ctx.module,
            ctx.scaler_opts,
            ctx.det_scaler_opts,
            ctx.window_mode,
            ctx.lookback,
            ctx.offset,
            self.spec.train_cfg,
            ctx.folds,
            self.mesh,
            warm=warm,
        )

    def warm(
        self,
        m: int,
        n_rows: int,
        n_features: int,
        n_out: Optional[int] = None,
        padded: bool = False,
    ) -> float:
        """AOT pre-compile the fleet program for one group geometry from
        shape structs alone — no data, no execution (``Program.warm`` for
        the build plane).  Returns compile seconds, 0.0 on a cache hit.

        Cold programs only: the warm-start variant's ``params0`` signature
        depends on the previous generation's leaf layout, which isn't
        derivable from geometry.
        """
        n_out = int(n_out) if n_out is not None else int(n_features)
        ctx = self._group_context(int(n_rows), int(n_features), n_out)
        m_pad = _model_axis_pad(int(m), self.mesh)
        ms = model_sharding(self.mesh) if self.mesh is not None else None

        def aval(shape, dtype):
            if ms is not None:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=ms)
            return jax.ShapeDtypeStruct(shape, dtype)

        X_av = aval((m_pad, int(n_rows), int(n_features)), jnp.float32)
        y_av = aval((m_pad, int(n_rows), n_out), jnp.float32)
        seeds_av = aval((m_pad,), jnp.uint32)
        program = self._group_program(ctx, padded=padded, warm=False)
        if padded:
            return program.warm(
                X_av, y_av, aval((m_pad,), jnp.int32), seeds_av
            )
        return program.warm(X_av, y_av, seeds_av)

    def _span(self, phase: str, **attrs: Any):
        """The span of one phase of this builder's chunk: on the chunk's
        row where the drive loop handed a clock, a plain span otherwise."""
        if self.clock is not None:
            return self.clock.span(phase, **attrs)
        return telemetry.span(SPAN_PREFIX + phase, **attrs)

    def _dispatch_group(
        self,
        stacked: Callable[[], Tuple[np.ndarray, np.ndarray,
                                    Optional[np.ndarray]]],
        warm: Optional[Sequence[Any]] = None,
    ) -> _PendingGroup:
        """Launch one length-homogeneous group's device program and return
        WITHOUT blocking.  ``stacked()`` gives the group's ``(X, y, lens)``
        with a leading machine axis (``lens`` set: the masked pad-up
        program; ``warm`` given: the warm program resuming from stacked
        previous params); it runs inside the ``stage`` span with the
        model-axis padding and the placement seam's async H2D, then the
        jitted call alone is the ``enqueue`` span — a re-trace or a
        compile shows there — and returns device futures; the blocking
        fetch lives in :meth:`_collect_group`.  Lint-enforced dispatch
        window: no blocking D2H here (scripts/lint.py)."""
        spec = self.spec
        t0 = time.time()
        with self._span("stage") as staged:
            X, y, lens = stacked()
            m, n_rows = X.shape[:2]
            ctx = self._group_context(n_rows, X.shape[2], y.shape[2])

            # Pad the model axis (dummy copies; results discarded): next
            # power of two + mesh multiple, so distinct machine counts
            # share one compiled program per (module, length) — see
            # _model_axis_pad.
            m_pad = _model_axis_pad(m, self.mesh)
            if m_pad != m:
                y_is_x = y is X
                X = _pad_models_capacity(X, m_pad)
                y = X if y_is_x else _pad_models_capacity(y, m_pad)
                if lens is not None:
                    # host ints → int32 view (this scope's lint gate
                    # reserves the np.asarray spelling for D2H misuse)
                    lens = fleet_mod._pad_models(
                        lens.astype(np.int32, copy=False), m_pad
                    )

            seeds = np.full((m_pad,), spec.seed, dtype=np.uint32)
            params0 = (
                _stack_warm_params(warm, m_pad) if warm is not None else None
            )
            program = self._group_program(
                ctx, padded=lens is not None, warm=params0 is not None
            )
            host_args = (X, y, seeds) if lens is None else (X, y, lens, seeds)
            args = fleet_mod.stage_inputs(host_args, self.mesh)
            if params0 is not None:
                args = (*args, fleet_mod.stage_inputs(params0, self.mesh))
            staged["machines"] = m
        with self._span("enqueue") as enqueued:
            out = program(*args)
        watcher = None
        if self.clock is not None and "end" in enqueued:
            watcher = _watch_program(out, self.clock, enqueued["end"])

        return _PendingGroup(
            indices=[],
            out=out,
            m=m,
            built_kwargs=ctx.built_kwargs,
            k_folds=ctx.k_folds,
            t0=t0,
            module=ctx.module,
            watcher=watcher,
        )

    def _collect_group(
        self, g: _PendingGroup
    ) -> List[DiffBasedAnomalyDetector]:
        """Blocking side of the split: fetch the group's device results —
        partially, where less than the full tree is ever read — and
        assemble per-machine detectors.  An async XLA failure from
        dispatch surfaces here."""
        out = g.out
        with self._span("fetch"):
            # what is fetched whole starts for the host now: the sliced reads
            # below are device operations, which queue behind the NEXT chunk's
            # program, and these bytes cross while it runs
            start_fetch([v for k, v in out.items() if k != "scaler_stats"])
            host = {
                # fold axis: slot -1 is the final full-data fit — the only
                # slot _assemble reads, so slice on device and fetch (K+1)x
                # fewer bytes than the stacked per-fold stats
                "scaler_stats": [
                    {stat: np.asarray(val[:, -1]) for stat, val in step.items()}
                    for step in out["scaler_stats"]
                ],
                "det_scaler_stats": to_host(out["det_scaler_stats"]),
                "final_params": to_host(out["final_params"]),
                "final_history": np.asarray(out["final_history"]),
                "feature_thresholds": np.asarray(out["feature_thresholds"]),
                "aggregate_threshold": np.asarray(out["aggregate_threshold"]),
                "metrics": {
                    name: np.asarray(v) for name, v in out["metrics"].items()
                },
            }
            if "moe" in out:
                host["moe"] = to_host(out["moe"])
                self._count_routing(host["moe"], g.m, g.module.cfg)
            g.out = None  # free the device buffers now, not at pending teardown
            g.host = host  # views of these back the detectors; no extra copy
        fleet_seconds = time.time() - g.t0
        with self._span("assemble"):
            detectors = self._assemble(
                host, g.m, g.built_kwargs, fleet_seconds, g.k_folds
            )
            if g.pad_built:
                for det in detectors:
                    det.pad_built_ = True
        return detectors

    @staticmethod
    def _count_routing(moe: Dict[str, Any], m: int, cfg: Any) -> None:
        """One group's routing counts (``(M, layers, held)`` tokens, ``(M,)``
        pairs) onto the process's counters."""
        tokens = moe["tokens"][:m].sum(axis=0)
        for row, layer in zip(tokens, cfg.moe_labels):
            for e, count in enumerate(row):
                _MOE_TOKENS.inc(
                    float(count), layer, str(cfg.experts_held_from + e))
        _MOE_HELD_PAIRS.inc(float(moe["held"][:m].sum()))
        _MOE_SELECTED_PAIRS.inc(float(moe["selected"][:m].sum()))
        if "blocks_run" in moe:  # the expert layers walk row blocks
            _MOE_ROW_BLOCKS.inc(float(moe["blocks_run"][:m].sum()), "run")
            _MOE_ROW_BLOCKS.inc(float(moe["blocks_full"][:m].sum()), "full")
        if "loss_terms" in moe:
            _MTP_POSITIONS.inc(float(moe["loss_terms"][:m, 3].sum()))

    # -- unpacking into per-machine detector objects ------------------------
    def _assemble(
        self,
        out: Dict[str, Any],
        m: int,
        built_kwargs: Dict[str, Any],
        fleet_seconds: float,
        k_folds: int,
    ) -> List[DiffBasedAnomalyDetector]:
        """Unpack one group's HOST result tree into per-machine detectors.

        Still O(M) Python, but deliberately thin: prototypes are cloned
        from one ``pickle.dumps`` per bucket (a ``pickle.loads`` per
        machine replaces the ``copy.deepcopy`` ×(2+scalers) chain), array
        leaves are handed out as zero-copy views of the stacked host
        arrays, and ``cv_metadata_`` floats come from whole-array
        ``tolist()``/axis reductions instead of per-fold Python ``float()``
        loops.  ``out["scaler_stats"]`` arrives pre-sliced to the final-fit
        fold slot (see :meth:`_collect_group`).
        """
        spec = self.spec
        final_params_leaves, treedef = jax.tree.flatten(out["final_params"])
        est_blob = pickle.dumps(spec.estimator_proto)
        scaler_blobs = [pickle.dumps(p) for p in spec.scaler_protos]
        det_scaler_blob = pickle.dumps(spec.detector_proto.scaler)
        wrap = bool(spec.scaler_protos) or isinstance(
            spec.detector_proto.base_estimator, Pipeline
        )
        per_machine_seconds = fleet_seconds / m

        metrics = out["metrics"]
        folds_by = {n_: metrics[n_][:m].tolist() for n_ in METRIC_NAMES}
        means = {n_: metrics[n_][:m].mean(axis=1) for n_ in METRIC_NAMES}
        stds = {n_: metrics[n_][:m].std(axis=1) for n_ in METRIC_NAMES}
        feat_rows = out["feature_thresholds"]
        feat_lists = feat_rows[:m].tolist()
        agg = out["aggregate_threshold"]
        agg_list = agg[:m].tolist()

        detectors: List[DiffBasedAnomalyDetector] = []
        for i in range(m):
            est = pickle.loads(est_blob)
            est.module_ = None
            est.params_ = jax.tree.unflatten(
                treedef, [leaf[i] for leaf in final_params_leaves]
            )
            est._factory_kwargs_built = dict(built_kwargs)
            est.history_ = out["final_history"][i]
            est.fit_seconds_ = per_machine_seconds

            steps = []
            for blob, stats in zip(scaler_blobs, out["scaler_stats"]):
                sc = pickle.loads(blob)
                sc.stats_ = {key: val[i] for key, val in stats.items()}
                steps.append(sc)
            base: Any = Pipeline([*steps, est]) if wrap else est

            det_scaler = pickle.loads(det_scaler_blob)
            det_scaler.stats_ = {
                key: val[i] for key, val in out["det_scaler_stats"].items()
            }

            det = DiffBasedAnomalyDetector(
                base_estimator=base,
                scaler=det_scaler,
                require_thresholds=spec.detector_proto.require_thresholds,
                window=spec.detector_proto.window,
            )
            det.feature_thresholds_ = feat_rows[i]
            det.aggregate_threshold_ = float(agg[i])
            det.cv_metadata_ = {
                "scores": {
                    name: {
                        "folds": folds_by[name][i],
                        "mean": float(means[name][i]),
                        "std": float(stds[name][i]),
                    }
                    for name in METRIC_NAMES
                },
                "feature_thresholds": feat_lists[i],
                "aggregate_threshold": agg_list[i],
                "fleet": {"bucket_size": m, "fleet_seconds": fleet_seconds},
            }
            if "moe" in out:
                moe = out["moe"]
                det.cv_metadata_["moe"] = {
                    "counted_on": "the final fit's optimiser steps",
                    "tokens_per_held_expert": moe["tokens"][i].tolist(),
                    "held_pairs": int(moe["held"][i]),
                    "selected_pairs": int(moe["selected"][i]),
                }
                if "loss_terms" in moe:
                    first, w1, then, w2 = (float(v) for v in moe["loss_terms"][i])
                    det.cv_metadata_["loss_terms"] = {
                        "counted_on": "the final fit's optimiser steps",
                        "next_row": first / max(w1, 1.0),
                        "row_after_next": then / max(w2, 1.0),
                        "row_after_next_positions": int(w2),
                    }
            detectors.append(det)
        return detectors


# ---------------------------------------------------------------------------
# The exact compiled program (cached across equal-signature length-groups)
# ---------------------------------------------------------------------------

# One jitted program per (module, scalers, windowing, cfg, folds, mesh) —
# the closure must be cached so repeat builds (bench warm runs, CV re-runs)
# hit jax's compile cache instead of re-tracing a fresh closure every call.
# The cache itself lives in the compile plane (`compile.cached_closure`):
# one LRU and one `gordo_compiled_programs` gauge across the whole stack,
# replacing the private _EXACT_PROGRAMS dict this module used to keep.


def _exact_fleet_program(
    module,
    scaler_opts,
    det_scaler_opts,
    window_mode: str,
    lookback: int,
    offset: int,
    cfg: TrainConfig,
    folds: Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]], ...],
    mesh,
    warm: bool = False,
    stride: int = 1,
):
    """Return the jitted exact program ``(X, y, seeds) -> out`` for one
    length-group (``warm=True``: ``(X, y, seeds, params0) -> out``).

    Single-machine parity by construction: each CV fold (and the final fit)
    materializes exactly the rows ``train.cv.cross_validate`` would hand the
    cloned pipeline — gather fold rows, fit the scaler chain on them, window,
    pad to the fold's OWN ``steps x bs`` geometry, fit with the same derived
    RNG keys.  No weight-mask approximations; the only difference from M
    separate single fits is the vmap over machines.

    The warm variant is the incremental-refresh entry point: ``params0``
    arrives as a TRACED stacked pytree (the previous generation's weights,
    leading axis = padded machine count) instead of being derived from the
    init keys, so every fold and the final fit resume from the served
    model.  Machine-count/length geometry still keys the compile cache the
    same way — warm and cold programs cache independently (``warm`` is part
    of the key) but share XLA lowerings across refresh cycles.

    Memory: the program keeps ``params0`` alive across the fold fits and the
    final fit (every fit starts from it), beside the running fit's weights,
    their gradient and Adam's two moments: 20 bytes a float32 parameter and
    machine.  ``builder.fleet_build.default_bucket_size`` plans a chunk from
    that figure.  (A cold sequence program draws ``params0`` again where each
    fit begins and holds 16.)

    Window mode ``"sequence"`` (``SequenceForecast``): the rows are cut into
    ``lookback``-row sequences at ``stride``, every position is a sample
    (the loss weighs positions, padding 0), a fold's held-out forecasts are
    put back into rows, and every fit runs through one traced optimiser step
    (:func:`_sequence_fits`), machines one after another.  A module that
    counts what its expert layers routed (``module.apply(..., counts=True)``)
    has the counts of the final fit's optimiser steps in ``out["moe"]``.
    """
    # Fold indices are digested (they can be tens of thousands of ints —
    # storing them verbatim in every cache key would bloat the cache and
    # make each lookup re-hash the full tuples).
    folds_digest = hashlib.md5(repr(folds).encode()).hexdigest()
    key = (
        module,
        scaler_opts,
        det_scaler_opts,
        window_mode,
        lookback,
        offset,
        cfg,
        folds_digest,
        mesh,
        bool(warm),
        int(stride),
    )

    from gordo_tpu.ops import metrics as jmetrics
    from gordo_tpu.ops.windows import sequences_to_rows
    from gordo_tpu.train.fit import batch_geometry, pad_weights

    det_cls, det_opts = det_scaler_opts
    fold_idx = [
        (np.asarray(tr, np.int32), np.asarray(te, np.int32)) for tr, te in folds
    ]
    scale_chain = _make_scale_chain(scaler_opts)
    apply_chain = _make_apply_chain(scaler_opts)
    windowize = _make_windowize(window_mode, lookback, stride)
    sequence = window_mode == "sequence"
    counted = sequence and hasattr(module, "cfg") and hasattr(
        module.cfg, "experts_held")

    def one_fit(params0, inputs, targets, fit_keys):
        """vmapped fit with THIS fold's true batch geometry (exactly
        ``train.fit.fit``: pad to steps*bs, weight-mask the padding)."""
        m = inputs.shape[0]
        na = inputs.shape[1]
        steps, bs, n_pad = batch_geometry(na, cfg.batch_size)
        w = pad_weights(na, n_pad)
        if n_pad:
            inputs = jnp.concatenate(
                [inputs, jnp.zeros((m, n_pad) + inputs.shape[2:], inputs.dtype)],
                axis=1,
            )
            targets = jnp.concatenate(
                [targets, jnp.zeros((m, n_pad) + targets.shape[2:], targets.dtype)],
                axis=1,
            )
        fit_fn = make_fit_fn(module, cfg, steps, bs)
        return jax.vmap(fit_fn, in_axes=(0, 0, 0, None, 0))(
            params0, inputs, targets, w, fit_keys
        )

    vapply = jax.vmap(lambda p, x: module.apply({"params": p}, x))

    def body(X, y, seeds, warm_params0):
        # X: (M, N, F) raw rows, y: (M, N, Fout) raw targets, seeds: (M,)
        init_keys, fit_keys = fleet_mod.fleet_keys(seeds)

        # Detector scaler: fit ONCE on the full raw target series
        # (cross_validate fits self.scaler before any fold).
        det_stats = jax.vmap(
            lambda ym: det_cls.compute_stats(ym, **dict(det_opts))
        )(y)

        # Final fit's scaler chain + windows (also provides the init shape).
        full_stats, Xt_full = scale_chain(X)
        inputs_full, targets_full = windowize(Xt_full, y)
        if counted:
            # runs where the program is traced: on the tracing chunk's
            # enqueue span
            telemetry.add_to_span(
                params=module.param_count(),
                experts_held=module.cfg.experts_held,
                sequences=inputs_full.shape[1],
                context=lookback,
                **module.moe_blocks(
                    min(cfg.batch_size, inputs_full.shape[1]) * lookback),
                **module.window_trips(
                    min(cfg.batch_size, inputs_full.shape[1]), lookback),
                **({"mtp_depth": module.cfg.mtp_depth,
                    "mtp_weight": module.cfg.mtp_weight}
                   if getattr(module.cfg, "mtp_depth", 0) else {}),
                # the layer pattern: how many layers have each kind of mixer
                **{f"layers_{kind}": len(module.cfg.layers_of(kind))
                   for kind in set(getattr(module.cfg, "pattern", ()))},
                # the rows a windowed attention layer's query sees
                **({"attn_window": module.cfg.attn_window}
                   if getattr(module.cfg, "attn_window", 0) else {}),
            )
        if sequence:
            params0 = None  # drawn where each fit begins, see _sequence_fits
        elif warm_params0 is None:
            params0 = fleet_mod.fleet_init(
                module, init_keys, inputs_full[0, :1]
            )
        else:
            params0 = warm_params0

        per_step_stats: List[List[Any]] = [[] for _ in scaler_opts]
        feat_maxes, total_maxes = [], []
        metric_vals: Dict[str, List[Any]] = {n: [] for n in METRIC_NAMES}
        routed = None

        def train_rows(tr):
            # Materialize the fold exactly as the single path would.
            X_tr, y_tr = jnp.take(X, tr, axis=1), jnp.take(y, tr, axis=1)
            stats_k, Xt = scale_chain(X_tr)
            return (stats_k, *windowize(Xt, y_tr))

        def held_out_rows(stats_k, te):
            X_te, y_te = jnp.take(X, te, axis=1), jnp.take(y, te, axis=1)
            te_inputs, _ = windowize(apply_chain(stats_k, X_te), y_te)
            return te_inputs, y_te

        if sequence:
            # every fit through one traced step (see _sequence_fits)
            trained = [train_rows(tr) for tr, _ in fold_idx]
            held_out = [held_out_rows(t[0], te) for t, (_, te) in zip(trained, fold_idx)]
            if warm_params0 is None:
                sample = inputs_full[0, :1]
                start, starts = (
                    lambda key: module.init(key, sample)["params"]), init_keys
            else:
                start, starts = (lambda given: given), warm_params0
            final_params, final_history, fold_preds, routed = _sequence_fits(
                module, cfg, lookback, stride, counted, start, starts, fit_keys,
                [(t[1], t[2], len(tr), h[0])
                 for t, h, (tr, _) in zip(trained, held_out, fold_idx)]
                + [(inputs_full, targets_full, X.shape[1], None)],
            )

        for k, (tr, te) in enumerate(fold_idx):
            if sequence:
                stats_k, y_te = trained[k][0], held_out[k][1]
                pred = jax.vmap(
                    lambda o: sequences_to_rows(o, len(te), lookback, stride)
                )(fold_preds[k])
            else:
                stats_k, inputs, targets = train_rows(tr)
                params_k, _ = one_fit(params0, inputs, targets, fit_keys)
                # Out-of-fold predictions on the materialized test slice.
                te_inputs, y_te = held_out_rows(stats_k, te)
                pred = vapply(params_k, te_inputs)
            y_true = y_te[:, offset:]

            for name in METRIC_NAMES:
                metric_vals[name].append(
                    jax.vmap(getattr(jmetrics, name))(y_true, pred)
                )
            y_s = jax.vmap(det_cls.apply, in_axes=(0, 0))(det_stats, y_true)
            p_s = jax.vmap(det_cls.apply, in_axes=(0, 0))(det_stats, pred)
            tag_err = jnp.abs(p_s - y_s)
            total = jnp.linalg.norm(tag_err, axis=-1)
            feat_maxes.append(
                jax.vmap(lambda e: _smoothed_max(e, SMOOTHING_WINDOW))(tag_err)
            )
            total_maxes.append(
                jax.vmap(
                    lambda t: _smoothed_max(t[:, None], SMOOTHING_WINDOW)[0]
                )(total)
            )
            for j, st in enumerate(stats_k):
                per_step_stats[j].append(st)

        # Final full-data fit (fold index -1 in the stats layout).
        if not sequence:
            final_params, final_history = one_fit(
                params0, inputs_full, targets_full, fit_keys
            )
        for j, st in enumerate(full_stats):
            per_step_stats[j].append(st)

        out = {
            # per scaler step: {stat: (M, K+1, ...)}; fold -1 = final fit
            "scaler_stats": [
                {
                    stat: jnp.stack([s[stat] for s in fold_stats], axis=1)
                    for stat in fold_stats[0]
                }
                for fold_stats in per_step_stats
            ],
            "det_scaler_stats": det_stats,
            "final_params": final_params,
            "final_history": final_history,
            "feature_thresholds": jnp.mean(
                jnp.stack(feat_maxes, axis=1), axis=1
            ),
            "aggregate_threshold": jnp.mean(
                jnp.stack(total_maxes, axis=1), axis=1
            ),
            "metrics": {
                name: jnp.stack(v, axis=1) for name, v in metric_vals.items()
            },
        }
        if routed is not None:
            # what the final fit's optimiser steps routed, per machine
            out["moe"] = routed
        if mesh is not None:
            out = jax.lax.with_sharding_constraint(out, model_sharding(mesh))
        return out

    if warm:
        def program(X, y, seeds, params0):
            return body(X, y, seeds, params0)
        name = "fleet.exact_warm"
    else:
        def program(X, y, seeds):
            return body(X, y, seeds, None)
        name = "fleet.exact"

    # closure construction above is cheap; on a cache hit the factory is
    # never called and the PREVIOUSLY built ClosureProgram (whose jit
    # trace cache AND warmed AOT executables are intact) is returned
    return compile_plane.cached_closure(
        key, lambda: compile_plane.closure_program(program, name=name)
    )


def _padded_fleet_program(
    module,
    scaler_opts,
    det_scaler_opts,
    window_mode: str,
    lookback: int,
    offset: int,
    cfg: TrainConfig,
    folds: Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]], ...],
    mesh,
    warm: bool = False,
):
    """The pad-up program ``(X, y, lens, seeds) -> out`` — ragged fleets
    without data loss (``warm=True`` appends a traced ``params0`` stacked
    pytree, exactly as in :func:`_exact_fleet_program`).

    ``X``/``y`` arrive NaN-padded past each machine's true row count
    (``lens``).  Row padding is handled by masking, never by dropping:

    - scaler stats: computed on the NaN-padded series — every fleetable
      scaler's stats are nan-aware reductions, so padding simply falls out;
    - training: zero-filled padding rows carry zero loss weight (the
      weight-mask machinery of ``train.fit.make_loss_fn``);
    - CV metrics: row-weighted metric variants (``ops.metrics``);
    - thresholds: rolling-smoothed errors at padded rows are masked to
      ``-inf`` before the row-max (padding is a SUFFIX, so every window
      ending on a real row contains only real rows).

    Semantics difference vs the exact per-length mode (documented
    contract, ``docs/fleet.md``): CV fold boundaries and minibatch
    geometry derive from the PADDED length, so a machine whose true length
    differs from the group length sees slightly different fold membership
    and shuffle partitions than its single-machine build would.  For
    machines already at the aligned length the program is the exact one
    (all-ones masks) — ``tests/test_fleet.py`` pins that parity.  ``lens``
    is a traced argument: machine-length variation never recompiles; only
    the padded group length does.
    """
    folds_digest = hashlib.md5(repr(folds).encode()).hexdigest()
    key = (
        "padded",
        module,
        scaler_opts,
        det_scaler_opts,
        window_mode,
        lookback,
        offset,
        cfg,
        folds_digest,
        mesh,
        bool(warm),
    )

    from gordo_tpu.ops.metrics import WEIGHTED_METRICS
    from gordo_tpu.train.fit import batch_geometry, make_fit_fn

    det_cls, det_opts = det_scaler_opts
    fold_idx = [
        (np.asarray(tr, np.int32), np.asarray(te, np.int32)) for tr, te in folds
    ]
    # the shared scale-chain on NaN-padded rows: nan-aware stat reductions
    # exclude padding, and NaN propagates through apply so step i+1's
    # stats exclude it too; the transformed output is discarded (training
    # inputs are rebuilt from the zero-padded arrays)
    scale_chain = _make_scale_chain(scaler_opts)
    apply_chain = _make_apply_chain(scaler_opts)
    windowize = _make_windowize(window_mode, lookback)

    def one_fit(params0, inputs, targets, wv, fit_keys):
        """vmapped fit with PER-MACHINE weights: fold batch geometry from
        the padded length, real rows weighted 1, padding 0."""
        m = inputs.shape[0]
        na = inputs.shape[1]
        steps, bs, n_pad = batch_geometry(na, cfg.batch_size)
        if n_pad:
            inputs = jnp.concatenate(
                [inputs, jnp.zeros((m, n_pad) + inputs.shape[2:], inputs.dtype)],
                axis=1,
            )
            targets = jnp.concatenate(
                [targets, jnp.zeros((m, n_pad) + targets.shape[2:], targets.dtype)],
                axis=1,
            )
            wv = jnp.concatenate(
                [wv, jnp.zeros((m, n_pad), wv.dtype)], axis=1
            )
        fit_fn = make_fit_fn(module, cfg, steps, bs)
        return jax.vmap(fit_fn)(params0, inputs, targets, wv, fit_keys)

    vapply = jax.vmap(lambda p, x: module.apply({"params": p}, x))
    masked_smoothed_max = _masked_smoothed_max

    def body(X, y, lens, seeds, warm_params0):
        # X: (M, N, F) NaN-padded, y: (M, N, Fout) NaN-padded, lens: (M,)
        init_keys, fit_keys = fleet_mod.fleet_keys(seeds)
        n = X.shape[1]
        valid = (
            jnp.arange(n, dtype=jnp.int32)[None, :] < lens[:, None]
        ).astype(jnp.float32)                       # (M, N)
        Xz = jnp.where(jnp.isnan(X), 0.0, X)
        yz = jnp.where(jnp.isnan(y), 0.0, y)

        det_stats = jax.vmap(
            lambda ym: det_cls.compute_stats(ym, **dict(det_opts))
        )(y)                                        # nan-aware: pads fall out

        full_stats, _ = scale_chain(X)
        Xt_full = jnp.where(
            valid[..., None] > 0, apply_chain(full_stats, Xz), 0.0
        )
        inputs_full, targets_full = windowize(Xt_full, yz)
        wv_full = valid[:, offset:] if offset else valid
        if warm_params0 is None:
            params0 = fleet_mod.fleet_init(
                module, init_keys, inputs_full[0, :1]
            )
        else:
            params0 = warm_params0

        per_step_stats: List[List[Any]] = [[] for _ in scaler_opts]
        feat_maxes, feat_has = [], []
        total_maxes = []
        metric_vals: Dict[str, List[Any]] = {n_: [] for n_ in METRIC_NAMES}

        for tr, te in fold_idx:
            X_tr_nan = jnp.take(X, tr, axis=1)
            stats_k, _ = scale_chain(X_tr_nan)
            valid_tr = jnp.take(valid, tr, axis=1)
            Xt = jnp.where(
                valid_tr[..., None] > 0,
                apply_chain(stats_k, jnp.take(Xz, tr, axis=1)),
                0.0,
            )
            inputs, targets = windowize(Xt, jnp.take(yz, tr, axis=1))
            wv = valid_tr[:, offset:] if offset else valid_tr
            params_k, _ = one_fit(params0, inputs, targets, wv, fit_keys)

            valid_te = jnp.take(valid, te, axis=1)
            Xt_te = jnp.where(
                valid_te[..., None] > 0,
                apply_chain(stats_k, jnp.take(Xz, te, axis=1)),
                0.0,
            )
            y_te = jnp.take(yz, te, axis=1)
            te_inputs, _ = windowize(Xt_te, y_te)
            pred = vapply(params_k, te_inputs)
            y_true = y_te[:, offset:]
            wv_te = valid_te[:, offset:] if offset else valid_te

            for name in METRIC_NAMES:
                metric_vals[name].append(
                    jax.vmap(WEIGHTED_METRICS[name])(y_true, pred, wv_te)
                )
            y_s = jax.vmap(det_cls.apply, in_axes=(0, 0))(det_stats, y_true)
            p_s = jax.vmap(det_cls.apply, in_axes=(0, 0))(det_stats, pred)
            tag_err = jnp.abs(p_s - y_s)
            total = jnp.linalg.norm(tag_err, axis=-1)
            feat_maxes.append(jax.vmap(masked_smoothed_max)(tag_err, wv_te))
            total_maxes.append(
                jax.vmap(
                    lambda t, w: masked_smoothed_max(t[:, None], w)[0]
                )(total, wv_te)
            )
            feat_has.append((jnp.sum(wv_te, axis=1) > 0).astype(jnp.float32))
            for j, st in enumerate(stats_k):
                per_step_stats[j].append(st)

        final_params, final_history = one_fit(
            params0, inputs_full, targets_full, wv_full, fit_keys
        )
        for j, st in enumerate(full_stats):
            per_step_stats[j].append(st)

        # fold means weighted by "this machine had any valid test rows in
        # this fold" — _dispatch_padded demotes machines too short for the
        # fold layout to the exact path, so this is belt-and-braces against
        # a 0/0 NaN-ing the artifact
        has = jnp.stack(feat_has, axis=1)            # (M, K)
        denom = jnp.maximum(jnp.sum(has, axis=1), 1.0)
        out = {
            "scaler_stats": [
                {
                    stat: jnp.stack([s[stat] for s in fold_stats], axis=1)
                    for stat in fold_stats[0]
                }
                for fold_stats in per_step_stats
            ],
            "det_scaler_stats": det_stats,
            "final_params": final_params,
            "final_history": final_history,
            "feature_thresholds": jnp.sum(
                jnp.stack(feat_maxes, axis=1) * has[:, :, None], axis=1
            ) / denom[:, None],
            "aggregate_threshold": jnp.sum(
                jnp.stack(total_maxes, axis=1) * has, axis=1
            ) / denom,
            "metrics": {
                name: jnp.stack(v, axis=1) for name, v in metric_vals.items()
            },
        }
        if mesh is not None:
            out = jax.lax.with_sharding_constraint(out, model_sharding(mesh))
        return out

    if warm:
        def program(X, y, lens, seeds, params0):
            return body(X, y, lens, seeds, params0)
        name = "fleet.padded_warm"
    else:
        def program(X, y, lens, seeds):
            return body(X, y, lens, seeds, None)
        name = "fleet.padded"

    return compile_plane.cached_closure(
        key, lambda: compile_plane.closure_program(program, name=name)
    )
