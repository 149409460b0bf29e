"""Jitted model fitting.

Reference equivalent: the ``keras Model.fit`` hot loop inside
``gordo_components/model/models.py::KerasBaseEstimator.fit`` — the only
compute-bound loop in the reference (single-process CPU TensorFlow).

TPU-native design: the ENTIRE fit — every epoch, every minibatch, the
per-epoch shuffle — is one XLA program: ``lax.scan`` over epochs around
``lax.scan`` over minibatches, with the dataset resident in device memory
(these datasets are tiny: months of 10-minute samples x tens of tags).
One dispatch, zero host↔device traffic inside training.  Shapes are static:
the data is padded to ``steps * batch_size`` rows and a weight vector masks
the padding out of the loss.

The pure pieces (``make_loss_fn``, ``make_optimizer``, ``make_epoch_fn``)
are reused by the fleet engine (``gordo_tpu.parallel.fleet``) which vmaps
them across stacked models and shards them over the device mesh.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gordo_tpu import compile as compile_plane
from gordo_tpu import telemetry

# _fit_jit donates params/X/y/w.  Only params can alias an output, so XLA
# reports X/y/w as "not usable" donations — donating them is still the
# point: the padded training set frees at its last use inside the program
# instead of surviving until the result fetch.  Silence that advisory.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

OPTIMIZERS: Dict[str, Callable[..., optax.GradientTransformation]] = {
    "adam": optax.adam,
    "adamw": optax.adamw,
    "sgd": optax.sgd,
    "rmsprop": optax.rmsprop,
    "adagrad": optax.adagrad,
    "nadam": optax.nadam,
    "lamb": optax.lamb,
}

#: optimisers whose update of an element reads that element's own
#: gradient and state alone, so that it gives the same numbers whether
#: leaves are carried apart or concatenated.  Not ``lamb``: its trust
#: ratio is a norm over each leaf.
ELEMENTWISE_OPTIMIZERS = frozenset(
    {"adam", "adamw", "sgd", "rmsprop", "adagrad", "nadam"}
)

_FIT_LAYOUT = telemetry.counter(
    "gordo_fit_layout_total",
    "Fits traced, by the layout their parameters are carried in through "
    "the epochs: packed (the layout the module's layers compute in) or "
    "public (the tree artifacts store)",
    labels=("layout",),
)


def _mse(pred, target):
    return (pred - target) ** 2


def _mae(pred, target):
    return jnp.abs(pred - target)


def _huber(pred, target, delta: float = 1.0):
    err = pred - target
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    return 0.5 * quad ** 2 + delta * (abs_err - quad)


LOSSES: Dict[str, Callable] = {
    "mse": _mse,
    "mean_squared_error": _mse,
    "mae": _mae,
    "mean_absolute_error": _mae,
    "huber": _huber,
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Hashable training config (static arg to the jitted fit)."""

    epochs: int = 10
    batch_size: int = 256
    optimizer: str = "adam"
    learning_rate: float = 1e-3
    loss: str = "mse"
    shuffle: bool = True
    optimizer_kwargs: Tuple[Tuple[str, Any], ...] = ()

    @classmethod
    def from_kwargs(cls, kwargs: Dict[str, Any]) -> Tuple["TrainConfig", Dict[str, Any]]:
        """Split estimator kwargs into (train config, factory kwargs)."""
        known = {f.name for f in dataclasses.fields(cls)}
        cfg_kwargs = {}
        rest = {}
        for k, v in kwargs.items():
            if k in known:
                cfg_kwargs[k] = v
            elif k == "optimizer_kwargs" or k == "compile_kwargs":
                cfg_kwargs["optimizer_kwargs"] = tuple(sorted(dict(v).items()))
            else:
                rest[k] = v
        if "optimizer_kwargs" in cfg_kwargs and not isinstance(
            cfg_kwargs["optimizer_kwargs"], tuple
        ):
            cfg_kwargs["optimizer_kwargs"] = tuple(
                sorted(dict(cfg_kwargs["optimizer_kwargs"]).items())
            )
        return cls(**cfg_kwargs), rest


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    name = cfg.optimizer.lower()
    if name not in OPTIMIZERS:
        raise ValueError(f"Unknown optimizer {cfg.optimizer!r}; available: {sorted(OPTIMIZERS)}")
    kwargs = dict(cfg.optimizer_kwargs)
    lr = kwargs.pop("learning_rate", cfg.learning_rate)
    return OPTIMIZERS[name](lr, **kwargs)


def make_loss_fn(apply_fn: Callable, loss: str, aux: bool = False,
                 second: float = 0.0) -> Callable:
    """Weighted scalar loss of (params, x, y, w); w masks padded rows.
    ``w`` weighs samples ``(bs,)`` or, for a model that forecasts every
    position of a sequence, positions ``(bs, T)``: the error is averaged
    over the axes ``w`` does not have.  With ``aux``, ``apply_fn`` returns
    ``(prediction, aux)`` and so does the loss: ``(loss, aux)``, for
    ``jax.value_and_grad(..., has_aux=True)``.

    With ``second`` (lambda > 0) the prediction is a pair of forecasts from
    one pass over the minibatch, position ``i``'s target and its successor's
    (a multi-token-prediction module's): the loss is ``L1 + second * L2``,
    where ``L2`` holds the second forecast of position ``i`` against the
    target of position ``i + 1`` with that position's weight (the last
    position of a sequence has no successor and weighs 0) and is a weighted
    mean over its own weights' sum.  The aux, a dict then, gains
    ``"loss_terms"``: ``[L1 * sum(w), sum(w), L2 * sum(w2), sum(w2)]``, the
    two terms' sums apart."""
    if loss not in LOSSES:
        raise ValueError(f"Unknown loss {loss!r}; available: {sorted(LOSSES)}")
    elem = LOSSES[loss]

    def mean(pred, y, w):
        per_row = jnp.mean(elem(pred, y), axis=tuple(range(w.ndim, pred.ndim)))
        return jnp.sum(per_row * w) / jnp.maximum(jnp.sum(w), 1.0)

    def loss_fn(params, x, y, w):
        pred = apply_fn({"params": params}, x)
        pred, extra = pred if aux else (pred, None)
        # a leaf name: what the loss does with the module's forecast, and
        # the start of its backward (PERF.md section 3)
        with jax.named_scope("fit.loss"):
            if not second:
                value = mean(pred, y, w)
                return (value, extra) if aux else value
            if w.ndim != 2:
                raise ValueError("a second horizon needs a weight for every position")
            pred, ahead = pred
            # position i's successor: its target and its weight, zeros at the end
            y2, w2 = (jnp.concatenate([z[:, 1:], jnp.zeros_like(z[:, :1])], axis=1)
                      for z in (y, w))
            first, then = mean(pred, y, w), mean(ahead, y2, w2)
            value = first + second * then
            if not aux:
                return value
            terms = jnp.stack(
                [first * jnp.sum(w), jnp.sum(w), then * jnp.sum(w2), jnp.sum(w2)])
            return value, {**(extra or {}), "loss_terms": terms}

    return loss_fn


def training_pass(module, counts: bool = False):
    """``(apply_fn, second)`` for :func:`make_loss_fn`: the module's apply
    as a training step calls it.  A module that trains a second horizon
    beside its main head says so by ``mtp_weight`` and is asked for both
    forecasts; ``counts`` asks a module that counts what it routed for the
    counts as the loss's aux."""
    second = float(getattr(module, "mtp_weight", 0.0) or 0.0)
    asked = {**({"counts": True} if counts else {}), **({"mtp": True} if second else {})}
    if not asked:
        return module.apply, 0.0
    return (lambda variables, x: module.apply(variables, x, **asked)), second


def init_params(module, rng: jax.Array, sample_x: jnp.ndarray):
    return module.init(rng, sample_x)["params"]


def batch_geometry(n: int, batch_size: int) -> Tuple[int, int, int]:
    """Shared minibatch geometry: ``(steps, bs, n_pad)`` for ``n`` rows.

    Single source of truth for the padding arithmetic that the single-model,
    fleet, and data-parallel fits must all agree on (their bit-parity tests
    depend on identical geometry).
    """
    bs = int(min(batch_size, n))
    steps = -(-n // bs)
    return steps, bs, steps * bs - n


def pad_weights(n: int, n_pad: int, w=None):
    """The weight of every sample slot of a padded training set: ``w``
    (default: ones ``(n,)``) then zeros for the ``n_pad`` padding samples."""
    w = jnp.ones((n,), jnp.float32) if w is None else jnp.asarray(w, jnp.float32)
    return jnp.concatenate([w, jnp.zeros((n_pad,) + w.shape[1:], jnp.float32)])


def _pad_batches(X, y, batch_size: int, w=None):
    """Pad to a whole number of batches; returns (X, y, w, steps, bs)."""
    n = X.shape[0]
    steps, bs, n_pad = batch_geometry(n, batch_size)
    w = pad_weights(n, n_pad, w)
    if n_pad:
        X = jnp.concatenate([X, jnp.zeros((n_pad,) + X.shape[1:], X.dtype)])
        y = jnp.concatenate([y, jnp.zeros((n_pad,) + y.shape[1:], y.dtype)])
    return X, y, w, steps, bs


def make_epoch_fn(loss_fn: Callable, tx: optax.GradientTransformation,
                  steps: int, bs: int, shuffle: bool) -> Callable:
    """One epoch as a pure function — scan over minibatches of padded data."""

    grad_fn = jax.value_and_grad(loss_fn)

    def epoch(carry, key, X, y, w):
        params, opt_state = carry
        n_total = X.shape[0]
        if shuffle:
            perm = jax.random.permutation(key, n_total)
        else:
            perm = jnp.arange(n_total)
        xb = X[perm].reshape((steps, bs) + X.shape[1:])
        yb = y[perm].reshape((steps, bs) + y.shape[1:])
        wb = w[perm].reshape((steps, bs) + w.shape[1:])

        def step(c, batch):
            p, s = c
            bx, by, bw = batch
            loss, grads = grad_fn(p, bx, by, bw)
            with jax.named_scope("fit.optimizer"):
                updates, s = tx.update(grads, s, p)
                p = optax.apply_updates(p, updates)
            return (p, s), loss * jnp.sum(bw)

        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state), (xb, yb, wb))
        epoch_loss = jnp.sum(losses) / jnp.maximum(jnp.sum(w), 1.0)
        return (params, opt_state), epoch_loss

    return epoch


def make_stateful_fit_fn(module, cfg: TrainConfig, steps: int, bs: int) -> Callable:
    """Resumable fit: ``(params, opt_state, X, y, w, epoch_keys) ->
    (params, opt_state, history)``.

    Unlike :func:`make_fit_fn` the optimizer state flows through, and the
    per-epoch shuffle keys come in as an array — so a fit chunked across
    checkpoints (``gordo_tpu.train.checkpoint``) is bit-identical to the
    uninterrupted run.
    """
    tx = make_optimizer(cfg)
    loss_fn = make_loss_fn(module.apply, cfg.loss)
    epoch = make_epoch_fn(loss_fn, tx, steps, bs, cfg.shuffle)

    def fit_fn(params, opt_state, X, y, w, epoch_keys):
        def body(carry, key):
            return epoch(carry, key, X, y, w)

        (params, opt_state), history = jax.lax.scan(
            body, (params, opt_state), epoch_keys
        )
        return params, opt_state, history

    return fit_fn


def packed_layout(module, cfg: TrainConfig) -> bool:
    """Whether a fit of ``module`` under ``cfg`` may carry the module's
    packed parameters (``pack`` / ``unpack`` / ``apply_packed``, as
    ``LSTMAutoEncoderModule`` has them) for the public tree and give the
    same numbers: the optimiser is elementwise, and none of its keyword
    arguments can address a leaf of the public tree (a mask, a per-leaf
    schedule: anything that is not a plain number, string or None)."""
    return (
        hasattr(module, "pack")
        and cfg.optimizer.lower() in ELEMENTWISE_OPTIMIZERS
        and all(
            v is None or isinstance(v, (int, float, str))
            for _, v in cfg.optimizer_kwargs
        )
    )


def make_fit_fn(module, cfg: TrainConfig, steps: int, bs: int) -> Callable:
    """The whole multi-epoch fit as ONE pure function
    ``(params, X, y, w, rng) -> (params, history)``.

    This is the unit the fleet engine vmaps across stacked models
    (``gordo_tpu.parallel.fleet``) and the single-model path jits directly.

    Where :func:`packed_layout` allows, the parameters are packed once,
    every epoch and step (loss, gradient, optimiser state, update) runs on
    the packed tree, and the public tree is unpacked once after the last
    epoch: a step then carries and updates a few large arrays instead of
    concatenating, slicing and updating one small array per gate.
    """
    tx = make_optimizer(cfg)
    packed = packed_layout(module, cfg)
    if packed:
        def apply_fn(variables, x):
            return module.apply_packed(variables["params"], x)
        second = 0.0
    else:
        apply_fn, second = training_pass(module)
    loss_fn = make_loss_fn(apply_fn, cfg.loss, second=second)
    epoch = make_epoch_fn(loss_fn, tx, steps, bs, cfg.shuffle)

    def fit_fn(params, X, y, w, rng):
        if packed:
            params = module.pack(params)
        opt_state = tx.init(params)
        # runs where the fit is traced: once per program and fit
        _FIT_LAYOUT.inc(1.0, "packed" if packed else "public")
        telemetry.add_to_span(
            fit_traces=1,
            carry_leaves=len(jax.tree.leaves((params, opt_state))),
        )
        keys = jax.random.split(rng, cfg.epochs)

        def body(carry, key):
            return epoch(carry, key, X, y, w)

        (params, _), history = jax.lax.scan(body, (params, opt_state), keys)
        return (module.unpack(params) if packed else params), history

    return fit_fn


# Static-keyed on the module itself: flax modules are frozen dataclasses, so
# two estimators built from the same factory kwargs produce EQUAL modules and
# hit the same compiled executable (per-instance bound methods would not —
# every CV fold / fleet member would recompile).
# params/X/y/w are DONATED: the fitted params alias the incoming params
# buffers, and the (padded) training set frees at its last device use —
# callers must hand over buffers they no longer need (fit() guarantees
# this for its own callers by copying anything the caller still owns).
def _fit_jit_fn(module, cfg: TrainConfig, steps: int, bs: int,
                params, X, y, w, rng):
    return make_fit_fn(module, cfg, steps, bs)(params, X, y, w, rng)


_fit_jit = compile_plane.jit(
    _fit_jit_fn,
    name="train.fit",
    static_argnames=("module", "cfg", "steps", "bs"),
    donate_argnums=(4, 5, 6, 7),
)


def fit(module, X, y, cfg: TrainConfig,
        rng: Optional[jax.Array] = None,
        params: Optional[Any] = None,
        w: Optional[Any] = None) -> Tuple[Any, np.ndarray]:
    """Fit ``module`` on (X, y); returns (params, per-epoch loss history).

    The whole multi-epoch loop compiles to a single XLA executable; repeat
    fits with the same shapes/config reuse the compiled program.  ``w``
    weighs the samples (or a sequence model's positions, see
    :func:`make_loss_fn`); by default every sample weighs 1.
    """
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    X_in, y_in, params_in = X, y, params
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if params is None:
        init_rng, rng = jax.random.split(rng)
        params = init_params(module, init_rng, X[:1])
    Xp, yp, w, steps, bs = _pad_batches(X, y, cfg.batch_size, w)
    # _fit_jit donates params/X/y/w; a donated buffer is deleted, so never
    # hand over one the CALLER may still hold.  jnp.asarray copies host
    # arrays and padding copies device arrays — only an unpadded
    # caller-owned jax array (or caller-supplied params, or y aliasing X)
    # can reach the donated slots, so copy exactly those cases.
    if Xp is X_in:
        Xp = jnp.array(Xp)
    if yp is y_in or yp is Xp:
        yp = jnp.array(yp)
    if params_in is not None:
        params = jax.tree.map(jnp.array, params)
    params, history = _fit_jit(module, cfg, steps, bs, params, Xp, yp, w, rng)
    return params, np.asarray(history)
