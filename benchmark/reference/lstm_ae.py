"""Plain reference of the two LSTM autoencoders and of their fit.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: the LSTM written step by step (no hoisted projection; one
``lax.scan`` over the window's rows, which keeps the program small enough
to compile in seconds), mean squared error on the window's last row, Adam
written out, the machines of a sample fitted side by side under ``vmap``.
It imports nothing of the program and takes nothing the program made; it
makes its own initial weights from the seed by the published recipe of the
layers (LeCun-normal input kernels, orthogonal recurrent kernels, zero
biases), with the per-parameter keys folded from the parameter's path the
way ``flax.linen`` names them, so that a sound program starts where the
reference starts.

``quantize`` is the control's hook: a function put on both operands of
every matmul.  ``None`` is the reference; rounding to float8 is the nearest
precision below the bfloat16 compute the configurations state.
"""

from __future__ import annotations

import functools
import hashlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GATES = "ifgo"
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
_EPS = 1e-12


# ---------------------------------------------------------------------------
# shapes from a configuration
# ---------------------------------------------------------------------------

def hourglass_dims(n_features: int, encoding_layers: int,
                   compression_factor: float) -> Tuple[int, ...]:
    """Upstream ``hourglass_calc_dims``: an even taper down to
    ``n_features * compression_factor``."""
    smallest = max(min(round(n_features * compression_factor), n_features), 1)
    slope = (n_features - smallest) / encoding_layers
    return tuple(
        max(int(round(n_features - i * slope)), 1)
        for i in range(1, encoding_layers + 1)
    )


def layer_dims(model: Dict[str, Any], n_features: int) -> Tuple[int, ...]:
    """Units of every LSTM layer, encoder then mirrored decoder."""
    kind = model["kind"]
    if kind == "lstm_hourglass":
        enc = hourglass_dims(
            n_features, int(model.get("encoding_layers", 3)),
            float(model.get("compression_factor", 0.5)),
        )
    elif kind == "lstm_symmetric":
        enc = tuple(int(d) for d in model.get("dims", (256, 128, 64)))
    else:
        raise ValueError(f"no reference for kind {kind!r}")
    return enc + enc[::-1]


# ---------------------------------------------------------------------------
# initial weights from the seed
# ---------------------------------------------------------------------------

def _path_key(root: jax.Array, *path) -> jax.Array:
    """Key of one parameter: the root key with the SHA-1 of the parameter's
    path (module names, then the parameter's ordinal in its module) folded
    in — ``flax.linen``'s rule for ``Module.param``."""
    m = hashlib.sha1()
    for part in path:
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(int(part).to_bytes((int(part).bit_length() + 7) // 8, "big"))
    word = int.from_bytes(m.digest()[:4], "big")
    return jax.random.fold_in(root, jnp.uint32(word))


@functools.lru_cache(maxsize=None)
def _init_fn(dims: Tuple[int, ...], n_features: int, n_out: int):
    """The initialiser of one architecture as ONE program (a run is a new
    process: dozens of one-operation programs would each compile anew)."""

    def init(key):
        init_key, fit_key = jax.random.split(key)
        lecun = jax.nn.initializers.lecun_normal()
        ortho = jax.nn.initializers.orthogonal()
        params: Dict[str, Any] = {}
        prev = n_features
        for li, h in enumerate(dims):
            cell = f"OptimizedLSTMCell_{li}"
            layer: Dict[str, Any] = {}
            for g in GATES:
                layer[f"i{g}"] = {"kernel": lecun(
                    _path_key(init_key, cell, f"i{g}", 1), (prev, h), jnp.float32)}
            for g in GATES:
                layer[f"h{g}"] = {
                    "kernel": ortho(
                        _path_key(init_key, cell, f"h{g}", 1), (h, h), jnp.float32),
                    "bias": jnp.zeros((h,), jnp.float32),
                }
            params[cell] = layer
            prev = h
        params["out"] = {
            "kernel": lecun(_path_key(init_key, "out", 1), (prev, n_out), jnp.float32),
            "bias": jnp.zeros((n_out,), jnp.float32),
        }
        return params, fit_key

    return jax.jit(init)


def init_params(seed: int, dims: Sequence[int], n_features: int,
                n_out: int) -> Tuple[Dict[str, Any], jax.Array]:
    """``(params, fit_key)`` as a fit from ``seed`` starts: the seed's key
    splits into the initialiser's and the shuffles'."""
    return _init_fn(tuple(int(d) for d in dims), int(n_features), int(n_out))(
        jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward, loss
# ---------------------------------------------------------------------------

def _matmul(a, b, quantize):
    if quantize is not None:
        a, b = quantize(a), quantize(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def forward(params: Dict[str, Any], x: jnp.ndarray, n_layers: int,
            quantize: Optional[Callable] = None) -> jnp.ndarray:
    """``x``: ``(B, T, F)`` scaled windows → ``(B, n_out)``, the
    reconstruction of each window's last row.  Every layer's output passes
    through tanh before the next, as both factories default to."""
    seq = x.astype(jnp.float32)
    for li in range(n_layers):
        layer = params[f"OptimizedLSTMCell_{li}"]
        w_i = jnp.concatenate([layer[f"i{g}"]["kernel"] for g in GATES], axis=-1)
        w_h = jnp.concatenate([layer[f"h{g}"]["kernel"] for g in GATES], axis=-1)
        b = jnp.concatenate([layer[f"h{g}"]["bias"] for g in GATES], axis=-1)
        units = w_h.shape[0]

        def step(carry, x_t, w_i=w_i, w_h=w_h, b=b):
            c, h = carry
            z = _matmul(x_t, w_i, quantize) + _matmul(h, w_h, quantize) + b
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (c, h), h

        zeros = jnp.zeros((seq.shape[0], units), jnp.float32)
        _, outs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(seq, 0, 1))
        seq = jnp.tanh(jnp.swapaxes(outs, 0, 1))
    head = params["out"]
    return _matmul(seq[:, -1], head["kernel"], quantize) + head["bias"]


def loss_fn(params, x, y, w, n_layers, quantize=None):
    """Mean squared error over the rows that ``w`` marks as real."""
    pred = forward(params, x, n_layers, quantize)
    per_row = jnp.mean((pred - y) ** 2, axis=-1)
    return jnp.sum(per_row * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# the fit: min-max scaling, windows, shuffled minibatches, Adam
# ---------------------------------------------------------------------------

def minmax_scale(rows: jnp.ndarray) -> jnp.ndarray:
    lo = jnp.min(rows, axis=0)
    hi = jnp.max(rows, axis=0)
    scale = 1.0 / jnp.maximum(hi - lo, _EPS)
    return rows * scale + (0.0 - lo * scale)


def windows(rows: jnp.ndarray, lookback: int) -> jnp.ndarray:
    n = rows.shape[0] - lookback + 1
    idx = jnp.arange(n)[:, None] + jnp.arange(lookback)[None, :]
    return rows[idx]


@functools.lru_cache(maxsize=None)
def _epoch_fn(n_layers: int, bs: int, lr: float, quantize: Optional[Callable]):
    """One epoch, jitted once per array shape: Adam over the minibatches of
    ``perm`` in order.  Only the first ``n_steps`` rows of ``perm`` are
    taken; the rest leave the state as it is, so that a fold (fewer rows,
    fewer steps) runs through the program compiled for the whole series."""
    grad = jax.value_and_grad(loss_fn)

    def epoch(carry, perm, n_steps, x, y, w):
        def step(carry, item):
            index, batch_idx = item
            p, m, v, t = carry
            bw = w[batch_idx]
            loss, g = grad(p, x[batch_idx], y[batch_idx], bw, n_layers, quantize)
            t1 = t + 1
            m1 = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
            v1 = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
            c1 = 1 - ADAM_B1 ** t1
            c2 = 1 - ADAM_B2 ** t1
            p1 = jax.tree.map(
                lambda a, mm, vv: a - lr * (mm / c1) / (jnp.sqrt(vv / c2) + ADAM_EPS),
                p, m1, v1,
            )
            live = index < n_steps
            keep = lambda new, old: jax.tree.map(  # noqa: E731
                lambda a, b: jnp.where(live, a, b), new, old)
            carry = (keep(p1, p), keep(m1, m), keep(v1, v), jnp.where(live, t1, t))
            seen = jnp.where(live, jnp.sum(bw), 0.0)
            return carry, (loss * seen, seen)

        carry, (losses, seen) = jax.lax.scan(
            step, carry, (jnp.arange(perm.shape[0]), perm))
        return carry, jnp.sum(losses) / jnp.maximum(jnp.sum(seen), 1.0)

    # over the machines of a stack: each its own state and rows, all the
    # same shuffles and the same marks (one project, one seed, one length)
    return jax.jit(jax.vmap(epoch, in_axes=(0, None, None, 0, 0, None)))


def _steps(n_windows: int, batch: int) -> Tuple[int, int]:
    bs = min(batch, n_windows)
    return -(-n_windows // bs), bs


@functools.lru_cache(maxsize=None)
def _prepare_fn(lookback: int, pad: int):
    """A stack's raw rows → scaled windows, raw targets and the marks of the
    real rows, each padded by ``pad`` rows; and every machine's start."""

    def prepare(rows, params0):
        x = jax.vmap(lambda r: windows(minmax_scale(r), lookback))(rows)
        y = rows[:, lookback - 1:]
        n_machines, n = x.shape[:2]
        w = jnp.concatenate([jnp.ones((n,), jnp.float32), jnp.zeros((pad,), jnp.float32)])
        x = jnp.concatenate([x, jnp.zeros((n_machines, pad) + x.shape[2:], x.dtype)], axis=1)
        y = jnp.concatenate([y, jnp.zeros((n_machines, pad) + y.shape[2:], y.dtype)], axis=1)
        stacked0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_machines,) + a.shape), params0)
        zeros = jax.tree.map(jnp.zeros_like, stacked0)
        carry = (stacked0, zeros, zeros, jnp.zeros((n_machines,), jnp.float32))
        return x, y, w, carry

    return jax.jit(prepare)


@functools.lru_cache(maxsize=None)
def _perms_fn(epochs: int, steps: int, bs: int, cap_steps: int):
    """Every epoch's shuffle of the rows (padded to whole minibatches), cut
    into minibatches and padded with idle steps up to ``cap_steps``."""

    def perms(fit_key):
        out = []
        for key in jax.random.split(fit_key, epochs):
            perm = jax.random.permutation(key, steps * bs).reshape(steps, bs)
            out.append(jnp.concatenate(
                [perm, jnp.zeros((cap_steps - steps, bs), perm.dtype)]))
        return jnp.stack(out)

    return jax.jit(perms)


def fit(rows: np.ndarray, model: Dict[str, Any], seed: int,
        quantize: Optional[Callable] = None,
        max_steps: Optional[int] = None,
        capacity_rows: Optional[int] = None) -> Dict[str, Any]:
    """One fit of each machine of a stack ``(machines, rows, tags)`` from
    its raw rows; ``(rows, tags)`` is one machine, and the result then has
    no machine axis.  The machines of a project share the seed, so they
    start from the same weights and see the same shuffles; each has its own
    rows, and nothing is shared between their fits.

    Inputs are the min-max-scaled rows cut into ``lookback_window`` windows;
    the target of a window is its last RAW row (the pipeline scales what the
    model reads, not what it is asked to reproduce).  Every epoch shuffles
    the rows, padded to whole minibatches, anew.  Returns the initial
    weights (one copy), the final weights and the per-epoch mean loss of
    every machine.  ``max_steps`` stops every epoch early (tests only);
    ``capacity_rows`` pads the arrays to the shapes of a longer series, so
    that a fold's fit reuses the whole series' compiled epoch.
    """
    rows = jnp.asarray(rows, jnp.float32)
    single = rows.ndim == 2
    if single:
        rows = rows[None]
    lookback = int(model["lookback_window"])
    epochs = int(model.get("epochs", 1))
    batch = int(model.get("batch_size", 32))
    lr = float(model.get("learning_rate", 1e-3))
    _, n_rows, n_features = rows.shape
    dims = layer_dims(model, n_features)
    params0, fit_key = init_params(seed, dims, n_features, n_features)

    n = n_rows - lookback + 1
    steps, bs = _steps(n, batch)
    cap_steps, cap_bs = _steps(int(capacity_rows or n_rows) - lookback + 1, batch)
    if cap_bs != bs or cap_steps < steps:
        raise ValueError("capacity_rows is smaller than the rows, or under one batch")
    run_steps = steps if max_steps is None else min(steps, max_steps)
    x, y, w, carry = _prepare_fn(lookback, cap_steps * bs - n)(rows, params0)
    perms = _perms_fn(epochs, steps, bs, cap_steps)(fit_key)

    epoch = _epoch_fn(len(dims), bs, lr, quantize)
    history = []
    with jax.default_matmul_precision("highest"):
        for perm in perms:
            carry, epoch_loss = epoch(carry, perm, run_steps, x, y, w)
            history.append(np.asarray(epoch_loss))
    params = jax.tree.map(np.asarray, carry[0])
    history = np.stack(history, axis=1)
    if single:
        params, history = jax.tree.map(lambda a: a[0], params), history[0]
    return {
        "init": jax.tree.map(np.asarray, params0),
        "params": params,
        "history": history,
    }


# ---------------------------------------------------------------------------
# cross-validation: the thresholds of the anomaly detector
# ---------------------------------------------------------------------------

SMOOTHING_WINDOW = 6   # rows of the trailing rolling minimum


def expanding_folds(n_rows: int, n_splits: int) -> Tuple[Tuple[int, int], ...]:
    """``(train_end, test_end)`` of each fold: the series is cut into
    ``n_splits + 1`` blocks of ``n_rows // (n_splits + 1)`` rows, fold k
    trains on the first k blocks and is tested on the next, and the last
    test block takes the rows left over."""
    block = n_rows // (n_splits + 1)
    return tuple(
        (block * k, block * (k + 1) if k < n_splits else n_rows)
        for k in range(1, n_splits + 1)
    )


def smoothed_max(err: np.ndarray) -> np.ndarray:
    """Per column, the largest value over the rows (axis -2) of the
    trailing rolling minimum over ``SMOOTHING_WINDOW`` rows (shorter at the
    start of the series)."""
    err = np.asarray(err, np.float64)
    front = np.full(err.shape[:-2] + (SMOOTHING_WINDOW - 1,) + err.shape[-1:], np.inf)
    rolled = np.lib.stride_tricks.sliding_window_view(
        np.concatenate([front, err], axis=-2), SMOOTHING_WINDOW, axis=-2)
    return rolled.min(axis=-1).max(axis=-2)


@functools.lru_cache(maxsize=None)
def _predict_fn(n_layers: int, lookback: int, quantize: Optional[Callable]):
    """A fold's held-out rows, scaled by the fold's own training rows and
    cut into windows, through every machine's fitted model."""

    def predict(params, train, held):
        lo, hi = jnp.min(train, axis=1), jnp.max(train, axis=1)
        scale = 1.0 / jnp.maximum(hi - lo, _EPS)
        scaled = held * scale[:, None] + (0.0 - lo * scale)[:, None]
        x = jax.vmap(lambda r: windows(r, lookback))(scaled)
        return jax.vmap(lambda p, xs: forward(p, xs, n_layers, quantize))(params, x)

    return jax.jit(predict)


def cross_validate(rows: np.ndarray, model: Dict[str, Any], seed: int,
                   n_splits: int, quantize: Optional[Callable] = None,
                   max_steps: Optional[int] = None) -> np.ndarray:
    """The detector's thresholds of every machine of a stack (or of one
    machine), the aggregate one first and then one per tag: for each
    expanding fold a fit on the fold's rows from the same start and the
    same shuffles as the final fit, its reconstruction of the held-out
    block, the absolute error in the scale of the whole series (min-max of
    the raw targets), smoothed and maximised over the block; then the mean
    over the folds.  The aggregate error of a row is the Euclidean norm of
    its tags' errors."""
    rows = np.asarray(rows, np.float32)
    single = rows.ndim == 2
    if single:
        rows = rows[None]
    lookback = int(model["lookback_window"])
    n_rows = rows.shape[1]
    span = np.maximum(rows.max(axis=1) - rows.min(axis=1), _EPS).astype(np.float64)
    n_layers = len(layer_dims(model, rows.shape[2]))
    per_fold = []
    for train_end, test_end in expanding_folds(n_rows, n_splits):
        train = rows[:, :train_end]
        fitted = fit(train, model, seed, quantize=quantize, max_steps=max_steps,
                     capacity_rows=n_rows)
        with jax.default_matmul_precision("highest"):
            pred = _predict_fn(n_layers, lookback, quantize)(
                fitted["params"], train, rows[:, train_end:test_end])
        truth = rows[:, train_end + lookback - 1: test_end].astype(np.float64)
        err = np.abs(np.asarray(pred, np.float64) - truth) / span[:, None]
        total = np.linalg.norm(err, axis=-1, keepdims=True)
        per_fold.append(np.concatenate([smoothed_max(total), smoothed_max(err)], axis=-1))
    thresholds = np.mean(per_fold, axis=0)
    return thresholds[0] if single else thresholds


def float8(a):
    """The control's rounding: through float8 (e4m3) and back."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def bfloat16(a):
    """Rounding through bfloat16 — what the program's compute does to a
    matmul's operands; used by the tests' control at float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)
