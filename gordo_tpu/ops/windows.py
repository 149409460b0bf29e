"""Device-side sliding-window materialisation for LSTM estimators.

Reference equivalent: the keras ``TimeseriesGenerator`` helper used by
``KerasLSTMAutoEncoder``/``KerasLSTMForecast`` in
``gordo_components/model/models.py`` — there a host-side Python generator;
here a single gather on device (static shapes, vmap/jit-safe).
"""

from __future__ import annotations

import jax.numpy as jnp


def num_windows(n_rows: int, lookback: int) -> int:
    return max(n_rows - lookback + 1, 0)


def make_windows(X: jnp.ndarray, lookback: int) -> jnp.ndarray:
    """(N, F) -> (N - lookback + 1, lookback, F) overlapping windows."""
    X = jnp.asarray(X)
    n = X.shape[0]
    if n < lookback:
        raise ValueError(f"Need at least lookback={lookback} rows, got {n}")
    idx = jnp.arange(n - lookback + 1)[:, None] + jnp.arange(lookback)[None, :]
    return X[idx]


def num_sequences(n_rows: int, context: int, stride: int) -> int:
    """Sequences that :func:`make_sequences` cuts ``n_rows`` rows into."""
    n_in = max(n_rows - 1, 0)
    return -(-max(n_in - context, 0) // stride) + 1


def make_sequences(X: jnp.ndarray, y: jnp.ndarray, context: int, stride: int):
    """Next-row training sequences: ``(N, F)``, ``(N, F_out)`` →
    ``inputs (S, T, F)``, ``targets (S, T, F_out)``, ``weights (S, T)``.

    Sequence ``s`` reads rows ``s * stride .. s * stride + T - 1``; the target
    of the position that reads row ``r`` is row ``r + 1`` of ``y``.  The last
    row is nobody's input (its next row does not exist), so there are
    ``N - 1`` real positions a pass; the last sequence is padded with zero
    rows that weigh 0.  With ``stride < T`` consecutive sequences overlap
    and a row is read by ``T / stride`` of them."""
    X, y = jnp.asarray(X), jnp.asarray(y)
    n_in = X.shape[0] - 1
    if n_in < 1:
        raise ValueError(f"Need at least 2 rows, got {X.shape[0]}")
    s = num_sequences(X.shape[0], context, stride)
    idx = jnp.arange(s)[:, None] * stride + jnp.arange(context)[None, :]
    real = idx < n_in
    at = jnp.minimum(idx, n_in - 1)
    inputs = jnp.where(real[..., None], X[at], 0.0)
    targets = jnp.where(real[..., None], y[at + 1], 0.0)
    return inputs, targets, real.astype(jnp.float32)


def sequences_to_rows(out: jnp.ndarray, n_rows: int, context: int,
                      stride: int) -> jnp.ndarray:
    """``(S, T, F_out)`` outputs over :func:`make_sequences`' inputs → the
    forecasts of rows ``1 .. n_rows - 1``, each exactly once: the first
    sequence gives all its positions, every later one its last ``stride``
    (the positions with the most context, ``T - stride`` rows at least)."""
    if stride > context:
        raise ValueError("stride may not exceed context: rows would go unread")
    head = out[0]
    tail = out[1:, context - stride:].reshape(-1, out.shape[-1])
    return jnp.concatenate([head, tail])[: n_rows - 1]
