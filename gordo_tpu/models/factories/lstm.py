"""LSTM autoencoder/forecast factories as Flax modules.

Reference equivalent:
``gordo_components/model/factories/lstm_autoencoder.py`` — ``lstm_model`` /
``lstm_symmetric`` / ``lstm_hourglass`` over ``(lookback, n_features)``
windows.

TPU-native design: recurrence is expressed with ``flax.linen.RNN`` (which
lowers to ``lax.scan`` — compiler-friendly sequential control flow, no
Python loops in the traced program).  The window axis is short (order 10^2)
so scan latency is fine; throughput comes from batching across windows *and*
across models in the fleet engine.  The head reads the final timestep state
and projects to the output features, matching the reference's 2D
``(batch, n_features)`` output contract.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from gordo_tpu import telemetry
from gordo_tpu.models.factories.feedforward import (
    _broadcast_funcs,
    resolve_activation,
    resolve_compute_dtype,
)
from gordo_tpu.models.factories.utils import hourglass_calc_dims
from gordo_tpu.registry import register_model_builder


class _GateParams(nn.Module):
    """One gate's Dense parameters, never applied directly.

    Mirrors ``flax.linen.recurrent.DenseParams`` so the param tree under an
    ``OptimizedLSTMCell_{k}`` scope is bit-compatible with artifacts trained
    on the flax cell (same names, shapes, initializers, and path-derived
    init RNG)."""

    features: int
    use_bias: bool
    kernel_init: Any

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param(
            "kernel", self.kernel_init, (in_features, self.features),
            jnp.float32,
        )
        bias = (
            self.param(
                "bias", nn.initializers.zeros_init(), (self.features,),
                jnp.float32,
            )
            if self.use_bias
            else None
        )
        return kernel, bias


GATES = "ifgo"  # flax's gate order inside a fused kernel


class _LSTMCellParams(nn.Module):
    """Owns one LSTM layer's params under the exact OptimizedLSTMCell tree:
    one ``_GateParams`` per gate, ``ii if ig io`` (kernels) and ``hi hf hg
    ho`` (kernels and biases)."""

    features: int

    @nn.compact
    def __call__(self, in_features: int):
        cell = {}
        for c in GATES:
            k, _ = _GateParams(
                self.features, False, nn.initializers.lecun_normal(),
                name=f"i{c}",
            )(in_features)
            cell[f"i{c}"] = {"kernel": k}
        for c in GATES:
            k, b = _GateParams(
                self.features, True, nn.initializers.orthogonal(),
                name=f"h{c}",
            )(self.features)
            cell[f"h{c}"] = {"kernel": k, "bias": b}
        return cell


_LSTM_BACKWARD = telemetry.counter(
    "gordo_lstm_backward_total",
    "Backward passes of an LSTM layer traced, by the rule that gives them: "
    "written (the hand-written rule of _fused_lstm_layer)",
    labels=("rule",),
)


def _forward(x, kernel_i, kernel_h, bias, cd, save: bool):
    """One layer's input projection and its scan over time.  Returns ``hs``
    time-major ``(T, B, H)`` float32 and, with ``save``, what the backward
    needs of every time step, time-major too: the four gates BEFORE their
    non-linearities, the one ``(T, B, 4H)`` array ``z`` in ``cd`` they are
    computed as, and the cell state each step STARTED from, ``(T, B, H)``
    float32 (so the backward reads ``c_{t-1}`` where it stands and
    recomputes ``c_t``).  Not the gates after their non-linearities: a
    sigmoid near 1 rounded to bfloat16 leaves ``1 - o`` a quarter off, and
    the output gate of a trained last layer sits there.

    Step math mirrors ``OptimizedLSTMCell`` exactly (same concat order,
    same dtype promotion: gates in ``cd``, carries promoted to float32 by
    the elementwise ops)."""
    xp = x.astype(cd) @ kernel_i.astype(cd)         # (B, T, 4H), one GEMM
    kernel_h = kernel_h.astype(cd)
    bias = bias.astype(cd)
    batch, features = x.shape[0], kernel_h.shape[0]
    c0 = jnp.zeros((batch, features), jnp.float32)  # flax carries are f32
    h0 = jnp.zeros((batch, features), jnp.float32)

    def step(carry, xp_t):
        c_in, h = carry
        z = (h.astype(cd) @ kernel_h + bias) + xp_t  # dense_h + dense_i
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i, f, o = nn.sigmoid(i), nn.sigmoid(f), nn.sigmoid(o)
        g = nn.tanh(g)
        c = f * c_in + i * g       # promotes to f32 against the f32 carry
        h = o * jnp.tanh(c)
        return (c, h), ((h, z, c_in) if save else h)

    # plain scan, no unroll: XLA does not fuse across the recurrence.  By
    # the count of the program compiled for a v5e (the tool is
    # scripts/fleet_program_ops.py) a time step is 4-9 operations here and
    # 8-11 in the written backward, where autodiff's were 7-12 and 11-14
    # (ISSUE 30); unrolling was counted on autodiff's (ISSUE 27: unroll=3
    # is 10-23 operations a time step for 13, unroll=12 twice the compile)
    _, out = jax.lax.scan(step, (c0, h0), jnp.swapaxes(xp, 0, 1))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_lstm_layer(
    x: jnp.ndarray,
    kernel_i: jnp.ndarray,
    kernel_h: jnp.ndarray,
    bias: jnp.ndarray,
    features: int,
    compute_dtype,
) -> jnp.ndarray:
    """LSTM layer with the input projection hoisted OUT of the recurrence.

    ``nn.RNN(OptimizedLSTMCell)`` recomputes ``x_t @ W_i`` inside every scan
    step: T small ``(B, F) @ (F, 4H)`` matmuls that can't fill the MXU.
    Here all T input projections run as ONE ``(B·T, F) @ (F, 4H)`` GEMM
    before the scan (under the fleet vmap: a batched GEMM over machines —
    the MXU-shaped form), and each scan step only pays the unavoidable
    recurrent ``(B, H) @ (H, 4H)``.  Results match the flax cell.

    Its backward is written out (:func:`_fused_lstm_layer_bwd`) on the same
    principle: the reverse scan keeps only what is sequential, and every
    weight gradient is one GEMM over all time steps after it.
    """
    hs = _forward(x, kernel_i, kernel_h, bias, compute_dtype, save=False)
    return jnp.swapaxes(hs, 0, 1)                   # (B, T, H)


def _fused_lstm_layer_fwd(x, kernel_i, kernel_h, bias, features, compute_dtype):
    """The forward as above, operation for operation, that also keeps three
    stacks for the backward: ``z``, the cell states and ``hs`` itself."""
    hs, z, c_in = _forward(x, kernel_i, kernel_h, bias, compute_dtype, save=True)
    return jnp.swapaxes(hs, 0, 1), (x, kernel_i, kernel_h, bias, (hs, z, c_in))


def _fused_lstm_layer_bwd(features, compute_dtype, residuals, d_out):
    """What autodiff through the scan would give, arranged for the device.

    Autodiff stacks every gate's value and derivative, the cell state,
    its tanh and the cast of ``h`` apart (zero-filled before the loop,
    copied after it) and forms the cotangent of ``kernel_h`` and of
    ``bias`` at every time step into an accumulator carried through the
    reverse loop.  Here the reverse scan carries ``(dh, dc)`` alone,
    recomputes a time step's gates and ``tanh(c_t)`` in float32 from the
    saved ``z_t`` and ``c_{t-1}``, turns them into ``dz_t`` and pays the one
    product that is truly sequential, ``dh_{t-1} = dz_t @ kernel_hᵀ``; the
    weight, input and bias cotangents are three GEMMs and a sum over the
    whole ``dz`` stack (under the fleet vmap batched over machines,
    contracting ``T·B`` rows at once).  Matmul operands are ``cd`` as in
    the forward; every accumulation is float32.
    """
    cd = compute_dtype
    f32 = jnp.float32
    x, kernel_i, kernel_h, bias, stacks = residuals
    hs, z, c_in = stacks
    # runs where a gradient through the layer is traced
    _LSTM_BACKWARD.inc(1.0, "written")
    telemetry.add_to_span(lstm_backward_traces=1, lstm_saved_stacks=len(stacks))
    kh = kernel_h.astype(cd)
    into_h = (((1,), (1,)), ((), ()))               # (B, 4H) · (H, 4H)ᵀ

    def step(carry, saved):
        dh, dc = carry
        z_t, c_in_t, d_out_t = saved
        i, f, g, o = jnp.split(z_t.astype(f32), 4, axis=-1)
        i, f, o = nn.sigmoid(i), nn.sigmoid(f), nn.sigmoid(o)
        g = nn.tanh(g)
        tanh_c = jnp.tanh(f * c_in_t + i * g)
        dh = dh + d_out_t
        dc = dc + dh * o * (1.0 - tanh_c * tanh_c)
        dz = jnp.concatenate([
            dc * g * i * (1.0 - i),
            dc * c_in_t * f * (1.0 - f),
            dc * i * (1.0 - g * g),
            dh * tanh_c * o * (1.0 - o),
        ], axis=-1).astype(cd)
        dh = jax.lax.dot_general(dz, kh, into_h, preferred_element_type=f32)
        return (dh, dc * f), dz

    zero = jnp.zeros(hs.shape[1:], f32)
    _, dz = jax.lax.scan(
        step, (zero, zero),
        (z, c_in, jnp.swapaxes(d_out, 0, 1)), reverse=True)

    over_steps = (((0, 1), (0, 1)), ((), ()))       # contract T and B at once
    # h_{t-1} is hs shifted by a step and h_{-1} = 0: leave that term out
    d_kernel_h = jax.lax.dot_general(
        hs[:-1].astype(cd), dz[1:], over_steps, preferred_element_type=f32)
    d_kernel_i = jax.lax.dot_general(
        x.astype(cd), dz, (((1, 0), (0, 1)), ((), ())),
        preferred_element_type=f32)
    dx = jax.lax.dot_general(
        dz, kernel_i.astype(cd), (((2,), (1,)), ((), ())),
        preferred_element_type=f32)                 # (T, B, F)
    d_bias = jnp.sum(dz, axis=(0, 1), dtype=f32)
    return (
        jnp.swapaxes(dx, 0, 1).astype(x.dtype),
        d_kernel_i.astype(kernel_i.dtype),
        d_kernel_h.astype(kernel_h.dtype),
        d_bias.astype(bias.dtype),
    )


_fused_lstm_layer.defvjp(_fused_lstm_layer_fwd, _fused_lstm_layer_bwd)


class LSTMAutoEncoderModule(nn.Module):
    """Stacked LSTM layers over the window, final-step dense head.

    Recurrent compute runs in ``compute_dtype`` (bfloat16 by default —
    MXU-native, same mixed-precision scheme as the feedforward modules)
    with float32 params and a float32 output head.  The recurrence is the
    fused scan of :func:`_fused_lstm_layer`; its param tree is identical to
    the ``nn.RNN(OptimizedLSTMCell)`` stack it replaced, so pre-existing
    artifacts load unchanged.
    """

    dims: Tuple[int, ...]
    funcs: Tuple[Union[str], ...]
    out_dim: int
    out_func: str = "linear"
    #: class default is float32 — NOT bf16 — so artifacts pickled before
    #: this field existed unpickle to exactly the numerics they trained and
    #: calibrated thresholds with; factories always pass a resolved value
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # x: (batch, lookback, n_features).  Declares the public tree,
        # whose shapes need the feature count alone, and runs the one
        # forward there is on it.
        in_features = x.shape[-1]
        params = {}
        for i, d in enumerate(self.dims):
            name = f"OptimizedLSTMCell_{i}"
            params[name] = _LSTMCellParams(int(d), name=name)(in_features)
            in_features = int(d)
        # nn.Dense's own parameters (same names, initializers and RNG path)
        kernel, bias = _GateParams(
            self.out_dim, True, nn.initializers.lecun_normal(), name="out"
        )(in_features)
        params["out"] = {"kernel": kernel, "bias": bias}
        return self.apply_packed(self.pack(params), x)

    @nn.nowrap
    def pack(self, params):
        """The public tree in the layout the layers compute in: per layer
        ``kernel_i (F, 4H)``, ``kernel_h (H, 4H)`` and ``bias (4H,)``, gates
        concatenated in flax's ``i, f, g, o`` order; the head as it is.
        20 leaves for 74 at six layers.  A fit that carries this tree
        (``train.fit.make_fit_fn``) concatenates once, not every step."""
        packed = {}
        for i in range(len(self.dims)):
            cell = params[f"OptimizedLSTMCell_{i}"]
            packed[f"OptimizedLSTMCell_{i}"] = {
                "kernel_i": jnp.concatenate(
                    [cell[f"i{c}"]["kernel"] for c in GATES], axis=-1),
                "kernel_h": jnp.concatenate(
                    [cell[f"h{c}"]["kernel"] for c in GATES], axis=-1),
                "bias": jnp.concatenate(
                    [cell[f"h{c}"]["bias"] for c in GATES], axis=-1),
            }
        packed["out"] = dict(params["out"])
        return packed

    @nn.nowrap
    def unpack(self, packed):
        """Inverse of :meth:`pack`: the ``OptimizedLSTMCell_{k}/{ii..ho}``
        tree every artifact stores."""
        params = {}
        for i in range(len(self.dims)):
            layer = packed[f"OptimizedLSTMCell_{i}"]
            cell = {}
            for c, k in zip(GATES, jnp.split(layer["kernel_i"], 4, axis=-1)):
                cell[f"i{c}"] = {"kernel": k}
            for c, k, b in zip(GATES,
                               jnp.split(layer["kernel_h"], 4, axis=-1),
                               jnp.split(layer["bias"], 4, axis=-1)):
                cell[f"h{c}"] = {"kernel": k, "bias": b}
            params[f"OptimizedLSTMCell_{i}"] = cell
        params["out"] = dict(packed["out"])
        return params

    @nn.nowrap
    def apply_packed(self, packed, x: jnp.ndarray) -> jnp.ndarray:
        """The forward pass as a pure function of a :meth:`pack` tree."""
        squeeze = x.ndim == 2
        if squeeze:  # single window
            x = x[None]
        x = x.astype(self.compute_dtype)
        for i, (d, f) in enumerate(zip(self.dims, self.funcs)):
            layer = packed[f"OptimizedLSTMCell_{i}"]
            x = _fused_lstm_layer(
                x, layer["kernel_i"], layer["kernel_h"], layer["bias"],
                int(d), self.compute_dtype,
            )
            x = resolve_activation(f)(x)
        # nn.Dense(out_dim, dtype=float32), written out
        head = packed["out"]
        out = jax.lax.dot_general(
            x[:, -1, :].astype(jnp.float32),
            head["kernel"].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
        ) + head["bias"].astype(jnp.float32)
        out = resolve_activation(self.out_func)(out)
        return out[0] if squeeze else out


@register_model_builder(type="LSTMAutoEncoder")
def lstm_model(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 1,
    encoding_dim: Sequence[int] = (256, 128, 64),
    encoding_func: Sequence[str] = None,
    decoding_dim: Sequence[int] = (64, 128, 256),
    decoding_func: Sequence[str] = None,
    out_func: str = "linear",
    compute_dtype: str = "auto",
    **_ignored,
) -> nn.Module:
    """Encoder/decoder LSTM stack (reference: ``lstm_autoencoder.lstm_model``).

    ``lookback_window`` is consumed by the estimator for windowing; the module
    itself handles any window length (scan over time axis).
    ``compute_dtype="float32"`` opts out of mixed precision.
    """
    n_features_out = n_features_out or n_features
    enc = tuple(int(d) for d in encoding_dim)
    dec = tuple(int(d) for d in decoding_dim)
    funcs = _broadcast_funcs(encoding_func, len(enc)) + _broadcast_funcs(
        decoding_func, len(dec)
    )
    return LSTMAutoEncoderModule(
        dims=enc + dec,
        funcs=funcs,
        out_dim=int(n_features_out),
        out_func=out_func,
        compute_dtype=resolve_compute_dtype(compute_dtype),
    )


@register_model_builder(type="LSTMAutoEncoder")
def lstm_symmetric(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 1,
    dims: Sequence[int] = (256, 128, 64),
    funcs: Sequence[str] = None,
    **kwargs,
) -> nn.Module:
    """Symmetric LSTM AE (reference: ``lstm_symmetric``)."""
    if not dims:
        raise ValueError("dims must be non-empty")
    dims = tuple(int(d) for d in dims)
    funcs = _broadcast_funcs(funcs, len(dims))
    return lstm_model(
        n_features,
        n_features_out,
        lookback_window=lookback_window,
        encoding_dim=dims,
        encoding_func=funcs,
        decoding_dim=dims[::-1],
        decoding_func=funcs[::-1],
        **kwargs,
    )


@register_model_builder(type="LSTMAutoEncoder")
def lstm_hourglass(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 1,
    encoding_layers: int = 3,
    compression_factor: float = 0.5,
    func: str = "tanh",
    **kwargs,
) -> nn.Module:
    """Tapered LSTM AE (reference: ``lstm_autoencoder.lstm_hourglass``)."""
    dims = hourglass_calc_dims(compression_factor, encoding_layers, n_features)
    return lstm_symmetric(
        n_features,
        n_features_out,
        lookback_window=lookback_window,
        dims=dims,
        funcs=[func] * len(dims),
        **kwargs,
    )
