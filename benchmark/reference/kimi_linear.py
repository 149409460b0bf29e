"""Plain reference of the ``kimi_linear`` next-row forecaster and of its fit.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision, written from the layer equations of Kimi-Linear-48B-A3B
(arXiv:2510.26692; ``model_type`` ``kimi_linear``) and not from the
program's factory.  It imports nothing of the program and takes nothing the
program made: its own seeded weights (the parameters' names, shapes and
initial distributions are the artifact's format, which stacks the parameters
of one kind of part over the layers that have it, with the per-parameter
keys folded from the parameter's ordinal the way ``flax.linen`` does for a
module's own parameters), its own data copy (``reference/data.py``), its own
sequences, folds, held-out forecasts and thresholds.  It works on a model as
a plain list of layers (:class:`Model`), and its fit writes the chain rule
over the layers out: one small compiled program per kind of layer, where the
whole model's step in one program took minutes to compile and a compile
cache's room to keep.

Every block is pre-norm residual, ``h += Mixer(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``, ``rms_norm_eps`` 1e-5, layers numbered from 1.

- KDA as the **time-step recurrence** (a ``lax.scan`` over t, one rank-one
  update a step): ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
  beta_t k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d_k)``, ``alpha_t = exp(-exp(A_log)
  softplus(W_f x_t + dt_bias))``, ``q, k, v`` through a width-4 causal
  depthwise convolution and SiLU, ``q, k`` L2-normalised per head, output
  ``W_o [RMSNorm_head(o_t) * sigmoid(W_g x_t)]``.  The scan runs in blocks of
  32 steps under ``jax.checkpoint`` so that its backward pass fits a chip;
  that changes what is kept, not what is computed.
- MLA as a full masked softmax: ``q = W_q x`` (heads x (128 + 64)), ``[c,
  k_r] = W_kv_a x``, ``c <- RMSNorm(c)``, ``[k_n, v] = W_kv_b c``, ``k = [k_n,
  k_r]`` with ``k_r`` shared by all heads and NOT rotated (``mla_use_nope``).
- Experts as a dense loop (``lax.scan``) over the held experts with a mask: ``s =
  sigmoid(W_r h)``, the 8 largest, ``w_e = 2.446 s_e / sum_selected s``, ``y =
  sum_{e selected and held} w_e E_e(h) + E_shared(h)``.

Departures from the source, each also in the configuration's file:
``vocabulary`` (no token embedding or head: ``h_0 = X W_in``, ``Y =
RMSNorm(h_L) W_out + b``); three sizes the config does not give
(``assumed``: the low-rank width of ``W_f`` and ``W_g`` is the head
dimension, ``A_log`` is per head, ``dt_bias`` per head and channel); the
router's selection bias is held at 0 and not stored; no auxiliary loss.

``quantize`` is the control's hook: a function put on both operands of
every matmul that the configuration computes in bfloat16 (the router stays
float32 in the program, so it stays unrounded here).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lstm_ae import (  # the benchmark's own, accepted pieces
    ADAM_B1, ADAM_B2, ADAM_EPS, _EPS, _path_key, bfloat16, expanding_folds,  # noqa: F401
    float8, smoothed_max,
)

HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 32
#: the published widths (config.json of the source) and this repo's cut
PUBLISHED = dict(
    num_layers=5, hidden_size=2304, num_heads=32, kda_head_dim=128,
    kda_gate_rank=128, short_conv_kernel_size=4, full_attn_every=4,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    intermediate_size=9216, first_k_dense_replace=1, moe_intermediate_size=1024,
    num_experts=256, num_experts_per_token=8, num_shared_experts=1,
    routed_scaling_factor=2.446, experts_held_from=0, experts_held=8,
    rms_norm_eps=1e-5,
)


def shape_of(model: Dict[str, Any], n_features: int, n_out: int) -> Tuple:
    """The architecture as a hashable tuple of ``(key, value)``: the
    published widths, overridden by what the configuration's ``model`` says."""
    if model["kind"] != "kimi_linear":
        raise ValueError(f"no reference for kind {model['kind']!r}")
    spec = {**PUBLISHED, **{k: model[k] for k in PUBLISHED if k in model}}
    spec.update(n_features=int(n_features), n_out=int(n_out))
    return tuple(sorted(spec.items()))


def _mixer(a: Dict[str, Any], layer: int) -> str:
    return "mla" if layer % a["full_attn_every"] == 0 else "kda"


def _ffn(a: Dict[str, Any], layer: int) -> str:
    return "dense" if layer <= a["first_k_dense_replace"] else "moe"


# ---------------------------------------------------------------------------
# initial weights from the seed
# ---------------------------------------------------------------------------

KINDS = ("kda", "mla", "dense", "moe")


def layers_of(a: Dict[str, Any], kind: str) -> List[int]:
    """The layers (from 1) whose mixer or feed-forward is ``kind``."""
    return [layer for layer in range(1, a["num_layers"] + 1)
            if kind in (_mixer(a, layer), _ffn(a, layer))]


def parameter_list(a: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every parameter's name, shape and initial distribution, in the order
    the artifact's module creates them (the ordinal seeds the draw).  The
    artifact stacks the parameters of one kind of part over the layers that
    have it (the leading axis); a kind no layer has is left out."""
    d, h, dk, r = a["hidden_size"], a["num_heads"], a["kda_head_dim"], a["kda_gate_rank"]
    conv = a["short_conv_kernel_size"]
    dn, dr, dv, rank = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                        a["v_head_dim"], a["kv_lora_rank"])
    w, e = a["moe_intermediate_size"], a["experts_held"]
    ws, wide = w * a["num_shared_experts"], a["intermediate_size"]
    by_kind = {
        "kda": [(f"kda_w{n}", (d, h * dk), "normal") for n in "qkv"]
        + [(f"kda_conv_{n}", (conv, h * dk), "normal") for n in "qkv"]
        + [
            ("kda_wf_down", (d, r), "normal"),
            ("kda_wf_up", (r, h * dk), "normal"),
            ("kda_a_log", (h,), "a_log"),
            ("kda_dt_bias", (h * dk,), "dt_bias"),
            ("kda_wbeta", (d, h), "normal"),
            ("kda_wg_down", (d, r), "normal"),
            ("kda_wg_up", (r, h * dk), "normal"),
            ("kda_out_norm", (dk,), "ones"),
            ("kda_wo", (h * dk, d), "normal"),
        ],
        "mla": [
            ("mla_wq", (d, h * (dn + dr)), "normal"),
            ("mla_wkv_a", (d, rank + dr), "normal"),
            ("mla_kv_norm", (rank,), "ones"),
            ("mla_wkv_b", (rank, h * (dn + dv)), "normal"),
            ("mla_wo", (h * dv, d), "normal"),
        ],
        "dense": [("dense_wg", (d, wide), "normal"), ("dense_wu", (d, wide), "normal"),
                  ("dense_wd", (wide, d), "normal")],
        "moe": [
            ("moe_router", (d, a["num_experts"]), "normal"),
            ("moe_shared_wg", (d, ws), "normal"),
            ("moe_shared_wu", (d, ws), "normal"),
            ("moe_shared_wd", (ws, d), "normal"),
            ("moe_wg", (e, d, w), "normal"),
            ("moe_wu", (e, d, w), "normal"),
            ("moe_wd", (e, w, d), "normal"),
        ],
    }
    out: List[Tuple[str, Tuple[int, ...], str]] = [
        ("in_proj", (a["n_features"], d), "normal"),
        ("mixer_norm", (a["num_layers"], d), "ones"),
        ("ffn_norm", (a["num_layers"], d), "ones"),
    ]
    for kind in KINDS:
        n = len(layers_of(a, kind))
        if n:
            out += [(name, (n,) + dims, how) for name, dims, how in by_kind[kind]]
    out += [("out_norm", (d,), "ones"), ("out_proj", (d, a["n_out"]), "normal"),
            ("out_bias", (a["n_out"],), "zeros")]
    return out


def layer_of(a: Dict[str, Any], params: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s own parameters out of the artifact's stacks: its two
    norms and the parts of its mixer and its feed-forward."""
    own = {"mixer_norm": params["mixer_norm"][layer - 1],
           "ffn_norm": params["ffn_norm"][layer - 1]}
    for kind in (_mixer(a, layer), _ffn(a, layer)):
        slot = layers_of(a, kind).index(layer)
        own.update({name: value[slot] for name, value in params.items()
                    if name.startswith(kind + "_")})
    return own


def _draw(key, shape, how: str):
    """``normal``: N(0, 1 / fan-in), the fan-in being the second-last axis
    (a convolution's: its width).  ``a_log``: log of uniform(1, 16).
    ``dt_bias``: the inverse softplus of a step drawn log-uniformly from
    [1e-3, 1e-1] (both as the linear-attention family initialises them)."""
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    if how == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if how == "normal":
        return jax.random.normal(key, shape, jnp.float32) * (shape[-2] ** -0.5)
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@functools.lru_cache(maxsize=None)
def _init_fn(shape: Tuple):
    a = dict(shape)

    def init(key):
        init_key, fit_key = jax.random.split(key)
        params = {
            name: _draw(_path_key(init_key, i + 1), dims, how)
            for i, (name, dims, how) in enumerate(parameter_list(a))
        }
        return params, fit_key

    # thirty-odd random draws, run twice a machine: compiled at the least
    # effort, a third of the time and half the room in a compile cache
    return jax.jit(init, compiler_options={"exec_time_optimization_effort": -1.0})


def init_params(seed: int, shape: Tuple):
    """``(params, fit_key)`` as a fit from ``seed`` starts."""
    return _init_fn(shape)(jax.random.PRNGKey(seed))



# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _matmul(x, w, quantize):
    if quantize is not None:
        x, w = quantize(x), quantize(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _einsum(expr, x, y, quantize):
    if quantize is not None:
        x, y = quantize(x), quantize(y)
    return jnp.einsum(expr, x, y, precision=HIGHEST)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(x, wg, wu, wd, quantize):
    return _matmul(jax.nn.silu(_matmul(x, wg, quantize)) * _matmul(x, wu, quantize),
                   wd, quantize)


def _causal_conv(x, w):
    """``y_t = sum_j w[j] x_{t - K + 1 + j}``, zeros before the sequence."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.concatenate([jnp.zeros_like(x[:, : k - 1]), x], axis=1)
    return sum(padded[:, j: j + t] * w[j] for j in range(k))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, alpha, beta, quantize=None):
    """The delta rule one step at a time.  ``q, k`` (B, T, H, dk), ``v``
    (B, T, H, dv), ``alpha`` (B, T, H, dk), ``beta`` (B, T, H); ``q``
    arrives scaled.  Returns ``o`` (B, T, H, dv)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]

    if quantize is None:
        quantize = lambda x: x  # noqa: E731

    def step(S, item):
        # products of single vectors with the state, written as multiply and
        # sum: a matrix unit has nothing to do for one row at a time
        q_t, k_t, v_t, a_t, b_t = item
        S = S * a_t[..., None]
        Sq, kq = quantize(S), quantize(k_t)
        seen = jnp.sum(kq[..., None] * Sq, axis=-2)
        write = quantize(k_t * b_t[..., None])[..., None] * quantize(v_t - seen)[..., None, :]
        S = S + write
        return S, jnp.sum(quantize(S) * quantize(q_t)[..., None], axis=-2)

    @jax.checkpoint
    def block(S, items):
        return jax.lax.scan(step, S, items)

    pad = -t % SCAN_BLOCK
    items = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)]
    items = [jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) for x in items]
    items = [x.reshape((-1, SCAN_BLOCK) + x.shape[1:]) for x in items]
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), tuple(items))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:t], 0, 1)


def _kda(a, p, x, quantize):
    h, dk = a["num_heads"], a["kda_head_dim"]
    b, t, _ = x.shape
    heads = lambda z: z.reshape(b, t, h, dk)  # noqa: E731
    q, k, v = (
        heads(jax.nn.silu(_causal_conv(_matmul(x, p[f"kda_w{n}"], quantize),
                                       p[f"kda_conv_{n}"])))
        for n in "qkv"
    )
    q, k = _unit(q) * dk ** -0.5, _unit(k)
    f = _matmul(_matmul(x, p["kda_wf_down"], quantize), p["kda_wf_up"], quantize)
    g = -jnp.exp(p["kda_a_log"])[:, None] * heads(jax.nn.softplus(f + p["kda_dt_bias"]))
    beta = jax.nn.sigmoid(_matmul(x, p["kda_wbeta"], quantize))
    o = kda_recurrence(q, k, v, jnp.exp(g), beta, quantize)
    gate = jax.nn.sigmoid(
        _matmul(_matmul(x, p["kda_wg_down"], quantize), p["kda_wg_up"], quantize))
    o = _rms_norm(o, p["kda_out_norm"], a["rms_norm_eps"]).reshape(b, t, h * dk) * gate
    return _matmul(o, p["kda_wo"], quantize)


def _mla(a, p, x, quantize):
    h, dn, dr, dv, rank = (a["num_heads"], a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                           a["v_head_dim"], a["kv_lora_rank"])
    b, t, _ = x.shape
    q = _matmul(x, p["mla_wq"], quantize).reshape(b, t, h, dn + dr)
    kv_a = _matmul(x, p["mla_wkv_a"], quantize)
    c = _rms_norm(kv_a[..., :rank], p["mla_kv_norm"], a["rms_norm_eps"])
    k_r = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, t, h, dr))   # no rotation
    kv = _matmul(c, p["mla_wkv_b"], quantize).reshape(b, t, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    scores = _einsum("bthc,bshc->bhts", q, k, quantize) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _einsum("bhts,bshv->bthv", probs, kv[..., dn:], quantize)
    return _matmul(o.reshape(b, t, h * dv), p["mla_wo"], quantize)


def routing(a, router, x):
    """``(experts, weights)`` (..., 8): the selected experts of every
    position and ``2.446 s_e / sum_selected s``.  Float32, never rounded."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    top, experts = jax.lax.top_k(scores, a["num_experts_per_token"])
    return experts, a["routed_scaling_factor"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def _experts(a, p, x, quantize, held: Optional[Tuple[int, int]] = None):
    """The shared expert plus the routed sum over the held experts.  ``held``
    (first, count) defaults to the architecture's; the weights ``moe_w*``
    are those of the held experts, in order."""
    first, count = held or (a["experts_held_from"], a["experts_held"])
    experts, weights = routing(a, p["moe_router"], x)
    y = _swiglu(x, p["moe_shared_wg"], p["moe_shared_wu"], p["moe_shared_wd"], quantize)

    def add_expert(y, expert):
        e, wg, wu, wd = expert
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return y + w_e[..., None] * _swiglu(x, wg, wu, wd, quantize), None

    # every held expert on every position, its weight 0 where it was not
    # selected: a loop over the experts, compiled once
    y, _ = jax.lax.scan(
        add_expert, y, (jnp.arange(count), p["moe_wg"], p["moe_wu"], p["moe_wd"]))
    return y


def kinds_of(a: Dict[str, Any], layer: int) -> Tuple[str, str]:
    """``(mixer, feed-forward)`` of a layer."""
    return _mixer(a, layer), _ffn(a, layer)


def part(a: Dict[str, Any], kind: str, p: Dict[str, Any], h, quantize):
    """One pre-norm residual part of a layer on the stream ``h`` (B, T, D):
    ``h + Mixer(RMSNorm(h))`` for ``kind`` ``kda`` or ``mla``, ``h +
    FFN(RMSNorm(h))`` for ``dense`` or ``moe``; ``p`` are the layer's own
    parameters (:func:`layer_of`), of which the part reads its own."""
    eps = a["rms_norm_eps"]
    if kind in ("kda", "mla"):
        z = _rms_norm(h, p["mixer_norm"], eps)
        return h + (_kda if kind == "kda" else _mla)(a, p, z, quantize)
    z = _rms_norm(h, p["ffn_norm"], eps)
    if kind == "dense":
        return h + _swiglu(z, p["dense_wg"], p["dense_wu"], p["dense_wd"], quantize)
    return h + _experts(a, p, z, quantize)


def own(kind: str, p: Dict[str, Any]) -> Dict[str, Any]:
    """The parameters of a layer that its part of ``kind`` reads."""
    norm = "mixer_norm" if kind in ("kda", "mla") else "ffn_norm"
    return {name: value for name, value in p.items()
            if name == norm or name.startswith(kind + "_")}


def block(a: Dict[str, Any], kinds: Tuple[str, str], p: Dict[str, Any], h, quantize):
    """One layer: its mixer's part, then its feed-forward's."""
    for kind in kinds:
        h = part(a, kind, p, h, quantize)
    return h


def embed(in_proj, x, quantize):
    return _matmul(x.astype(jnp.float32), in_proj, quantize)


def head(a: Dict[str, Any], io: Dict[str, Any], h, quantize):
    return _matmul(_rms_norm(h, io["out_norm"], a["rms_norm_eps"]), io["out_proj"],
                   quantize) + io["out_bias"]


def forward(params: Dict[str, Any], x: jnp.ndarray, shape: Tuple,
            quantize: Optional[Callable] = None) -> jnp.ndarray:
    """``x`` (B, T, F) scaled rows → (B, T, n_out): position t's output is
    the forecast of row t + 1.  ``params`` in the artifact's layout."""
    a = dict(shape)
    h = embed(params["in_proj"], x, quantize)
    for layer in range(1, a["num_layers"] + 1):
        h = block(a, kinds_of(a, layer), layer_of(a, params, layer), h, quantize)
    return head(a, params, h, quantize)


# ---------------------------------------------------------------------------
# sequences
# ---------------------------------------------------------------------------

def n_sequences(n_rows: int, context: int, stride: int) -> int:
    return -(-max(n_rows - 1 - context, 0) // stride) + 1


def sequences(scaled: np.ndarray, raw: np.ndarray, context: int, stride: int):
    """Rows → ``(inputs, targets, marks)``: sequence s reads the scaled rows
    ``s * stride .. s * stride + context - 1``; a position's target is the
    next RAW row; the last row is read by nobody; slots past the series are
    zero rows that ``marks`` leaves out."""
    n_in = scaled.shape[0] - 1
    s = n_sequences(scaled.shape[0], context, stride)
    x = np.zeros((s, context, scaled.shape[1]), np.float32)
    y = np.zeros((s, context, raw.shape[1]), np.float32)
    w = np.zeros((s, context), np.float32)
    for i in range(s):
        lo = i * stride
        n = max(min(context, n_in - lo), 0)
        x[i, :n], y[i, :n], w[i, :n] = scaled[lo: lo + n], raw[lo + 1: lo + 1 + n], 1.0
    return x, y, w


def rows_of(out: np.ndarray, n_rows: int, context: int, stride: int) -> np.ndarray:
    """Sequences' outputs → the forecasts of rows 1 .. n_rows - 1, each once:
    all of the first sequence, then the last ``stride`` positions of each."""
    tail = out[1:, context - stride:].reshape(-1, out.shape[-1])
    return np.concatenate([out[0], tail])[: n_rows - 1]


def minmax(train: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``rows`` in the min-max scale of ``train``'s columns."""
    lo, hi = train.min(axis=0), train.max(axis=0)
    scale = (1.0 / np.maximum(hi - lo, _EPS)).astype(np.float32)
    return rows * scale + (0.0 - lo * scale)


# ---------------------------------------------------------------------------
# the fit: Adam over shuffled minibatches of sequences, layer by layer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    """A model's parameters as a list of layers (:func:`layer_of`) and the
    four parameters around them; Adam's moments have the same form."""

    layers: List[Dict[str, Any]]
    around: Dict[str, Any]


AROUND = ("in_proj", "out_norm", "out_proj", "out_bias")


def split(a: Dict[str, Any], params: Dict[str, Any]) -> Model:
    """The artifact's stacked parameters as a :class:`Model`."""
    return Model([layer_of(a, params, layer) for layer in range(1, a["num_layers"] + 1)],
                 {name: params[name] for name in AROUND})


@functools.lru_cache(maxsize=None)
def _pieces(shape: Tuple, quantize: Optional[Callable]):
    """The compiled pieces a fit is made of.  The chain rule over the layers'
    parts (a mixer, a feed-forward) is written out: forward keeps the stream
    at every part's entrance, backward goes through the parts last to
    first, each part's own backward pass recomputing its forward, and a
    layer's parameters take their Adam step as soon as their gradient is
    there.  One small program per kind of part (KDA, MLA, dense, experts),
    not one of the whole model."""
    a = dict(shape)

    def mse(pred, y, w):
        per_position = jnp.mean((pred - y) ** 2, axis=-1)
        return jnp.sum(per_position * w) / jnp.maximum(jnp.sum(w), 1.0)

    @functools.partial(jax.jit, static_argnums=0)
    def forth(kind, p, h):
        return part(a, kind, p, h, quantize)

    @functools.partial(jax.jit, static_argnums=0)
    def back(kind, p, h, dh):
        _, vjp = jax.vjp(lambda p, h: part(a, kind, p, h, quantize), p, h)
        return vjp(dh)                                   # (dp, dh at the entrance)

    @jax.jit
    def first(in_proj, x):
        return embed(in_proj, x, quantize)

    @jax.jit
    def first_back(in_proj, x, dh):
        return jax.vjp(lambda w: embed(w, x, quantize), in_proj)[1](dh)[0]

    @jax.jit
    def last(around, h):
        return head(a, around, h, quantize)

    @jax.jit
    def last_back(around, h, y, w):
        out = {name: around[name] for name in AROUND[1:]}
        loss, (d_out, dh) = jax.value_and_grad(
            lambda out, h: mse(head(a, out, h, quantize), y, w), argnums=(0, 1))(out, h)
        return loss, d_out, dh

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, lr, c1, c2):
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        p = jax.tree.map(
            lambda a, mm, vv: a - lr * (mm / c1) / (jnp.sqrt(vv / c2) + ADAM_EPS), p, m, v)
        return p, m, v

    return dict(forth=forth, back=back, first=first, first_back=first_back,
                last=last, last_back=last_back, adam=adam)


def _step(pieces, a, model: Model, m: Model, v: Model, t: int, lr: float, x, y, w):
    """One optimiser step in place of ``model``, ``m``, ``v``; returns the
    minibatch's loss."""
    parts = [(i, kind) for i in range(a["num_layers"]) for kind in kinds_of(a, i + 1)]
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    h, entrances = pieces["first"](model.around["in_proj"], x), []
    for i, kind in parts:
        entrances.append(h)
        h = pieces["forth"](kind, own(kind, model.layers[i]), h)
    loss, d_around, dh = pieces["last_back"](model.around, h, y, w)
    del h
    grads: Dict[str, Any] = {}
    for i, kind in reversed(parts):
        dp, dh = pieces["back"](kind, own(kind, model.layers[i]), entrances.pop(), dh)
        grads.update(dp)
        if len(grads) == len(model.layers[i]):           # both parts of layer i are in
            model.layers[i], m.layers[i], v.layers[i] = pieces["adam"](
                model.layers[i], m.layers[i], v.layers[i], grads, lr, c1, c2)
            grads = {}
    d_around["in_proj"] = pieces["first_back"](model.around["in_proj"], x, dh)
    model.around, m.around, v.around = pieces["adam"](
        model.around, m.around, v.around, d_around, lr, c1, c2)
    return loss


def fit(rows: np.ndarray, model: Dict[str, Any], seed: int,
        quantize: Optional[Callable] = None,
        train_rows: Optional[int] = None,
        fault: Optional[str] = None) -> Dict[str, Any]:
    """One fit of one machine ``(rows, tags)`` from its raw rows (the first
    ``train_rows`` of them: a fold).  Inputs are the rows min-max-scaled by
    the rows trained on; the targets are raw.  Every epoch shuffles the
    sequences, padded to whole minibatches, anew; a minibatch smaller than
    ``batch_size`` (a fold with fewer sequences) is filled with slots that
    weigh nothing, so that one compiled step serves every fold.  Returns the
    per-epoch mean loss and the fitted ``model`` (a :class:`Model`, on the
    device).

    ``fault`` plants a fault of the timed path for the control
    (``benchmark/sequence_control.py``): ``"half_batch"`` leaves the second
    half of every minibatch out of the loss."""
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    rows = np.asarray(rows, np.float32)[: train_rows]
    context, stride = int(model["context"]), int(model["stride"])
    batch, epochs = int(model.get("batch_size", 8)), int(model.get("epochs", 1))
    lr = float(model.get("learning_rate", 1e-3))
    shape = shape_of(model, rows.shape[1], rows.shape[1])
    a = dict(shape)
    params0, fit_key = init_params(seed, shape)
    fitted = split(a, params0)
    del params0
    zeros = lambda: Model(  # noqa: E731
        [jax.tree.map(jnp.zeros_like, p) for p in fitted.layers],
        jax.tree.map(jnp.zeros_like, fitted.around))
    m, v = zeros(), zeros()
    x, y, w = sequences(minmax(rows, rows), rows, context, stride)
    n = x.shape[0]
    bs = min(batch, n)
    steps = -(-n // bs)
    blank = steps * bs                       # the slot every filler points at
    pad = lambda z: np.concatenate(  # noqa: E731
        [z, np.zeros((blank + 1 - n,) + z.shape[1:], z.dtype)])
    x, y, w = jnp.asarray(pad(x)), jnp.asarray(pad(y)), jnp.asarray(pad(w))
    kept = np.ones((batch, 1), np.float32)
    if fault == "half_batch":
        kept[batch // 2:] = 0.0

    pieces = _pieces(shape, quantize)
    history, t = [], 0
    with jax.default_matmul_precision("highest"):
        for key in jax.random.split(fit_key, epochs):
            perm = np.asarray(jax.random.permutation(key, steps * bs)).reshape(steps, bs)
            perm = np.concatenate(
                [perm, np.full((steps, batch - bs), blank, perm.dtype)], axis=1)
            losses, counts = [], []
            for idx in perm:
                t += 1
                wb = w[idx] * kept
                losses.append(_step(pieces, a, fitted, m, v, t, lr, x[idx], y[idx], wb))
                counts.append(jnp.sum(wb))
            total = sum(float(l) * float(c) for l, c in zip(losses, counts))
            history.append(total / max(sum(float(c) for c in counts), 1.0))
    return {"history": np.asarray(history, np.float64), "model": fitted, "shape": shape}


# ---------------------------------------------------------------------------
# how far two fits are apart, parameter by parameter
# ---------------------------------------------------------------------------

@jax.jit
def _norms(ours, theirs, start):
    norm = lambda z: jnp.sqrt(jnp.sum(z * z))  # noqa: E731
    return jax.tree.map(
        lambda r, o, i: jnp.stack([norm(r - i), norm(o - i), norm(o - r)]),
        ours, theirs, start)


def as_model(a: Dict[str, Any], params) -> Model:
    """A :class:`Model` from either a :class:`Model` or the artifact's
    stacked parameters (host or device arrays)."""
    return params if isinstance(params, Model) else split(a, params)


def distances(ours, theirs, seed: int, shape: Tuple) -> Dict[str, List]:
    """Per parameter of every layer (``l<layer>.<name>``) and of the four
    around them: the norm of our change from the seed's initial weights, of
    theirs, and of the difference between the two fits.  Taken on the
    device, one layer at a time: gigabytes of parameters leave a chip
    slowly, a few hundred numbers do not."""
    a = dict(shape)
    ours, theirs = as_model(a, ours), as_model(a, theirs)
    start = split(a, init_params(seed, shape)[0])
    names, rows = [], []
    groups = [(f"l{i + 1}.", ours.layers[i], theirs.layers[i], start.layers[i])
              for i in range(len(ours.layers))]
    groups.append(("", ours.around, theirs.around, start.around))
    for prefix, r, o, i in groups:
        out = _norms(r, jax.tree.map(jnp.asarray, dict(o)), i)
        for name in sorted(out):
            names.append(prefix + name)
            rows.append([float(z) for z in out[name]])
    moved_ours, moved_theirs, apart = (list(col) for col in zip(*rows))
    return {"names": names, "moved_ours": moved_ours, "moved_theirs": moved_theirs,
            "apart": apart}


def freeze(fitted: Model, seed: int, shape: Tuple, layer: int, name: str) -> Model:
    """``fitted`` with one parameter of one layer put back to its initial
    value: the fault "a leaf left unchanged", for the control."""
    start = split(dict(shape), init_params(seed, shape)[0])
    layers = [dict(p) for p in fitted.layers]
    layers[layer - 1][name] = start.layers[layer - 1][name]
    return Model(layers, dict(fitted.around))


# ---------------------------------------------------------------------------
# cross-validation: the thresholds of the anomaly detector
# ---------------------------------------------------------------------------

def predict(params, train: np.ndarray, rows: np.ndarray, model: Dict[str, Any],
            shape: Tuple, quantize: Optional[Callable] = None) -> np.ndarray:
    """Forecasts of rows 1.. of ``rows`` (scaled by ``train``'s columns);
    ``params`` a :class:`Model` or the artifact's stacked parameters.  The
    sequences go through the layers a minibatch at a time, the last one
    filled up with zero sequences that are dropped again (sequences do not
    see each other), so the pieces a fit compiled serve the forecast too."""
    a = dict(shape)
    context, stride = int(model["context"]), int(model["stride"])
    batch = int(model.get("batch_size", 8))
    fitted = as_model(a, params)
    x, _, _ = sequences(minmax(train, rows), rows, context, stride)
    n = x.shape[0]
    x = np.concatenate([x, np.zeros((-n % batch,) + x.shape[1:], x.dtype)])
    pieces = _pieces(shape, quantize)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, x.shape[0], batch):
            h = pieces["first"](fitted.around["in_proj"], jnp.asarray(x[lo: lo + batch]))
            for layer, p in enumerate(fitted.layers, 1):
                for kind in kinds_of(a, layer):
                    h = pieces["forth"](kind, own(kind, p), h)
            out.append(np.asarray(pieces["last"](fitted.around, h)))
    return rows_of(np.concatenate(out)[:n], rows.shape[0], context, stride)


def cross_validate(rows: np.ndarray, model: Dict[str, Any], seed: int,
                   n_splits: int, quantize: Optional[Callable] = None,
                   fault: Optional[str] = None) -> np.ndarray:
    """The detector's thresholds of one machine, the aggregate one first and
    then one per tag: for each expanding fold a fit on the fold's rows from
    the same start and the same shuffle keys as the final fit, its forecast
    of the held-out block's rows from the block's second on, the absolute
    error in the scale of the whole series (min-max of the raw targets),
    smoothed and maximised over the block; then the mean over the folds.
    The aggregate error of a row is the Euclidean norm of its tags'."""
    rows = np.asarray(rows, np.float32)
    span = np.maximum(rows.max(axis=0) - rows.min(axis=0), _EPS).astype(np.float64)
    per_fold = []
    for train_end, test_end in expanding_folds(rows.shape[0], n_splits):
        fitted = fit(rows, model, seed, quantize=quantize, train_rows=train_end,
                     fault=fault)
        held = rows[train_end:test_end]
        pred = predict(fitted["model"], rows[:train_end], held, model,
                       fitted["shape"], quantize)
        err = np.abs(pred.astype(np.float64) - held[1:].astype(np.float64)) / span
        total = np.linalg.norm(err, axis=-1, keepdims=True)
        per_fold.append(np.concatenate([smoothed_max(total), smoothed_max(err)], axis=-1))
        del fitted
    return np.mean(per_fold, axis=0)
