"""Plain reference of the ``lfm2_moe`` next-row forecaster and of its fit.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision, written from the layer equations of LFM2-24B-A2B (``model_type``
``lfm2_moe``, https://huggingface.co/LiquidAI/LFM2-24B-A2B) and not from the
program's factory.  It imports nothing of the program and takes nothing the
program made: its own seeded weights (the parameters' names, shapes and
initial distributions are the artifact's format: one flat dict, a kind's
parameters stacked over the layers that have it; the per-parameter keys
folded from the parameter's ordinal the way ``flax.linen`` does), its own
data copy, sequences, folds, held-out forecasts and thresholds.  Pieces that
know nothing of a model (the matmul with the control's hook, the norm,
SwiGLU, the sequences and the scaling, Adam's constants, the folds) are the
benchmark's own accepted ones, from ``reference/lstm_ae.py`` and
``reference/kimi_linear.py``.

Every block is pre-norm residual, ``h += Mixer(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``, ``norm_eps`` 1e-5.  Layers are numbered as the source
numbers them: the cut holds the source's layers 1 to ``num_layers``.

- Gated short convolution (``layer_types[l] == "conv"``): ``[B ; C ; X] = x
  W_in`` (three thirds of 2048, in that order), ``u = B * X``, ``v_t = w_0
  u_{t-2} + w_1 u_{t-1} + w_2 u_t`` (a loop over the three taps, zeros before
  the sequence), ``y = (C * v) W_out``.
- Grouped-query attention (``"full_attention"``): ``q = x W_q`` (32 heads of
  64), ``k = x W_k``, ``v = x W_v`` (8 heads of 64); ``q`` and ``k`` through
  an RMSNorm over a head's 64 channels (one weight vector for the query
  heads, one for the key heads), then rotated by their position inside the
  sequence (theta 1e6 over all 64 channels, by cosines and sines, channel j
  paired with channel j + 32); per query head ``i`` a full masked softmax of
  ``q_i k_{i // 4}^T / 8`` against its group's keys; ``W_o``.
- The source's layers below ``num_dense_layers`` (2): SwiGLU of width 11776.
  The others: the expert layer as a dense loop over the held experts with a
  mask: ``s = sigmoid(W_r x)``, the 4 largest, ``w_e = s_e / (sum_selected s
  + 1e-6)``, ``y = sum_{e selected and held} w_e E_e(x)``.  No shared expert.
- Head: ``h_0 = X W_in``, ``Y = RMSNorm(h_L) W_out + b``: position i reads
  row r and forecasts row r + 1.

Departures from the source, each also in the configuration's file:
``vocabulary`` (no token embedding or head); the head width 64 (the source
gives ``head_dim`` null: 2048 / 32); the order ``B, C, X`` of ``W_in``'s
thirds; the router's selection bias (``use_expert_bias``) is a buffer held
at 0 and not stored, and there is no auxiliary loss; rotary pairs are
half-split; initial weights N(0, 1 / fan-in) (a convolution's fan-in is its
width), norms 1; the cut (``depth``: the source's layer 1, whose dense
feed-forward stands for both leading dense layers, and layers 2-5;
``experts``: experts 0-7 of 64, the absent experts' terms left out).

The fit writes the chain rule over the parts out (one small compiled program
per kind of part, each part's own gradient by ``jax.vjp`` of its plain
forward): the whole model's step in one program would not fit a chip beside
the parameters.  ``quantize`` is the control's hook on both operands of
every matmul that the configuration computes in bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_linear import (  # model-agnostic, accepted pieces
    HIGHEST, _draw, _einsum, _matmul, _norms, _rms_norm, _swiglu, minmax, rows_of,
    sequences,
)
from benchmark.reference.lstm_ae import (  # noqa: F401  (re-exported for the controls)
    ADAM_B1, ADAM_B2, ADAM_EPS, _EPS, _path_key, bfloat16, expanding_folds, float8,
    smoothed_max,
)

#: the source's ``layer_types`` and ``num_dense_layers``, layers from 0
LAYER_TYPES = ("conv", "conv", "full_attention", "conv") * 10
NUM_DENSE_LAYERS = 2
#: the cut starts at the source's layer 1: layer 0 (a second convolution with
#: a dense feed-forward) is left out, "the leading dense layers counted once"
FIRST_LAYER = 1
#: the published widths (config.json of the source) and this repo's cut
PUBLISHED = dict(
    num_layers=5, hidden_size=2048, num_heads=32, num_kv_heads=8,
    short_conv_kernel_size=3, rope_theta=1e6, intermediate_size=11776,
    moe_intermediate_size=1536, num_experts=64, num_experts_per_token=4,
    routed_scaling_factor=1.0, route_eps=1e-6, experts_held_from=0,
    experts_held=8, rms_norm_eps=1e-5,
)
FAULTS = (None, "half_batch", "no_taps", "no_qk_norm", "no_rotation", "wrong_group")
#: the faults that change the forward pass (the others change the fit)
FORWARD_FAULTS = ("no_taps", "no_qk_norm", "no_rotation", "wrong_group")
LEAST_EFFORT = {"exec_time_optimization_effort": -1.0}
MIXERS = ("conv", "gqa")


def shape_of(model: Dict[str, Any], n_features: int, n_out: int) -> Tuple:
    """The architecture as a hashable tuple of ``(key, value)``: the
    published widths, overridden by what the configuration's ``model`` says."""
    if model["kind"] != "lfm2_moe":
        raise ValueError(f"no reference for kind {model['kind']!r}")
    spec = {**PUBLISHED, **{k: model[k] for k in PUBLISHED if k in model}}
    spec.update(n_features=int(n_features), n_out=int(n_out))
    return tuple(sorted(spec.items()))


def kinds_of(a: Dict[str, Any], layer: int) -> Tuple[str, str]:
    """``(mixer, feed-forward)`` of held layer ``layer`` (0 is the source's
    layer ``FIRST_LAYER``)."""
    source = FIRST_LAYER + layer
    return ("gqa" if LAYER_TYPES[source] == "full_attention" else "conv",
            "dense" if source < NUM_DENSE_LAYERS else "moe")


def layers_of(a: Dict[str, Any], kind: str) -> List[int]:
    return [layer for layer in range(a["num_layers"]) if kind in kinds_of(a, layer)]


# ---------------------------------------------------------------------------
# initial weights from the seed
# ---------------------------------------------------------------------------

def parameter_list(a: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every parameter's name, shape and initial distribution, in the order
    the artifact's module creates them (the ordinal seeds the draw): the
    input, the layers' norms, each kind's stack over its layers (the
    convolutions, attention, the dense feed-forward, the experts), the head."""
    d, n, h, kv = a["hidden_size"], a["num_layers"], a["num_heads"], a["num_kv_heads"]
    hd = d // h                              # the source gives head_dim null
    w, e, wide = a["moe_intermediate_size"], a["experts_held"], a["intermediate_size"]
    by_kind = {
        "conv": [
            ("conv_win", (d, 3 * d), "normal"),
            ("conv_taps", (a["short_conv_kernel_size"], d), "normal"),
            ("conv_wout", (d, d), "normal"),
        ],
        "gqa": [
            ("gqa_wq", (d, h * hd), "normal"),
            ("gqa_wk", (d, kv * hd), "normal"),
            ("gqa_wv", (d, kv * hd), "normal"),
            ("gqa_q_norm", (hd,), "ones"),
            ("gqa_k_norm", (hd,), "ones"),
            ("gqa_wo", (h * hd, d), "normal"),
        ],
        "dense": [("dense_wg", (d, wide), "normal"), ("dense_wu", (d, wide), "normal"),
                  ("dense_wd", (wide, d), "normal")],
        "moe": [
            ("moe_router", (d, a["num_experts"]), "normal"),
            ("moe_wg", (e, d, w), "normal"),
            ("moe_wu", (e, d, w), "normal"),
            ("moe_wd", (e, w, d), "normal"),
        ],
    }
    out: List[Tuple[str, Tuple[int, ...], str]] = [
        ("in_proj", (a["n_features"], d), "normal"),
        ("mixer_norm", (n, d), "ones"),
        ("ffn_norm", (n, d), "ones"),
    ]
    for kind in MIXERS + ("dense", "moe"):
        count = len(layers_of(a, kind))
        if count:
            out += [(name, (count,) + dims, how) for name, dims, how in by_kind[kind]]
    return out + [("out_norm", (d,), "ones"), ("out_proj", (d, a["n_out"]), "normal"),
                  ("out_bias", (a["n_out"],), "zeros")]


def parameter_count(a: Dict[str, Any]) -> int:
    return sum(math.prod(dims) for _, dims, _ in parameter_list(a))


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

    return jax.jit(init, compiler_options=LEAST_EFFORT)


def init_params(seed: int, shape: Tuple):
    """``(params, fit_key)`` as a fit from ``seed`` starts."""
    return _init_fn(shape)(jax.random.PRNGKey(seed))


def layer_of(a: Dict[str, Any], params: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Held layer ``layer``'s own parameters out of the artifact's stacks."""
    own_ = {"mixer_norm": params["mixer_norm"][layer], "ffn_norm": params["ffn_norm"][layer]}
    for kind in kinds_of(a, layer):
        slot = layers_of(a, kind).index(layer)
        own_.update({name: value[slot] for name, value in params.items()
                     if name.startswith(kind + "_")})
    return own_


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rope(x, theta: float):
    """Rotary positions on the last axis of ``x`` (B, T, heads, width):
    position t turns the pair (channel j, channel j + width / 2) by the angle
    ``t * theta^(-2j / width)``: ``(a, b) -> (a cos - b sin, a sin + b cos)``."""
    t, width = x.shape[1], x.shape[-1]
    half = width // 2
    angle = np.arange(t, dtype=np.float64)[:, None] / (
        float(theta) ** (2.0 * np.arange(half, dtype=np.float64) / width))[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _conv(a, p, x, quantize, fault: Optional[str] = None):
    """The gated short convolution on the normed stream ``x`` (B, T, D)."""
    d, taps = a["hidden_size"], p["conv_taps"]
    thirds = _matmul(x, p["conv_win"], quantize)
    gate_in, gate_out, inner = thirds[..., :d], thirds[..., d:2 * d], thirds[..., 2 * d:]
    u = gate_in * inner
    k, t = taps.shape[0], x.shape[1]
    v = jnp.zeros_like(u)
    for j in range(k):
        back = k - 1 - j                       # tap j reads the row ``back`` rows earlier
        if fault == "no_taps" and back:
            continue
        moved = u if not back else jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, : t - back]], axis=1)
        v = v + taps[j] * moved
    return _matmul(gate_out * v, p["conv_wout"], quantize)


def _gqa(a, p, x, quantize, fault: Optional[str] = None):
    """Grouped-query attention on the normed stream ``x`` (B, T, D): one
    query head at a time, a full masked softmax against its group's keys."""
    h, kv, eps = a["num_heads"], a["num_kv_heads"], a["rms_norm_eps"]
    b, t, d = x.shape
    hd = d // h
    q = _matmul(x, p["gqa_wq"], quantize).reshape(b, t, h, hd)
    k = _matmul(x, p["gqa_wk"], quantize).reshape(b, t, kv, hd)
    v = _matmul(x, p["gqa_wv"], quantize).reshape(b, t, kv, hd)
    if fault != "no_qk_norm":
        q, k = _rms_norm(q, p["gqa_q_norm"], eps), _rms_norm(k, p["gqa_k_norm"], eps)
    if fault != "no_rotation":
        q, k = rope(q, a["rope_theta"]), rope(k, a["rope_theta"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = h // kv

    def attend(qkv):
        """One sequence ``(1, t, ...)``: every query head's full square."""
        q, k, v = qkv
        heads = []
        for i in range(h):
            mine = i % kv if fault == "wrong_group" else i // group
            scores = _einsum("btc,bsc->bts", q[:, :, i], k[:, :, mine], quantize) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            heads.append(_einsum("bts,bsv->btv", probs, v[:, :, mine], quantize))
        return jnp.stack(heads, axis=2)

    # a sequence at a time, each recomputed in the backward pass: memory
    # forces it (32 squares of 2,048 are 0.5 GB a sequence in float32), and
    # sequences do not see each other
    o = jax.lax.map(jax.checkpoint(attend), tuple(z[:, None] for z in (q, k, v)))
    o = o.reshape(b, t, h * hd)
    return _matmul(o, p["gqa_wo"], quantize)


def routing(a, router, x):
    """``(experts, weights)`` (..., 4): the selected experts of every
    position and ``scale * s_e / (sum_selected s + 1e-6)``.  Float32, never
    rounded; the source's selection bias is 0, so the largest of ``s + b``
    are the largest of ``s``."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    top, experts = jax.lax.top_k(scores, a["num_experts_per_token"])
    return experts, a["routed_scaling_factor"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + a["route_eps"])


def _experts(a, p, x, quantize, held: Optional[Tuple[int, int]] = None):
    """The routed sum over the held experts, and nothing else.  ``held``
    (first, count) defaults to the architecture's; the weights ``moe_w*``
    are those of the held experts, in order."""
    first, count = held or (a["experts_held_from"], a["experts_held"])
    experts, weights = routing(a, p["moe_router"], x)

    def add_expert(y, expert):
        e, wg, wu, wd = expert
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return y + w_e[..., None] * _swiglu(x, wg, wu, wd, quantize), None

    # every held expert on every position, its weight 0 where it was not
    # selected: a loop over the experts, compiled once
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(count), p["moe_wg"], p["moe_wu"], p["moe_wd"]))
    return y


def part(a: Dict[str, Any], kind: str, p: Dict[str, Any], h, quantize,
         fault: Optional[str] = None, held: Optional[Tuple[int, int]] = None):
    """One pre-norm residual part of a block on the stream ``h`` (B, T, D):
    ``h + Mixer(RMSNorm(h))`` for ``conv`` or ``gqa``, ``h + FFN(RMSNorm(h))``
    for ``dense`` or ``moe``; ``p`` are the block's own parameters."""
    eps = a["rms_norm_eps"]
    if kind in MIXERS:
        z = _rms_norm(h, p["mixer_norm"], eps)
        return h + (_conv if kind == "conv" else _gqa)(a, p, z, quantize, fault)
    z = _rms_norm(h, p["ffn_norm"], eps)
    if kind == "dense":
        return h + _swiglu(z, p["dense_wg"], p["dense_wu"], p["dense_wd"], quantize)
    return h + _experts(a, p, z, quantize, held)


def own(kind: str, p: Dict[str, Any]) -> Dict[str, Any]:
    """The parameters of a block that its part of ``kind`` reads."""
    norm = "mixer_norm" if kind in MIXERS else "ffn_norm"
    return {name: value for name, value in p.items()
            if name == norm or name.startswith(kind + "_")}


def embed(in_proj, x, quantize):
    return _matmul(x.astype(jnp.float32), in_proj, quantize)


def head(a: Dict[str, Any], around: Dict[str, Any], h, quantize):
    return _matmul(_rms_norm(h, around["out_norm"], a["rms_norm_eps"]),
                   around["out_proj"], quantize) + around["out_bias"]


def forward(params: Dict[str, Any], x: jnp.ndarray, shape: Tuple,
            quantize: Optional[Callable] = None, fault: Optional[str] = None):
    """``x`` (B, T, F) scaled rows -> (B, T, n_out): position i's output is
    the forecast of the row after the one it reads.  ``params`` in the
    artifact's layout."""
    a = dict(shape)
    h = embed(params["in_proj"], x, quantize)
    for layer in range(a["num_layers"]):
        p = layer_of(a, params, layer)
        for kind in kinds_of(a, layer):
            h = part(a, kind, p, h, quantize, fault)
    return head(a, params, h, quantize)


def weighted_mse(pred, y, w):
    per_position = jnp.mean((pred - y) ** 2, axis=-1)
    return jnp.sum(per_position * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss(params: Dict[str, Any], x, y, w, shape: Tuple,
         quantize: Optional[Callable] = None, fault: Optional[str] = None):
    """The weighted mean squared error of one minibatch by the plain forward:
    what ``jax.grad`` differentiates in the tests; the fit below computes the
    same thing part by part."""
    return weighted_mse(forward(params, x, shape, quantize, fault), y, w)


# ---------------------------------------------------------------------------
# the fit: Adam over shuffled minibatches of sequences, part by part
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    """A model's parameters as a list of layers (:func:`layer_of`) and the
    four around them; Adam's moments have the same form."""

    layers: List[Dict[str, Any]]
    around: Dict[str, Any]


AROUND = ("in_proj", "out_norm", "out_proj", "out_bias")


def split(a: Dict[str, Any], params: Dict[str, Any]) -> Model:
    """The artifact's stacked parameters as a :class:`Model`."""
    return Model([layer_of(a, params, layer) for layer in range(a["num_layers"])],
                 {name: params[name] for name in AROUND})


def to_host(model: Model) -> Model:
    """``model`` with its arrays fetched to the host: 1.8 GB a chip has no
    room for beside a second fit (the control keeps the sound fit so)."""
    return Model([jax.device_get(p) for p in model.layers], jax.device_get(model.around))


def as_model(a: Dict[str, Any], params) -> Model:
    return params if isinstance(params, Model) else split(a, params)


@functools.lru_cache(maxsize=None)
def _pieces(shape: Tuple, quantize: Optional[Callable], fault: Optional[str]):
    """The compiled pieces a fit is made of: forward keeps the stream at
    every part's entrance, backward goes through the parts last to first,
    each part's own backward pass recomputing its forward."""
    a = dict(shape)
    # compiled at the least effort: a dozen pieces at the published widths
    # take a minute to compile at the default, and a run has 360 s in all
    jit = functools.partial(jax.jit, compiler_options=LEAST_EFFORT)

    @functools.partial(jit, static_argnums=0)
    def forth(kind, p, h):
        return part(a, kind, p, h, quantize, fault)

    @functools.partial(jit, static_argnums=0)
    def back(kind, p, h, dh):
        _, vjp = jax.vjp(lambda p, h: part(a, kind, p, h, quantize, fault), p, h)
        return vjp(dh)                                   # (dp, dh at the entrance)

    @jit
    def first(in_proj, x):
        return embed(in_proj, x, quantize)

    @jit
    def first_back(in_proj, x, dh):
        return jax.vjp(lambda w: embed(w, x, quantize), in_proj)[1](dh)[0]

    @jit
    def last(around, h):
        return head(a, around, h, quantize)

    @jit
    def last_back(around, h, y, w):
        """The loss with its gradients for (norm, W_out, b) and the stream."""
        return jax.value_and_grad(
            lambda around, h: weighted_mse(head(a, around, h, quantize), y, w),
            argnums=(0, 1))(around, h)

    @functools.partial(jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, lr, c1, c2):
        m = jax.tree.map(lambda a_, b: ADAM_B1 * a_ + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a_, b: ADAM_B2 * a_ + (1 - ADAM_B2) * b * b, v, g)
        p = jax.tree.map(
            lambda a_, mm, vv: a_ - lr * (mm / c1) / (jnp.sqrt(vv / c2) + ADAM_EPS), p, m, v)
        return p, m, v

    return dict(forth=forth, back=back, first=first, first_back=first_back, last=last,
                last_back=last_back, adam=adam)


def _step(pieces, a, model: Model, m: Model, v: Model, t: int, lr: float, x, y, w):
    """One optimiser step in place of ``model``, ``m``, ``v``; returns the
    minibatch's loss."""
    parts = [(i, kind) for i in range(a["num_layers"]) for kind in kinds_of(a, i)]
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    in_proj = model.around["in_proj"]
    h, entrances = pieces["first"](in_proj, x), []
    for i, kind in parts:
        entrances.append(h)
        h = pieces["forth"](kind, own(kind, model.layers[i]), h)
    out = {name: model.around[name] for name in AROUND[1:]}
    value, (d_around, dh) = pieces["last_back"](out, h, y, w)
    del h
    grads = {}
    for i, kind in reversed(parts):
        dp, dh = pieces["back"](kind, own(kind, model.layers[i]), entrances.pop(), dh)
        grads.update(dp)
        if len(grads) == len(model.layers[i]):           # both parts of layer i are in
            model.layers[i], m.layers[i], v.layers[i] = pieces["adam"](
                model.layers[i], m.layers[i], v.layers[i], grads, lr, c1, c2)
            grads = {}
    d_around = {**d_around, "in_proj": pieces["first_back"](in_proj, x, dh)}
    model.around, m.around, v.around = pieces["adam"](
        model.around, m.around, v.around, d_around, lr, c1, c2)
    return value


def fit(rows: np.ndarray, model: Dict[str, Any], seed: int,
        quantize: Optional[Callable] = None,
        train_rows: Optional[int] = None,
        fault: Optional[str] = None) -> Dict[str, Any]:
    """One fit of one machine ``(rows, tags)`` from its raw rows (the first
    ``train_rows`` of them: a fold).  Inputs are the rows min-max-scaled by
    the rows trained on; the targets are raw.  Every epoch shuffles the
    sequences, padded to whole minibatches, anew; a minibatch smaller than
    ``batch_size`` is filled with slots that weigh nothing.  Returns the
    per-epoch mean of the loss (``history``, a step weighing what its
    positions weigh) and the fitted ``model`` (a :class:`Model`, on the
    device).

    ``fault`` plants a fault of the timed path for the control
    (``benchmark/backbone_control.py``; ``tests/test_backbone_lfm2.py`` at the
    tiny size): ``"half_batch"`` leaves the second half of every minibatch
    out of the loss; ``"no_taps"`` zeroes the convolution's two earlier taps
    (the mixer sees its own row alone); ``"no_qk_norm"`` leaves the heads'
    norms out; ``"no_rotation"`` the rotary positions; ``"wrong_group"``
    lets query head ``i`` read key/value head ``i % 8``."""
    if fault not in FAULTS:
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

    pieces = _pieces(shape, quantize, fault if fault in FORWARD_FAULTS else None)
    history, t = [], 0
    with jax.default_matmul_precision("highest"):
        for key in jax.random.split(fit_key, epochs):
            perm = np.asarray(jax.random.permutation(key, steps * bs)).reshape(steps, bs)
            perm = np.concatenate(
                [perm, np.full((steps, batch - bs), blank, perm.dtype)], axis=1)
            total, weight = 0.0, 0.0
            for idx in perm:
                t += 1
                wb = w[idx] * kept
                value = _step(pieces, a, fitted, m, v, t, lr, x[idx], y[idx], wb)
                count = float(jnp.sum(wb))
                total, weight = total + float(value) * count, weight + count
            history.append(total / max(weight, 1.0))
    return {"history": np.asarray(history, np.float64), "model": fitted, "shape": shape}


# ---------------------------------------------------------------------------
# how far two fits are apart, parameter by parameter
# ---------------------------------------------------------------------------

def distances(ours, theirs, seed: int, shape: Tuple) -> Dict[str, List]:
    """Per parameter of every layer (``l<layer>.<name>``, layers from 0 as
    held) and of the four around them: the norm of our change from the
    seed's initial weights, of theirs, and of the difference between the two
    fits.  Taken on the device, one group at a time."""
    a = dict(shape)
    ours, theirs = as_model(a, ours), as_model(a, theirs)
    start = split(a, init_params(seed, shape)[0])
    groups = [(f"l{i}.", ours.layers[i], theirs.layers[i], start.layers[i])
              for i in range(len(ours.layers))]
    groups.append(("", ours.around, theirs.around, start.around))
    names, rows = [], []
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
    layers[layer][name] = start.layers[layer][name]
    return Model(layers, dict(fitted.around))


# ---------------------------------------------------------------------------
# cross-validation: the thresholds of the anomaly detector
# ---------------------------------------------------------------------------

def predict(params, train: np.ndarray, rows: np.ndarray, model: Dict[str, Any],
            shape: Tuple, quantize: Optional[Callable] = None,
            fault: Optional[str] = None) -> np.ndarray:
    """Forecasts of rows 1.. of ``rows`` (scaled by ``train``'s columns);
    ``params`` a :class:`Model` or the artifact's stacked parameters.  The
    sequences go through the layers a minibatch at a time, the last one
    filled up with zero sequences that are dropped again, so the pieces a
    fit compiled serve the forecast too."""
    a = dict(shape)
    context, stride = int(model["context"]), int(model["stride"])
    batch = int(model.get("batch_size", 8))
    fitted = as_model(a, params)
    x, _, _ = sequences(minmax(train, rows), rows, context, stride)
    n = x.shape[0]
    x = np.concatenate([x, np.zeros((-n % batch,) + x.shape[1:], x.dtype)])
    pieces = _pieces(shape, quantize, fault if fault in FORWARD_FAULTS else None)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, x.shape[0], batch):
            h = pieces["first"](fitted.around["in_proj"], jnp.asarray(x[lo: lo + batch]))
            for layer, p in enumerate(fitted.layers):
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
    error in the scale of the whole series, smoothed and maximised over the
    block; then the mean over the folds."""
    rows = np.asarray(rows, np.float32)
    span = np.maximum(rows.max(axis=0) - rows.min(axis=0), _EPS).astype(np.float64)
    per_fold = []
    for train_end, test_end in expanding_folds(rows.shape[0], n_splits):
        fitted = fit(rows, model, seed, quantize=quantize, train_rows=train_end,
                     fault=fault)
        held = rows[train_end:test_end]
        pred = predict(fitted["model"], rows[:train_end], held, model,
                       fitted["shape"], quantize, fault)
        err = np.abs(pred.astype(np.float64) - held[1:].astype(np.float64)) / span
        total = np.linalg.norm(err, axis=-1, keepdims=True)
        per_fold.append(np.concatenate([smoothed_max(total), smoothed_max(err)], axis=-1))
        del fitted
    return np.mean(per_fold, axis=0)
