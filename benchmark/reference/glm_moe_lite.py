"""Plain reference of the ``glm_moe_lite`` two-horizon forecaster and of its fit.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision, written from the layer equations of GLM-4.7-Flash (``model_type``
``glm4_moe_lite``, https://huggingface.co/zai-org/GLM-4.7-Flash) and not from
the program's factory.  It imports nothing of the program and takes nothing
the program made: its own seeded weights (the parameters' names, shapes and
initial distributions are the artifact's format: one flat dict, a kind's
parameters stacked over the layers that have it, the multi-token-prediction
module's under ``mtp_`` with a leading axis of one; the per-parameter keys
folded from the parameter's ordinal the way ``flax.linen`` does), its own
data copy, sequences, folds, held-out forecasts and thresholds.  Pieces that
know nothing of a model (the matmul with the control's hook, the norm, SwiGLU,
the router and the dense loop over held experts, the sequences and the
scaling) are the benchmark's own accepted ones, from ``reference/lstm_ae.py``
and ``reference/kimi_linear.py``.

Every block is pre-norm residual, ``h += Mixer(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``, ``rms_norm_eps`` 1e-5, layers numbered from 0 as the
source does.

- MLA in every layer, a full masked softmax: ``c_q = RMSNorm(W_qa x)``, ``q =
  W_qb c_q`` -> heads x (192 + 64) ``= [q_n, q_r]``; ``[c, k_r] = W_kva x``,
  ``c <- RMSNorm(c)``, ``[k_n, v] = W_kvb c`` -> heads x (192 + 256);
  ``q_r`` and the shared ``k_r`` rotated by their position inside the
  sequence (theta 1e6 over all 64 channels, the rotation written out by
  cosines and sines, channel j paired with channel j + 32); ``score = (q_n
  k_n + q_r k_r) / sqrt(256)``, causal softmax, values of 256 a head, ``W_o``.
- Layer 0's feed-forward SwiGLU of width 10240; the others' the expert layer
  as a dense loop over the held experts with a mask: ``s = sigmoid(W_r x)``,
  the 4 largest, ``w_e = 1.8 s_e / sum_selected s``, ``y = sum_{e selected
  and held} w_e E_e(x) + E_shared(x)``.
- Head: ``h_0 = X W_in``, ``Y1 = RMSNorm(h_L) W_out + b``: position i reads
  row r and forecasts row r + 1.
- The MTP module, in training only: ``h'_i = W_eh [RMSNorm_h(h_{L,i});
  RMSNorm_e(x_{i+1} W_in)]``, one whole block of the expert-layer form with
  its own weights, ``Y2_i = RMSNorm_mtp(h''_i) W_out + b``: row r + 2 from
  rows <= r + 1.  The last position of a sequence has no next position: it
  reads a zero row and weighs nothing.
- Loss ``L = L1 + lambda L2``, each a weighted mean over its own real
  positions (``L2``'s weights are ``L1``'s moved one position down);
  forecasts and thresholds from ``Y1`` alone.

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
    _draw, _einsum, _experts, _matmul, _norms, _rms_norm, _swiglu, minmax,
    rows_of, sequences,
)
from benchmark.reference.lstm_ae import (  # noqa: F401  (re-exported for the controls)
    ADAM_B1, ADAM_B2, ADAM_EPS, _EPS, _path_key, bfloat16, expanding_folds, float8,
    smoothed_max,
)

#: the published widths (config.json of the source) and this repo's cut
PUBLISHED = dict(
    num_layers=5, hidden_size=2048, num_heads=20, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    rope_theta=1e6, intermediate_size=10240, first_k_dense_replace=1,
    moe_intermediate_size=1536, num_experts=64, num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=1.8, experts_held_from=0,
    experts_held=8, rms_norm_eps=1e-5, mtp_depth=1, mtp_weight=0.3,
)
FAULTS = (None, "half_batch", "no_rotation", "no_mtp")
LEAST_EFFORT = {"exec_time_optimization_effort": -1.0}


def shape_of(model: Dict[str, Any], n_features: int, n_out: int) -> Tuple:
    """The architecture as a hashable tuple of ``(key, value)``: the
    published widths, overridden by what the configuration's ``model`` says."""
    if model["kind"] != "glm_moe_lite":
        raise ValueError(f"no reference for kind {model['kind']!r}")
    spec = {**PUBLISHED, **{k: model[k] for k in PUBLISHED if k in model}}
    if spec["mtp_depth"] not in (0, 1):
        raise ValueError("one multi-token-prediction module at most")
    spec.update(n_features=int(n_features), n_out=int(n_out))
    return tuple(sorted(spec.items()))


def kinds_of(a: Dict[str, Any], layer: int) -> Tuple[str, str]:
    """``(mixer, feed-forward)`` of a layer, numbered from 0."""
    return "mla", ("dense" if layer < a["first_k_dense_replace"] else "moe")


def layers_of(a: Dict[str, Any], kind: str) -> List[int]:
    return [layer for layer in range(a["num_layers"]) if kind in kinds_of(a, layer)]


# ---------------------------------------------------------------------------
# initial weights from the seed
# ---------------------------------------------------------------------------

def _block_parameters(a: Dict[str, Any]) -> Dict[str, List[Tuple[str, Tuple[int, ...], str]]]:
    d, h = a["hidden_size"], a["num_heads"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    qr, rank = a["q_lora_rank"], a["kv_lora_rank"]
    w, e = a["moe_intermediate_size"], a["experts_held"]
    ws, wide = w * a["num_shared_experts"], a["intermediate_size"]
    return {
        "mla": [
            ("mla_wq_a", (d, qr), "normal"),
            ("mla_q_norm", (qr,), "ones"),
            ("mla_wq_b", (qr, h * (dn + dr)), "normal"),
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


def parameter_list(a: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every parameter's name, shape and initial distribution, in the order
    the artifact's module creates them (the ordinal seeds the draw): the
    input, the layers' norms, each kind's stack over its layers, the head,
    and last the MTP module (two input norms, ``W_eh``, a block's two norms,
    its MLA and its expert layer, its output norm), stacked over one."""
    d, n = a["hidden_size"], a["num_layers"]
    by_kind = _block_parameters(a)
    out: List[Tuple[str, Tuple[int, ...], str]] = [
        ("in_proj", (a["n_features"], d), "normal"),
        ("mixer_norm", (n, d), "ones"),
        ("ffn_norm", (n, d), "ones"),
    ]
    for kind in ("mla", "dense", "moe"):
        count = len(layers_of(a, kind))
        if count:
            out += [(name, (count,) + dims, how) for name, dims, how in by_kind[kind]]
    out += [("out_norm", (d,), "ones"), ("out_proj", (d, a["n_out"]), "normal"),
            ("out_bias", (a["n_out"],), "zeros")]
    if a["mtp_depth"]:
        module = [("norm_h", (d,), "ones"), ("norm_e", (d,), "ones"),
                  ("weh", (2 * d, d), "normal"), ("mixer_norm", (d,), "ones"),
                  ("ffn_norm", (d,), "ones")] + by_kind["mla"] + by_kind["moe"] + [
                      ("out_norm", (d,), "ones")]
        out += [("mtp_" + name, (1,) + dims, how) for name, dims, how in module]
    return out


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
    """Layer ``layer``'s own parameters out of the artifact's stacks."""
    own_ = {"mixer_norm": params["mixer_norm"][layer], "ffn_norm": params["ffn_norm"][layer]}
    for kind in kinds_of(a, layer):
        slot = layers_of(a, kind).index(layer)
        own_.update({name: value[slot] for name, value in params.items()
                     if name.startswith(kind + "_")})
    return own_


def module_of(params: Dict[str, Any]) -> Dict[str, Any]:
    """The MTP module's own parameters, their prefix and leading axis off
    (empty where the model has none)."""
    return {name[len("mtp_"):]: value[0] for name, value in params.items()
            if name.startswith("mtp_")}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rope(x, theta: float):
    """Rotary positions on the last axis of ``x`` (B, T, ..., width):
    position t turns the pair (channel j, channel j + width / 2) by the angle
    ``t * theta^(-2j / width)``: ``(a, b) -> (a cos - b sin, a sin + b cos)``."""
    t, width = x.shape[1], x.shape[-1]
    half = width // 2
    angle = np.arange(t, dtype=np.float64)[:, None] / (
        float(theta) ** (2.0 * np.arange(half, dtype=np.float64) / width))[None, :]
    lead = (1, t) + (1,) * (x.ndim - 3) + (half,)
    cos = jnp.asarray(np.cos(angle), jnp.float32).reshape(lead)
    sin = jnp.asarray(np.sin(angle), jnp.float32).reshape(lead)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _mla(a, p, x, quantize, rotated: bool = True):
    h, dn, dr, dv, rank = (a["num_heads"], a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                           a["v_head_dim"], a["kv_lora_rank"])
    b, t, _ = x.shape
    c_q = _rms_norm(_matmul(x, p["mla_wq_a"], quantize), p["mla_q_norm"], a["rms_norm_eps"])
    q = _matmul(c_q, p["mla_wq_b"], quantize).reshape(b, t, h, dn + dr)
    kv_a = _matmul(x, p["mla_wkv_a"], quantize)
    c = _rms_norm(kv_a[..., :rank], p["mla_kv_norm"], a["rms_norm_eps"])
    q_r, k_r = q[..., dn:], kv_a[..., rank:]
    if rotated:
        q_r, k_r = rope(q_r, a["rope_theta"]), rope(k_r, a["rope_theta"])
    kv = _matmul(c, p["mla_wkv_b"], quantize).reshape(b, t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (b, t, h, dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
    scores = _einsum("bthc,bshc->bhts", q, k, quantize) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _einsum("bhts,bshv->bthv", probs, kv[..., dn:], quantize)
    return _matmul(o.reshape(b, t, h * dv), p["mla_wo"], quantize)


def part(a: Dict[str, Any], kind: str, p: Dict[str, Any], h, quantize,
         rotated: bool = True, held: Optional[Tuple[int, int]] = None):
    """One pre-norm residual part of a block on the stream ``h`` (B, T, D):
    ``h + Mixer(RMSNorm(h))`` for ``mla``, ``h + FFN(RMSNorm(h))`` for
    ``dense`` or ``moe``; ``p`` are the block's own parameters."""
    eps = a["rms_norm_eps"]
    if kind == "mla":
        return h + _mla(a, p, _rms_norm(h, p["mixer_norm"], eps), quantize, rotated)
    z = _rms_norm(h, p["ffn_norm"], eps)
    if kind == "dense":
        return h + _swiglu(z, p["dense_wg"], p["dense_wu"], p["dense_wd"], quantize)
    return h + _experts(a, p, z, quantize, held)


def own(kind: str, p: Dict[str, Any]) -> Dict[str, Any]:
    """The parameters of a block that its part of ``kind`` reads."""
    norm = "mixer_norm" if kind == "mla" else "ffn_norm"
    return {name: value for name, value in p.items()
            if name == norm or name.startswith(kind + "_")}


def embed(in_proj, x, quantize):
    return _matmul(x.astype(jnp.float32), in_proj, quantize)


def head(a: Dict[str, Any], norm, out_proj, out_bias, h, quantize):
    return _matmul(_rms_norm(h, norm, a["rms_norm_eps"]), out_proj, quantize) + out_bias


def ahead(z):
    """``z`` moved one position down its sequence: position i gets position
    i + 1's, the last one zeros."""
    return jnp.concatenate([z[:, 1:], jnp.zeros_like(z[:, :1])], axis=1)


def merge(a: Dict[str, Any], mp: Dict[str, Any], in_proj, x, h, quantize):
    """The MTP module's input: ``W_eh [RMSNorm_h(h_i); RMSNorm_e(x_{i+1} W_in)]``."""
    eps = a["rms_norm_eps"]
    both = jnp.concatenate([_rms_norm(h, mp["norm_h"], eps),
                            _rms_norm(embed(in_proj, ahead(x), quantize), mp["norm_e"], eps)],
                           axis=-1)
    return _matmul(both, mp["weh"], quantize)


def forward(params: Dict[str, Any], x: jnp.ndarray, shape: Tuple,
            quantize: Optional[Callable] = None, mtp: bool = False,
            rotated: bool = True):
    """``x`` (B, T, F) scaled rows -> (B, T, n_out): position i's output is
    the forecast of the row after the one it reads; with ``mtp`` the pair
    (that, the MTP module's forecast of the row after it).  ``params`` in the
    artifact's layout."""
    a = dict(shape)
    h = embed(params["in_proj"], x, quantize)
    for layer in range(a["num_layers"]):
        p = layer_of(a, params, layer)
        for kind in kinds_of(a, layer):
            h = part(a, kind, p, h, quantize, rotated)
    y = head(a, params["out_norm"], params["out_proj"], params["out_bias"], h, quantize)
    if not mtp:
        return y
    mp = module_of(params)
    g = merge(a, mp, params["in_proj"], x, h, quantize)
    for kind in ("mla", "moe"):
        g = part(a, kind, mp, g, quantize, rotated)
    return y, head(a, mp["out_norm"], params["out_proj"], params["out_bias"], g, quantize)


def weighted_mse(pred, y, w):
    per_position = jnp.mean((pred - y) ** 2, axis=-1)
    return jnp.sum(per_position * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss(params: Dict[str, Any], x, y, w, shape: Tuple,
         quantize: Optional[Callable] = None):
    """``(L, L1, L2)`` of one minibatch by the plain forward: the weighted
    mean squared error of the next row, of the row after next (weights and
    targets moved one position down, the last position's 0), and ``L1 +
    lambda L2``.  What ``jax.grad`` differentiates in the tests; the fit
    below computes the same thing part by part."""
    a = dict(shape)
    if not (a["mtp_depth"] and a["mtp_weight"]):
        first = weighted_mse(forward(params, x, shape, quantize), y, w)
        return first, first, jnp.zeros(())
    y1, y2 = forward(params, x, shape, quantize, mtp=True)
    first, then = weighted_mse(y1, y, w), weighted_mse(y2, ahead(y), ahead(w))
    return first + a["mtp_weight"] * then, first, then


# ---------------------------------------------------------------------------
# the fit: Adam over shuffled minibatches of sequences, part by part
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    """A model's parameters as a list of layers (:func:`layer_of`), the MTP
    module's (:func:`module_of`; empty without one) and the four around
    them; Adam's moments have the same form."""

    layers: List[Dict[str, Any]]
    mtp: Dict[str, Any]
    around: Dict[str, Any]


AROUND = ("in_proj", "out_norm", "out_proj", "out_bias")
MERGE = ("norm_h", "norm_e", "weh")


def split(a: Dict[str, Any], params: Dict[str, Any]) -> Model:
    """The artifact's stacked parameters as a :class:`Model`."""
    return Model([layer_of(a, params, layer) for layer in range(a["num_layers"])],
                 module_of(params), {name: params[name] for name in AROUND})


def to_host(model: Model) -> Model:
    """``model`` with its arrays fetched to the host: 2.5 GB a chip has no
    room for beside a second fit (the control keeps the sound fit so)."""
    return Model([jax.device_get(p) for p in model.layers],
                 jax.device_get(model.mtp), jax.device_get(model.around))


def as_model(a: Dict[str, Any], params) -> Model:
    return params if isinstance(params, Model) else split(a, params)


@functools.lru_cache(maxsize=None)
def _pieces(shape: Tuple, quantize: Optional[Callable], rotated: bool):
    """The compiled pieces a fit is made of: forward keeps the stream at
    every part's entrance, backward goes through the parts last to first,
    each part's own backward pass recomputing its forward."""
    a = dict(shape)
    # compiled at the least effort: at the published widths the dozen pieces
    # take 71 s to compile at the default and 4 s so (my compiles for a
    # described v5e, PR 34), and a run compiled anew has 360 s in all
    jit = functools.partial(jax.jit, compiler_options=LEAST_EFFORT)

    @functools.partial(jit, static_argnums=0)
    def forth(kind, p, h):
        return part(a, kind, p, h, quantize, rotated)

    @functools.partial(jit, static_argnums=0)
    def back(kind, p, h, dh):
        _, vjp = jax.vjp(lambda p, h: part(a, kind, p, h, quantize, rotated), p, h)
        return vjp(dh)                                   # (dp, dh at the entrance)

    @jit
    def first(in_proj, x):
        return embed(in_proj, x, quantize)

    @jit
    def first_back(in_proj, x, dh):
        return jax.vjp(lambda w: embed(w, x, quantize), in_proj)[1](dh)[0]

    @jit
    def last(around, h):
        return head(a, around["out_norm"], around["out_proj"], around["out_bias"], h, quantize)

    @jit
    def last_back(norm, out_proj, out_bias, h, y, w):
        """One head's loss with its gradients for (norm, W_out, b) and the
        stream: the main head's, or the MTP module's behind its own norm."""
        return jax.value_and_grad(
            lambda norm, out_proj, out_bias, h: weighted_mse(
                head(a, norm, out_proj, out_bias, h, quantize), y, w),
            argnums=(0, 1, 2, 3))(norm, out_proj, out_bias, h)

    @jit
    def merge_forth(mp, in_proj, x, h):
        return merge(a, mp, in_proj, x, h, quantize)

    @jit
    def merge_back(mp, in_proj, x, h, dg):
        _, vjp = jax.vjp(lambda mp, w, h: merge(a, mp, w, x, h, quantize), mp, in_proj, h)
        return vjp(dg)                                   # (d module's, d W_in, dh_L)

    @jit
    def combine(main, second, weight):
        return jax.tree.map(lambda m_, s_: m_ + weight * s_, main, second)

    @jit
    def scaled(tree, weight):
        return jax.tree.map(lambda z: weight * z, tree)

    @functools.partial(jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, lr, c1, c2):
        m = jax.tree.map(lambda a_, b: ADAM_B1 * a_ + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a_, b: ADAM_B2 * a_ + (1 - ADAM_B2) * b * b, v, g)
        p = jax.tree.map(
            lambda a_, mm, vv: a_ - lr * (mm / c1) / (jnp.sqrt(vv / c2) + ADAM_EPS), p, m, v)
        return p, m, v

    return dict(forth=forth, back=back, first=first, first_back=first_back, last=last,
                last_back=last_back, merge_forth=merge_forth, merge_back=merge_back,
                combine=combine, scaled=scaled, adam=adam)


def _step(pieces, a, model: Model, m: Model, v: Model, t: int, lr: float, lam: float,
          x, y, w):
    """One optimiser step in place of ``model``, ``m``, ``v``; returns the
    minibatch's ``(L1, L2)``.  With ``lam`` 0 the MTP module is not run and
    its parameters stay where they are."""
    parts = [(i, kind) for i in range(a["num_layers"]) for kind in kinds_of(a, i)]
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    in_proj = model.around["in_proj"]
    h, entrances = pieces["first"](in_proj, x), []
    for i, kind in parts:
        entrances.append(h)
        h = pieces["forth"](kind, own(kind, model.layers[i]), h)
    out = {name: model.around[name] for name in AROUND[1:]}
    first, (d_norm, d_proj, d_bias, dh) = pieces["last_back"](
        out["out_norm"], out["out_proj"], out["out_bias"], h, y, w)
    d_around = {"out_norm": d_norm, "out_proj": d_proj, "out_bias": d_bias}
    then, d_in = jnp.zeros(()), None
    if lam:
        mp = model.mtp
        own_merge = {name: mp[name] for name in MERGE}
        g0 = pieces["merge_forth"](own_merge, in_proj, x, h)
        g1 = pieces["forth"]("mla", own("mla", mp), g0)
        g2 = pieces["forth"]("moe", own("moe", mp), g1)
        then, (d_mtp_norm, d_proj2, d_bias2, dg) = pieces["last_back"](
            mp["out_norm"], out["out_proj"], out["out_bias"], g2, ahead(y), ahead(w))
        grads = {"out_norm": d_mtp_norm}
        del g2
        dp, dg = pieces["back"]("moe", own("moe", mp), g1, dg)
        grads.update(dp)
        del g1
        dp, dg = pieces["back"]("mla", own("mla", mp), g0, dg)
        grads.update(dp)
        del g0
        d_merge, d_in, dh2 = pieces["merge_back"](own_merge, in_proj, x, h, dg)
        grads.update(d_merge)
        dh = pieces["combine"](dh, dh2, lam)
        d_around["out_proj"] = pieces["combine"](d_around["out_proj"], d_proj2, lam)
        d_around["out_bias"] = pieces["combine"](d_around["out_bias"], d_bias2, lam)
        model.mtp, m.mtp, v.mtp = pieces["adam"](
            model.mtp, m.mtp, v.mtp, pieces["scaled"](grads, lam), lr, c1, c2)
        del grads, dh2, dg
    del h
    grads = {}
    for i, kind in reversed(parts):
        dp, dh = pieces["back"](kind, own(kind, model.layers[i]), entrances.pop(), dh)
        grads.update(dp)
        if len(grads) == len(model.layers[i]):           # both parts of layer i are in
            model.layers[i], m.layers[i], v.layers[i] = pieces["adam"](
                model.layers[i], m.layers[i], v.layers[i], grads, lr, c1, c2)
            grads = {}
    d_around["in_proj"] = pieces["first_back"](in_proj, x, dh)
    if d_in is not None:
        d_around["in_proj"] = pieces["combine"](d_around["in_proj"], d_in, lam)
    model.around, m.around, v.around = pieces["adam"](
        model.around, m.around, v.around, d_around, lr, c1, c2)
    return first, then


def fit(rows: np.ndarray, model: Dict[str, Any], seed: int,
        quantize: Optional[Callable] = None,
        train_rows: Optional[int] = None,
        fault: Optional[str] = None) -> Dict[str, Any]:
    """One fit of one machine ``(rows, tags)`` from its raw rows (the first
    ``train_rows`` of them: a fold).  Inputs are the rows min-max-scaled by
    the rows trained on; the targets are raw.  Every epoch shuffles the
    sequences, padded to whole minibatches, anew; a minibatch smaller than
    ``batch_size`` is filled with slots that weigh nothing.  Returns the
    per-epoch mean of the trained loss ``L1 + lambda L2`` (``history``, a
    step weighing what its next-row positions weigh), the two terms' own
    means (``terms``) and the fitted ``model`` (a :class:`Model`, on the
    device).

    ``fault`` plants a fault of the timed path for the control
    (``benchmark/horizons_control.py``; ``tests/test_backbone_glm.py`` at the
    tiny size): ``"half_batch"`` leaves the second
    half of every minibatch out of the loss, ``"no_rotation"`` leaves the
    rotary positions out of ``q_r`` and ``k_r``, ``"no_mtp"`` trains with
    lambda 0 (the MTP module stays at its start)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    rows = np.asarray(rows, np.float32)[: train_rows]
    context, stride = int(model["context"]), int(model["stride"])
    batch, epochs = int(model.get("batch_size", 8)), int(model.get("epochs", 1))
    lr = float(model.get("learning_rate", 1e-3))
    shape = shape_of(model, rows.shape[1], rows.shape[1])
    a = dict(shape)
    lam = 0.0 if fault == "no_mtp" or not a["mtp_depth"] else float(a["mtp_weight"])
    params0, fit_key = init_params(seed, shape)
    fitted = split(a, params0)
    del params0
    zeros = lambda: Model(  # noqa: E731
        [jax.tree.map(jnp.zeros_like, p) for p in fitted.layers],
        jax.tree.map(jnp.zeros_like, fitted.mtp),
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

    pieces = _pieces(shape, quantize, fault != "no_rotation")
    history, terms, t = [], [], 0
    with jax.default_matmul_precision("highest"):
        for key in jax.random.split(fit_key, epochs):
            perm = np.asarray(jax.random.permutation(key, steps * bs)).reshape(steps, bs)
            perm = np.concatenate(
                [perm, np.full((steps, batch - bs), blank, perm.dtype)], axis=1)
            sums = np.zeros(5)
            for idx in perm:
                t += 1
                wb = w[idx] * kept
                first, then = _step(pieces, a, fitted, m, v, t, lr, lam, x[idx], y[idx], wb)
                c1, c2 = float(jnp.sum(wb)), float(jnp.sum(wb[:, 1:]))
                first, then = float(first), float(then)
                sums += [(first + lam * then) * c1, first * c1, c1, then * c2, c2]
            history.append(sums[0] / max(sums[2], 1.0))
            terms.append((sums[1] / max(sums[2], 1.0), sums[3] / max(sums[4], 1.0)))
    return {"history": np.asarray(history, np.float64), "terms": terms,
            "model": fitted, "shape": shape}


# ---------------------------------------------------------------------------
# how far two fits are apart, parameter by parameter
# ---------------------------------------------------------------------------

def distances(ours, theirs, seed: int, shape: Tuple) -> Dict[str, List]:
    """Per parameter of every layer (``l<layer>.<name>``), of the MTP module
    (``mtp.<name>``) and of the four around them: the norm of our change
    from the seed's initial weights, of theirs, and of the difference between
    the two fits.  Taken on the device, one group at a time."""
    a = dict(shape)
    ours, theirs = as_model(a, ours), as_model(a, theirs)
    start = split(a, init_params(seed, shape)[0])
    groups = [(f"l{i}.", ours.layers[i], theirs.layers[i], start.layers[i])
              for i in range(len(ours.layers))]
    if ours.mtp:
        groups.append(("mtp.", ours.mtp, theirs.mtp, start.mtp))
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
    return Model(layers, dict(fitted.mtp), dict(fitted.around))


# ---------------------------------------------------------------------------
# cross-validation: the thresholds of the anomaly detector
# ---------------------------------------------------------------------------

def predict(params, train: np.ndarray, rows: np.ndarray, model: Dict[str, Any],
            shape: Tuple, quantize: Optional[Callable] = None,
            rotated: bool = True) -> np.ndarray:
    """Forecasts of rows 1.. of ``rows`` (scaled by ``train``'s columns) from
    the main head alone; ``params`` a :class:`Model` or the artifact's
    stacked parameters.  The sequences go through the layers a minibatch at a
    time, the last one filled up with zero sequences that are dropped again,
    so the pieces a fit compiled serve the forecast too."""
    a = dict(shape)
    context, stride = int(model["context"]), int(model["stride"])
    batch = int(model.get("batch_size", 8))
    fitted = as_model(a, params)
    x, _, _ = sequences(minmax(train, rows), rows, context, stride)
    n = x.shape[0]
    x = np.concatenate([x, np.zeros((-n % batch,) + x.shape[1:], x.dtype)])
    pieces = _pieces(shape, quantize, rotated)
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
    the same start and the same shuffle keys as the final fit, its main
    head's forecast of the held-out block's rows from the block's second on,
    the absolute error in the scale of the whole series, smoothed and
    maximised over the block; then the mean over the folds."""
    rows = np.asarray(rows, np.float32)
    span = np.maximum(rows.max(axis=0) - rows.min(axis=0), _EPS).astype(np.float64)
    per_fold = []
    for train_end, test_end in expanding_folds(rows.shape[0], n_splits):
        fitted = fit(rows, model, seed, quantize=quantize, train_rows=train_end,
                     fault=fault)
        held = rows[train_end:test_end]
        pred = predict(fitted["model"], rows[:train_end], held, model,
                       fitted["shape"], quantize, rotated=fault != "no_rotation")
        err = np.abs(pred.astype(np.float64) - held[1:].astype(np.float64)) / span
        total = np.linalg.norm(err, axis=-1, keepdims=True)
        per_fold.append(np.concatenate([smoothed_max(total), smoothed_max(err)], axis=-1))
        del fitted
    return np.mean(per_fold, axis=0)
