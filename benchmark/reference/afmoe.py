"""Plain reference of the ``afmoe`` next-row forecaster and of its fit.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision, written from the layer equations of Trinity-Mini (``model_type``
``afmoe``, https://huggingface.co/arcee-ai/Trinity-Mini) and not from the
program's factory.  It imports nothing of the program and takes nothing the
program made: its own seeded weights (the parameters' names, shapes and
initial distributions are the artifact's format: one flat dict, a kind's
parameters stacked over the layers that have it; the per-parameter keys
folded from the parameter's ordinal the way ``flax.linen`` does), its own
data copy, sequences, folds, held-out forecasts and thresholds.  Pieces that
know nothing of this model (the matmul with the control's hook, the norm,
SwiGLU, the sequences and the scaling, Adam's constants, the folds, the
rotation by cosines and sines, the sigmoid router) are the benchmark's own
accepted ones, from ``reference/lstm_ae.py``, ``reference/kimi_linear.py``
and ``reference/lfm2_moe.py``.

Layer ``l`` on the stream ``h`` (T, D), every ``RMS`` with a weight of its
own, eps 1e-5; layers are numbered as the source numbers them, and the cut
holds the source's layers 1 to ``num_layers``:

    x = RMS_in(h)
    q = x W_q (32 heads of 128);  k = x W_k, v = x W_v (4 heads of 128);  z = x W_z
    q = RMS_q(q), k = RMS_k(k)          over a head's channels, one weight vector each
    a windowed layer rotates q and k (theta 10,000, the position inside the
      sequence, channel j paired with channel j + 64); a full layer does not
    s_ij = q_i k_{g(i) j} / sqrt(128) for j <= i and, in a windowed layer,
      i - j < 2,048; -inf elsewhere;  o = softmax_j(s) v   (query head i reads
      key/value head i // 8)
    h = h + RMS_post_attn((o * sigmoid(z)) W_o)
    u = RMS_pre_ffn(h)
    f = SwiGLU_6144(u)                                       (source layers 0, 1)
    f = SwiGLU_shared(u) + sum_{e in top8(sigmoid(u W_r)), e held} w_e SwiGLU_e(u)
      with w = 2.826 * s_top8 / (sum s_top8 + 1e-20)                 (the others)
    h = h + RMS_post_ffn(f)

``h_0 = X W_in``, ``Y = RMS(h_L) W_out + b``: position i reads row r and
forecasts row r + 1.  Attention is a full masked softmax per query head
against its group's keys, computed ``QUERY_ROWS`` query rows at a time so
that 8,192 x 8,192 scores of 32 heads need not exist at once: a block of
rows is given the keys from the first one any of its rows may see to its own
last row, and the mask is taken from the rows' and keys' own positions.  The
experts are a dense loop over the held ones with a mask.

Departures from the source, each also in the configuration's file:
``vocabulary`` (no token embedding, so nothing for the source's embedding
multiplier to scale, and no head); the selection bias is a buffer held at 0
and not stored, and there is no auxiliary loss; rotary pairs are half-split;
initial weights N(0, 1 / fan-in), norms 1; the cut (``depth``: the source's
layer 1, whose dense feed-forward stands for both leading dense layers, and
layers 2-5; ``experts``: the first ``experts_held`` of 128, the absent
experts' terms left out, the shared expert whole).

The fit writes the chain rule over the parts out (one small compiled program
per kind of part, each part's own gradient by ``jax.vjp`` of its plain
forward): the whole model's step in one program would not fit a chip beside
the parameters.  ``quantize`` is the control's hook on both operands of
every matmul that the configuration computes in bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_linear import (  # model-agnostic, accepted pieces
    _draw, _einsum, _matmul, _norms, _rms_norm, _swiglu, minmax, rows_of, sequences,
)
from benchmark.reference.lfm2_moe import (  # what names no layer of that model
    AROUND, Model, embed, head, rope, routing, to_host, weighted_mse,
)
from benchmark.reference.lstm_ae import (  # noqa: F401  (re-exported for the controls)
    ADAM_B1, ADAM_B2, ADAM_EPS, _EPS, _path_key, bfloat16, expanding_folds, float8,
    smoothed_max,
)

#: the source's ``layer_types`` and ``num_dense_layers``, layers from 0
LAYER_TYPES = ("sliding_attention", "sliding_attention", "sliding_attention",
               "full_attention") * 8
NUM_DENSE_LAYERS = 2
#: the cut starts at the source's layer 1: layer 0 (a second windowed layer
#: with a dense feed-forward) is left out, "the leading dense layers counted once"
FIRST_LAYER = 1
#: the published widths (config.json of the source) and this repo's cut
PUBLISHED = dict(
    num_layers=5, hidden_size=2048, num_heads=32, num_kv_heads=4, head_dim=128,
    attn_window=2048, rope_theta=1e4, intermediate_size=6144,
    moe_intermediate_size=1024, num_experts=128, num_experts_per_token=8,
    routed_scaling_factor=2.826, route_eps=1e-20, experts_held_from=0,
    experts_held=8, rms_norm_eps=1e-5,
)
FAULTS = (None, "half_batch", "no_window", "rotated_full", "no_rotation", "no_gate",
          "no_post_norm", "no_qk_norm", "wrong_group")
#: the faults that change the forward pass (the others change the fit)
FORWARD_FAULTS = ("no_window", "rotated_full", "no_rotation", "no_gate", "no_post_norm",
                  "no_qk_norm", "wrong_group")
LEAST_EFFORT = {"exec_time_optimization_effort": -1.0}
MIXERS = ("gqa", "swa")
#: query rows whose scores exist at once (32 heads x 1,024 x 8,192 float32: 1 GiB)
QUERY_ROWS = 1024


def shape_of(model: Dict[str, Any], n_features: int, n_out: int) -> Tuple:
    """The architecture as a hashable tuple of ``(key, value)``: the
    published widths, overridden by what the configuration's ``model`` says."""
    if model["kind"] != "afmoe":
        raise ValueError(f"no reference for kind {model['kind']!r}")
    spec = {**PUBLISHED, **{k: model[k] for k in PUBLISHED if k in model}}
    spec.update(n_features=int(n_features), n_out=int(n_out))
    return tuple(sorted(spec.items()))


def kinds_of(a: Dict[str, Any], layer: int) -> Tuple[str, str]:
    """``(mixer, feed-forward)`` of held layer ``layer`` (0 is the source's
    layer ``FIRST_LAYER``): ``swa`` is attention over a window, ``gqa`` over
    the whole prefix."""
    source = FIRST_LAYER + layer
    return ("gqa" if LAYER_TYPES[source] == "full_attention" else "swa",
            "dense" if source < NUM_DENSE_LAYERS else "moe")


def layers_of(a: Dict[str, Any], kind: str) -> List[int]:
    return [layer for layer in range(a["num_layers"]) if kind in kinds_of(a, layer)]


# ---------------------------------------------------------------------------
# initial weights from the seed
# ---------------------------------------------------------------------------

def parameter_list(a: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Every parameter's name, shape and initial distribution, in the order
    the artifact's module creates them (the ordinal seeds the draw): the
    input, the layers' two input norms, each kind's stack over its layers
    (whole-prefix attention, windowed attention, the dense feed-forward, the
    experts; a part's output norm the last of its stack), the head."""
    d, n, h, kv = a["hidden_size"], a["num_layers"], a["num_heads"], a["num_kv_heads"]
    hd = a["head_dim"]
    w, e, wide = a["moe_intermediate_size"], a["experts_held"], a["intermediate_size"]
    attention = lambda kind: [  # noqa: E731
        (f"{kind}_wq", (d, h * hd), "normal"),
        (f"{kind}_wk", (d, kv * hd), "normal"),
        (f"{kind}_wv", (d, kv * hd), "normal"),
        (f"{kind}_wz", (d, h * hd), "normal"),
        (f"{kind}_q_norm", (hd,), "ones"),
        (f"{kind}_k_norm", (hd,), "ones"),
        (f"{kind}_wo", (h * hd, d), "normal"),
    ]
    by_kind = {
        "gqa": attention("gqa"),
        "swa": attention("swa"),
        "dense": [("dense_wg", (d, wide), "normal"), ("dense_wu", (d, wide), "normal"),
                  ("dense_wd", (wide, d), "normal")],
        "moe": [
            ("moe_router", (d, a["num_experts"]), "normal"),
            ("moe_shared_wg", (d, w), "normal"),
            ("moe_shared_wu", (d, w), "normal"),
            ("moe_shared_wd", (w, d), "normal"),
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
            out += [(name, (count,) + dims, how)
                    for name, dims, how in by_kind[kind] + [(kind + "_post_norm", (d,), "ones")]]
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

def _attention(a, kind: str, p, x, quantize, fault: Optional[str] = None):
    """Gated grouped-query attention on the normed stream ``x`` (B, T, D):
    ``kind`` ``"swa"`` over a window with rotary positions, ``"gqa"`` over
    the whole prefix with none.  Every query head gets its group's keys and
    values (a plain copy), and a full masked softmax against them."""
    h, kv, hd, eps = a["num_heads"], a["num_kv_heads"], a["head_dim"], a["rms_norm_eps"]
    b, t, _ = x.shape
    w = lambda name: p[f"{kind}_{name}"]  # noqa: E731
    q = _matmul(x, w("wq"), quantize).reshape(b, t, h, hd)
    k = _matmul(x, w("wk"), quantize).reshape(b, t, kv, hd)
    v = _matmul(x, w("wv"), quantize).reshape(b, t, kv, hd)
    if fault != "no_qk_norm":
        q, k = _rms_norm(q, w("q_norm"), eps), _rms_norm(k, w("k_norm"), eps)
    rotated = kind == "swa" or fault == "rotated_full"
    if rotated and fault != "no_rotation":
        q, k = rope(q, a["rope_theta"]), rope(k, a["rope_theta"])
    window = a["attn_window"] if kind == "swa" and fault != "no_window" else None
    group = h // kv
    mine = np.asarray([i % kv if fault == "wrong_group" else i // group for i in range(h)])
    k, v = k[:, :, mine], v[:, :, mine]                  # (B, T, heads, hd)

    def attend(q_rows, k_seen, v_seen, seen):
        scores = _einsum("bthc,bshc->bhts", q_rows, k_seen, quantize) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("bhts,bshv->bthv", probs, v_seen, quantize)

    # a block of query rows at a time, each recomputed in the backward pass:
    # memory forces it; rows do not see each other
    out = []
    for lo in range(0, t, QUERY_ROWS):
        hi = min(lo + QUERY_ROWS, t)
        first = 0 if window is None else max(0, lo - window + 1)
        i, j = np.arange(lo, hi)[:, None], np.arange(first, hi)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        out.append(jax.checkpoint(attend)(
            q[:, lo:hi], k[:, first:hi], v[:, first:hi], jnp.asarray(seen)))
    o = jnp.concatenate(out, axis=1).reshape(b, t, h * hd)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(_matmul(x, w("wz"), quantize))
    return _matmul(o, w("wo"), quantize)


def _experts(a, p, x, quantize, held: Optional[Tuple[int, int]] = None):
    """The shared expert plus the routed sum over the held experts.  ``held``
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
    return y + _swiglu(x, p["moe_shared_wg"], p["moe_shared_wu"], p["moe_shared_wd"], quantize)


def part(a: Dict[str, Any], kind: str, p: Dict[str, Any], h, quantize,
         fault: Optional[str] = None, held: Optional[Tuple[int, int]] = None):
    """One residual part of a block on the stream ``h`` (B, T, D), normed on
    both sides: ``h + RMS_post(Mixer(RMS(h)))`` for ``swa`` or ``gqa``, ``h +
    RMS_post(FFN(RMS(h)))`` for ``dense`` or ``moe``; ``p`` are the block's
    own parameters."""
    eps = a["rms_norm_eps"]
    z = _rms_norm(h, p["mixer_norm" if kind in MIXERS else "ffn_norm"], eps)
    if kind in MIXERS:
        y = _attention(a, kind, p, z, quantize, fault)
    elif kind == "dense":
        y = _swiglu(z, p["dense_wg"], p["dense_wu"], p["dense_wd"], quantize)
    else:
        y = _experts(a, p, z, quantize, held)
    if fault != "no_post_norm":
        y = _rms_norm(y, p[kind + "_post_norm"], eps)
    return h + y


def own(kind: str, p: Dict[str, Any]) -> Dict[str, Any]:
    """The parameters of a block that its part of ``kind`` reads."""
    norm = "mixer_norm" if kind in MIXERS else "ffn_norm"
    return {name: value for name, value in p.items()
            if name == norm or name.startswith(kind + "_")}


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


def loss(params: Dict[str, Any], x, y, w, shape: Tuple,
         quantize: Optional[Callable] = None, fault: Optional[str] = None):
    """The weighted mean squared error of one minibatch by the plain forward:
    what ``jax.grad`` differentiates in the tests; the fit below computes the
    same thing part by part."""
    return weighted_mse(forward(params, x, shape, quantize, fault), y, w)


# ---------------------------------------------------------------------------
# the fit: Adam over shuffled minibatches of sequences, part by part
# ---------------------------------------------------------------------------

def split(a: Dict[str, Any], params: Dict[str, Any]) -> Model:
    """The artifact's stacked parameters as a :class:`Model`."""
    return Model([layer_of(a, params, layer) for layer in range(a["num_layers"])],
                 {name: params[name] for name in AROUND})


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
    (``benchmark/backbone_control.py``; ``tests/test_backbone_afmoe.py`` at
    the tiny size): ``"half_batch"`` leaves the second half of every
    minibatch out of the loss (of a minibatch of one sequence, the second
    half of its positions); ``"no_window"`` lets a windowed layer attend to its
    whole prefix; ``"rotated_full"`` rotates the full layer too;
    ``"no_rotation"`` leaves the rotary positions out; ``"no_gate"`` the
    output gate; ``"no_post_norm"`` the norms of the parts' outputs;
    ``"no_qk_norm"`` the heads' norms; ``"wrong_group"`` lets query head
    ``i`` read key/value head ``i % 4``."""
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
    kept = np.ones((batch, context), np.float32)
    if fault == "half_batch" and batch > 1:
        kept[batch // 2:] = 0.0
    elif fault == "half_batch":              # one sequence a minibatch: its later rows
        kept[:, context // 2:] = 0.0

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
