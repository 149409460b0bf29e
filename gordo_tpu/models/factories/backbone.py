"""Sequence backbones from current open models' blocks: ``kimi_linear``,
``glm_moe_lite`` and ``lfm2_moe``.

No reference equivalent: upstream's factories are Keras feed-forward and
LSTM stacks.  These are the blocks of Kimi-Linear-48B-A3B (``model_type``
``kimi_linear``, arXiv:2510.26692), of GLM-4.7-Flash (``model_type``
``glm4_moe_lite``) and of LFM2-24B-A2B (``model_type`` ``lfm2_moe``) as the
encoder of a per-machine forecaster: input ``(S, T, F)`` scaled sensor rows,
output ``(S, T, F_out)`` where position t forecasts row t + 1.  The token
embedding and the language model head have no counterpart for real-valued
rows, so ``h_0 = X W_in`` and ``Y = RMSNorm(h_L) W_out + b``.  One module
(:class:`SequenceBackbone`), one :class:`BackboneConfig` and one
:func:`forward` serve every kind; a kind is a preset of the configuration's
keywords and nothing selects a path.

Every block is pre-norm residual: ``h += Mixer(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``.  Layers are numbered from 1 here, for every kind (GLM's
and LFM2's sources number their own from 0).  Which mixer a layer has is
data: ``BackboneConfig.pattern``, one of ``MIXER_KINDS`` a layer.
``kimi_linear``: every fourth layer's mixer is MLA, the others' KDA.
``glm_moe_lite``: every layer's is MLA (both derive the pattern from
``full_attn_every``).  ``lfm2_moe``: the source's ``layer_types`` from its
layer 1 on, a gated short convolution in three layers of four and
grouped-query attention in the fourth (``layer_pattern``).  The leading
``first_k_dense_replace`` layers' feed-forward is dense, the others' is the
expert layer.

- **KDA** (Kimi Delta Attention): ``q, k, v`` each through a depthwise causal
  convolution and SiLU, ``q, k`` L2-normalised per head; a per-channel
  forget gate ``g_t = -exp(A_log) softplus(W_f x_t + dt_bias)``, a per-head
  write strength ``beta_t``; state ``S_t = (I - beta_t k_t k_t^T)
  Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T`` and ``o_t = S_t^T q_t / sqrt(d_k)``.
  Computed chunk-wise (:func:`kda_chunked`): within a chunk the delta rule
  in matrix form, a unit lower triangular system solved by multiplying with
  its inverse, which is built block by block from batched products
  (:func:`_unit_lower_solve`); between chunks the state through a
  ``lax.scan``.
- **MLA** (:func:`mla_mixer`): keys and values from a normalised latent
  (``kv_lora_rank``), ``qk_rope_head_dim`` key channels shared by all heads,
  a causal softmax over ``(q_n k_n + q_r k_r) / sqrt(d_nope + d_rope)``
  (:func:`_causal_core`).  ``kimi_linear``: queries from one matrix, the
  shared channels carried without rotation (NoPE).  ``glm_moe_lite``:
  queries through a low-rank pair with a norm between (``q_lora_rank``),
  rotary positions on ``q_r`` and the shared ``k_r`` (``rope_theta``; the
  position counted inside the sequence, pairs half-split), values wider than
  the keys' own channels.
- **Gated short convolution** (:func:`conv_mixer`, ``lfm2_moe``): ``[B ; C ;
  X] = x W_in``, ``y = (C * conv(B * X)) W_out`` with a depthwise causal
  convolution of ``short_conv_kernel_size`` taps (:func:`short_conv`, as
  KDA's); no activation, no state beyond the taps' rows.  Half matrix
  products, half element-wise traffic over ``(positions, 3 D)`` arrays.
- **GQA** (:func:`gqa_mixer`, ``lfm2_moe``): ``num_heads`` query heads over
  ``num_kv_heads`` key/value heads, queries and keys each through an RMSNorm
  over a head's channels and then rotated on all of them; query head ``i``
  reads key/value head ``i // (num_heads / num_kv_heads)``
  (:func:`_grouped_core`, which contracts a key/value head against its group
  of query heads without repeating keys or values).
- **The causal cores**, latent and grouped, share ONE rule
  (:func:`_query_blocks`, :func:`_attend`): query blocks of ``MLA_BLOCK``
  rows, each against the prefix of keys it may see; one block, the whole
  masked square, where the sequence is no longer than a block or no multiple.
- **Expert layer** (:func:`expert_layer`): a sigmoid router over ALL the
  model's experts, the ``num_experts_per_token`` largest kept and
  renormalised (over their sum + ``route_eps``); the module is told which
  contiguous range of experts it holds and computes the sum over the
  selected experts it holds (the absent experts' terms are left out, as one
  chip of an expert-parallel deployment would before the exchange), plus the
  shared expert where the model has one (``num_shared_experts`` 0: none is
  stored or computed).  Positions are sorted by expert and multiplied
  through ``lax.ragged_dot``: no capacity, so no pair is ever dropped.  The
  selection bias of the sources is a buffer held at 0 and is not stored.
- **MTP module** (:func:`_mtp`, ``glm_moe_lite``; parameters ``mtp_*``,
  named scope ``backbone.mtp``): ``h'_i = W_eh [RMSNorm(h_{L,i});
  RMSNorm(h_{0,i+1})]``, one whole block of the expert-layer form with its
  own weights, then the main head's ``W_out, b`` behind the module's own
  norm: position i forecasts the row AFTER next from rows up to the next.
  Run in a training pass alone (``forward(..., mtp=True)``), where
  ``train.fit.make_loss_fn`` adds its term at weight ``mtp_weight``;
  prediction never runs it.

Parameters are float32; matmul operands are cast to ``compute_dtype``
(bfloat16 on a TPU under ``auto``) and accumulated in float32; norms, the
rotation, softmax, router scores, the KDA state and its decays, the loss and
the optimiser are float32.

Parameters of one kind of part are stacked over the layers that have it
(``kda_wq`` is ``(KDA layers, D, H dk)``, ``conv_win`` ``(convolution layers,
D, 3 D)``, ``moe_wg`` ``(expert layers, held, D, W)``, the two norms
``(layers, D)``, the module's ``(1, ...)``).  The leading dense layers are
traced one by one and the expert layers run as ONE ``lax.scan`` over their
stacked parameters, whose body chooses its layer's mixer among the kinds
those layers have (:func:`_choose`: a ``lax.cond`` where they are of two
kinds) and traces the one kind where they are not: the program holds the
expert layer and each of the scan's mixers once however many layers there
are, which is what keeps a
model of this size compilable in a build's set-up and its executable in a
compile cache.  (A ``lax.cond`` between the dense and the expert
feed-forward would put layer 1 into the scan too; the TPU compiler's
conditional code motion crashes on its gradient at the published widths,
PERF.md section 6.)  Each feed-forward is under ``jax.checkpoint`` and each
mixer's backward is written out (:func:`_mixer`), so backward keeps the
residual stream alone and recomputes each part once; a mixer reads
``mixer_group`` sequences at a time.

The module has no packed layout (``train.fit.packed_layout`` is False for
it: it has no ``pack``), and it asks the fleet program to run machines one
after another (``fleet_axis = "map"``): one model fills the chip.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import telemetry
from gordo_tpu.models.factories.feedforward import resolve_compute_dtype
from gordo_tpu.registry import register_model_builder

F32 = jnp.float32
#: bound on a decay exponent taken against the middle of a chunk; only a
#: channel that forgets by more than e^-80 within half a chunk reaches it
_EXP_CLIP = 80.0


@dataclasses.dataclass(frozen=True)
class BackboneConfig:
    """Shapes of the block, hashable (the module is a static jit argument)."""

    n_features: int
    n_features_out: int
    num_layers: int = 5
    hidden_size: int = 2304
    num_heads: int = 32
    kda_head_dim: int = 128
    kda_gate_rank: int = 128          # low-rank width of W_f and W_g
    short_conv_kernel_size: int = 4
    kda_chunk: int = 64
    mixer_group: int = 2              # sequences a mixer reads at a time
    full_attn_every: int = 4          # layers 4, 8, ... are MLA
    #: the mixer of every layer held, in order ("kda", "mla", "conv", "gqa");
    #: empty: MLA at every ``full_attn_every``-th layer and KDA at the others
    layer_pattern: Tuple[str, ...] = ()
    num_kv_heads: int = 8             # GQA: key/value heads, a divisor of num_heads
    q_lora_rank: int = 0              # 0: queries from one matrix
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64        # rotated only where rope_theta says so
    v_head_dim: int = 128
    rope_theta: float = 0.0           # 0: carried without rotation (NoPE)
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    route_eps: float = 1e-20          # added to the selected scores' sum
    experts_held_from: int = 0
    experts_held: int = 8
    rms_norm_eps: float = 1e-5
    mtp_depth: int = 0                # multi-token-prediction modules (0 or 1)
    mtp_weight: float = 0.0           # lambda of the second loss term
    compute_dtype: Any = jnp.float32

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The mixer of layer 1, 2, ...: ``layer_pattern`` where it is given."""
        return self.layer_pattern or tuple(
            "mla" if layer % self.full_attn_every == 0 else "kda"
            for layer in range(1, self.num_layers + 1))

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        """The kinds of mixer the pattern can give, sorted: those it names,
        or both that ``full_attn_every``'s rule chooses between."""
        return tuple(sorted(set(self.layer_pattern))) or ("kda", "mla")

    @property
    def gqa_head_dim(self) -> int:
        """A grouped-query head's width: the source gives none of its own."""
        return self.hidden_size // self.num_heads

    def mixer(self, layer: int) -> str:
        return self.pattern[layer - 1]

    def ffn(self, layer: int) -> str:
        return "dense" if layer <= self.first_k_dense_replace else "moe"

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return self.layers_of("moe")

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers (numbered from 1) whose mixer or feed-forward is
        ``kind``, in order: a layer's place here is its slot in the stack."""
        return tuple(l for l in range(1, self.num_layers + 1)
                     if kind in (self.mixer(l), self.ffn(l)))

    @property
    def moe_labels(self) -> Tuple[str, ...]:
        """What the rows of a training pass's ``tokens`` count are called:
        the expert layers by their numbers here (from 1), then the MTP
        module's expert layer as a layer of its own."""
        return tuple(str(l) for l in self.moe_layers) + ("mtp",) * self.mtp_depth


# ---------------------------------------------------------------------------
# parameters: one flat dict, created in this order
# ---------------------------------------------------------------------------

#: the kinds of mixer, and the kinds of part a layer is made of (a mixer and
#: a feed-forward), in the order their parameters are created
MIXER_KINDS = ("kda", "mla", "conv", "gqa")
KINDS = MIXER_KINDS + ("dense", "moe")


def param_specs(cfg: BackboneConfig) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(name, shape, init)`` of every parameter, in creation order; the
    leading axis of a ``kda_`` / ``mla_`` / ``conv_`` / ``gqa_`` / ``dense_``
    / ``moe_`` parameter runs over the layers of that kind
    (:meth:`BackboneConfig.layers_of`), a kind no layer has is left out, and
    so are the shared expert's three matrices where the model has none; the
    ``mtp_`` parameters (the module's two input norms and ``W_eh``, one whole
    MLA + expert block, its output norm) are stacked over the MTP modules.  ``init``: ``fan_in`` (normal, std
    ``shape[-2] ** -0.5``; a convolution's fan-in is its width), ``ones``,
    ``zeros``, ``a_log`` (log of uniform(1, 16)), ``dt_bias`` (inverse
    softplus of a step drawn log-uniformly from [1e-3, 1e-1])."""
    d, h = cfg.hidden_size, cfg.num_heads
    dk, r = cfg.kda_head_dim, cfg.kda_gate_rank
    qk, qr = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.q_lora_rank
    conv = cfg.short_conv_kernel_size
    w, e = cfg.moe_intermediate_size, cfg.experts_held
    ws = w * cfg.num_shared_experts
    kv, hd = cfg.num_kv_heads, cfg.gqa_head_dim
    shared = [
        ("moe_shared_wg", (d, ws), "fan_in"),
        ("moe_shared_wu", (d, ws), "fan_in"),
        ("moe_shared_wd", (ws, d), "fan_in"),
    ] if ws else []
    # the queries: one matrix, or a low-rank pair with a norm between
    queries = [("mla_wq", (d, h * qk), "fan_in")] if not qr else [
        ("mla_wq_a", (d, qr), "fan_in"),
        ("mla_q_norm", (qr,), "ones"),
        ("mla_wq_b", (qr, h * qk), "fan_in"),
    ]
    by_kind = {
        "kda": [
            ("kda_wq", (d, h * dk), "fan_in"),
            ("kda_wk", (d, h * dk), "fan_in"),
            ("kda_wv", (d, h * dk), "fan_in"),
            ("kda_conv_q", (conv, h * dk), "fan_in"),
            ("kda_conv_k", (conv, h * dk), "fan_in"),
            ("kda_conv_v", (conv, h * dk), "fan_in"),
            ("kda_wf_down", (d, r), "fan_in"),
            ("kda_wf_up", (r, h * dk), "fan_in"),
            ("kda_a_log", (h,), "a_log"),
            ("kda_dt_bias", (h * dk,), "dt_bias"),
            ("kda_wbeta", (d, h), "fan_in"),
            ("kda_wg_down", (d, r), "fan_in"),
            ("kda_wg_up", (r, h * dk), "fan_in"),
            ("kda_out_norm", (dk,), "ones"),
            ("kda_wo", (h * dk, d), "fan_in"),
        ],
        "mla": queries + [
            ("mla_wkv_a", (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), "fan_in"),
            ("mla_kv_norm", (cfg.kv_lora_rank,), "ones"),
            ("mla_wkv_b", (cfg.kv_lora_rank,
                           h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), "fan_in"),
            ("mla_wo", (h * cfg.v_head_dim, d), "fan_in"),
        ],
        "conv": [
            ("conv_win", (d, 3 * d), "fan_in"),        # [B ; C ; X]
            ("conv_taps", (conv, d), "fan_in"),
            ("conv_wout", (d, d), "fan_in"),
        ],
        "gqa": [
            ("gqa_wq", (d, h * hd), "fan_in"),
            ("gqa_wk", (d, kv * hd), "fan_in"),
            ("gqa_wv", (d, kv * hd), "fan_in"),
            ("gqa_q_norm", (hd,), "ones"),
            ("gqa_k_norm", (hd,), "ones"),
            ("gqa_wo", (h * hd, d), "fan_in"),
        ],
        "dense": [
            ("dense_wg", (d, cfg.intermediate_size), "fan_in"),
            ("dense_wu", (d, cfg.intermediate_size), "fan_in"),
            ("dense_wd", (cfg.intermediate_size, d), "fan_in"),
        ],
        "moe": [
            ("moe_router", (d, cfg.num_experts), "fan_in"),
            *shared,
            ("moe_wg", (e, d, w), "fan_in"),
            ("moe_wu", (e, d, w), "fan_in"),
            ("moe_wd", (e, w, d), "fan_in"),
        ],
    }
    specs: List[Tuple[str, Tuple[int, ...], str]] = [
        ("in_proj", (cfg.n_features, d), "fan_in"),
        ("mixer_norm", (cfg.num_layers, d), "ones"),
        ("ffn_norm", (cfg.num_layers, d), "ones"),
    ]
    for kind in KINDS:
        n = len(cfg.layers_of(kind))
        if n:
            specs += [(name, (n,) + shape, init) for name, shape, init in by_kind[kind]]
    specs += [
        ("out_norm", (d,), "ones"),
        ("out_proj", (d, cfg.n_features_out), "fan_in"),
        ("out_bias", (cfg.n_features_out,), "zeros"),
    ]
    if cfg.mtp_depth:
        # created last: the layers' draws are those of a model without it
        module = [
            ("norm_h", (d,), "ones"),
            ("norm_e", (d,), "ones"),
            ("weh", (2 * d, d), "fan_in"),
            ("mixer_norm", (d,), "ones"),
            ("ffn_norm", (d,), "ones"),
        ] + by_kind["mla"] + by_kind["moe"] + [("out_norm", (d,), "ones")]
        specs += [("mtp_" + name, (cfg.mtp_depth,) + shape, init)
                  for name, shape, init in module]
    return specs


def _initializer(kind: str):
    def init(key, shape, dtype=F32):
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "zeros":
            return jnp.zeros(shape, dtype)
        if kind == "fan_in":
            return jax.random.normal(key, shape, dtype) * (shape[-2] ** -0.5)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, dtype, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        raise ValueError(kind)

    return init


# ---------------------------------------------------------------------------
# pure pieces
# ---------------------------------------------------------------------------

def _mm(x, w, cd):
    """``x @ w`` with operands in the compute dtype, accumulated in float32."""
    return jnp.matmul(x.astype(cd), w.astype(cd), preferred_element_type=F32)


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def swiglu(x, wg, wu, wd, cd):
    return _mm(jax.nn.silu(_mm(x, wg, cd)) * _mm(x, wu, cd), wd, cd)


def short_conv(x, w):
    """Depthwise causal convolution over time: ``x`` (B, T, C), ``w`` (K, C);
    ``y_t = sum_j w[j] x_{t-K+1+j}`` with zeros before the sequence."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[j] for j in range(k))


def l2_normalize(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


_KDA_SOLVE = telemetry.counter(
    "gordo_kda_solve_total",
    "Solves of KDA's within-chunk triangular system traced, by the rule that "
    "gives them: block_inverse (the inverse built block by block and "
    "multiplied with, _unit_lower_solve)",
    labels=("rule",),
)
_SOLVE_SCOPE = "backbone.kda.scan.solve"


def _mm32(a, b):
    """``a @ b`` in float32, operands unrounded."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _lane_mm(a, b):
    """``a @ b`` for stacks of small matrices ``(s, s, N)`` whose batch is
    last: multiplied and summed in float32 on the vector unit."""
    return jnp.sum(a[:, :, None, :] * b[None, :, :, :], axis=1)


def _unit_lower_inverse(A):
    """``(I + tril(A, -1))^-1`` for a stack ``A`` (n, n, N) of matrices, ``n``
    a power of two.  ``[[L11, 0], [L21, L22]]^-1 = [[T11, 0], [-T22 L21 T11,
    T22]]``: the two diagonal blocks are put side by side on the batch axis
    and inverted together, so a level is two batched products however many
    blocks it has, and ``log2 n`` levels build the inverse.  Only inverses of
    diagonal blocks are multiplied with, as substitution does; the series
    ``prod (I + (-A)^(2^j))`` cancels to nothing in float32 where
    neighbouring keys are nearly parallel.

    The batch is the LAST axis: the blocks of the lower levels are 1, 2, 4
    wide, and a TPU pads the last two axes of an array to (8, 128), so with
    the batch in front a level moves 64 times its data and the six levels
    take 1.4 ms for 1,024 matrices of 64; with the batch in the lanes they
    take 0.3 ms (PERF.md section 6, PR 33)."""
    n, _, batch = A.shape
    if n == 1:
        return jnp.ones_like(A)
    h = n // 2
    T = _unit_lower_inverse(jnp.concatenate([A[:h, :h], A[h:, h:]], axis=-1))
    T11, T22 = T[..., :batch], T[..., batch:]
    T21 = -_lane_mm(_lane_mm(T22, A[h:, :h]), T11)
    top = jnp.concatenate([T11, jnp.zeros_like(T11)], axis=1)
    return jnp.concatenate([top, jnp.concatenate([T21, T22], axis=1)], axis=0)


def _solve_fwd(A, rhs):
    with jax.named_scope(_SOLVE_SCOPE):
        n = A.shape[-1]
        size = 1 << (n - 1).bit_length()
        pad = [(0, 0)] * (A.ndim - 2) + [(0, size - n)] * 2
        lanes = jnp.moveaxis(jnp.pad(A, pad).reshape((-1, size, size)), 0, -1)
        T = jnp.moveaxis(_unit_lower_inverse(lanes)[:n, :n], -1, 0).reshape(A.shape)
        # runs where the solve is traced
        _KDA_SOLVE.inc(1.0, "block_inverse")
        telemetry.add_to_span(
            kda_solve_traces=1, kda_solve_levels=size.bit_length() - 1)
        U = _mm32(T, rhs)
    return U, (T, U)


def _solve_bwd(res, dU):
    T, U = res
    with jax.named_scope(_SOLVE_SCOPE):
        d_rhs = _mm32(jnp.swapaxes(T, -1, -2), dU)
        dA = -_mm32(d_rhs, jnp.swapaxes(U, -1, -2))
        n = dA.shape[-1]
        dA = jnp.where(jnp.tri(n, k=-1, dtype=bool), dA, 0.0)
    return dA, d_rhs


@jax.custom_vjp
def _unit_lower_solve(A, rhs):
    """``U`` with ``(I + tril(A, -1)) U = rhs``: ``A`` (..., n, n) float32,
    read below its diagonal only, ``rhs`` (..., n, m) float32.

    Exact, in float32: the inverse ``T`` is built bottom-up
    (:func:`_unit_lower_inverse`; an ``n`` that is no power of two is padded
    with rows of the identity up to the next one) and ``U = T rhs`` is one
    matmul at the highest precision.  Backward reads the same ``T`` and
    ``U``: ``d_rhs = T^T dU``, ``dA = -tril(d_rhs U^T, -1)``, two matmuls
    and no second solve.  Both rules run under the named scope
    ``backbone.kda.scan.solve``."""
    return _solve_fwd(A, rhs)[0]


_unit_lower_solve.defvjp(_solve_fwd, _solve_bwd)


def kda_chunked(q, k, v, g, beta, chunk: int, cd):
    """The gated delta rule over whole sequences, chunk by chunk.

    ``q, k`` (B, H, T, dk), ``v`` (B, H, T, dv), ``g`` (B, H, T, dk) the log
    of the per-channel decay (<= 0), ``beta`` (B, H, T); ``q`` arrives
    scaled.  Returns ``o`` (B, H, T, dv), float32.  ``T`` is a multiple of
    ``chunk``.

    With ``G`` the running sum of ``g`` inside a chunk and ``S0`` the state
    entering it, ``S_t = Diag(e^{G_t}) S0 + sum_{s<=t} Diag(e^{G_t-G_s}) k_s
    u_s^T`` where the pseudo-values ``U`` solve ``(I + Diag(beta)
    tril(A, -1)) U = Diag(beta) (V - (K e^G) S0)``, ``A_ts = sum_c k_tc k_sc
    e^{G_tc - G_sc}`` (:func:`_unit_lower_solve`).  ``A`` is formed as a
    product of two factors taken against the chunk's middle row, so that
    neither exponent exceeds half a chunk's decay; the exponents are clipped
    at +-80, which only a channel that forgets by more than e^-80 within
    half a chunk can reach."""
    b, h, t, dk = k.shape
    dv = v.shape[-1]
    nc = t // chunk
    shape = lambda a: a.reshape(b, h, nc, chunk, *a.shape[3:])  # noqa: E731
    q, k, v, g, beta = (shape(a.astype(F32)) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)                                  # inclusive
    m = max(chunk // 2 - 1, 0)
    mid = G[:, :, :, m: m + 1]
    e_plus = jnp.exp(jnp.clip(G - mid, -_EXP_CLIP, _EXP_CLIP))
    e_minus = jnp.exp(jnp.clip(mid - G, -_EXP_CLIP, _EXP_CLIP))
    k_minus = (k * e_minus).astype(cd)
    pair = lambda a: jnp.einsum(  # noqa: E731
        "bhntc,bhnsc->bhnts", a.astype(cd), k_minus, preferred_element_type=F32)
    rows = jnp.arange(chunk)
    strict = rows[:, None] > rows[None, :]
    lower = rows[:, None] >= rows[None, :]
    A = jnp.where(strict, pair(k * e_plus), 0.0) * beta[..., None]
    Aqk = jnp.where(lower, pair(q * e_plus), 0.0)
    decay = jnp.exp(G)                                         # from chunk start
    rhs = jnp.concatenate([v, k * decay], axis=-1) * beta[..., None]
    solved = _unit_lower_solve(A, rhs)
    U0, W = solved[..., :dv], solved[..., dv:]
    G_end = G[:, :, :, -1:]
    k_end = k * jnp.exp(G_end - G)                             # to chunk end
    q_start = q * decay

    def step(S, xs):
        U0_n, W_n, Aqk_n, q_n, k_n, d_n = xs
        Sc = S.astype(cd)
        U = U0_n - jnp.matmul(W_n.astype(cd), Sc, preferred_element_type=F32)
        o = jnp.matmul(q_n.astype(cd), Sc, preferred_element_type=F32) + jnp.matmul(
            Aqk_n.astype(cd), U.astype(cd), preferred_element_type=F32)
        S = S * d_n[..., None] + jnp.einsum(
            "bhtc,bhtv->bhcv", k_n.astype(cd), U.astype(cd),
            preferred_element_type=F32)
        return S, o

    by_chunk = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    S0 = jnp.zeros((b, h, dk, dv), F32)
    xs = tuple(by_chunk(a) for a in (
        U0, W, Aqk, q_start, k_end, jnp.exp(G_end[:, :, :, 0])))
    _, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, dv)


def kda_mixer(cfg: BackboneConfig, p: Dict[str, Any], x):
    cd, h, dk = cfg.compute_dtype, cfg.num_heads, cfg.kda_head_dim
    b, t, _ = x.shape
    chunk = min(cfg.kda_chunk, t)
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the KDA chunk {chunk}")
    heads = lambda a: a.reshape(b, t, h, dk).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = (
        heads(jax.nn.silu(short_conv(_mm(x, p[f"kda_w{n}"], cd), p[f"kda_conv_{n}"])))
        for n in "qkv"
    )
    q = l2_normalize(q) * (dk ** -0.5)
    k = l2_normalize(k)
    f = _mm(_mm(x, p["kda_wf_down"], cd), p["kda_wf_up"], cd) + p["kda_dt_bias"]
    g = -jnp.exp(p["kda_a_log"])[None, :, None, None] * heads(jax.nn.softplus(f))
    beta = jax.nn.sigmoid(_mm(x, p["kda_wbeta"], cd)).transpose(0, 2, 1)
    with jax.named_scope("backbone.kda.scan"):
        o = kda_chunked(q, k, v, g, beta, chunk, cd)
    gate = jax.nn.sigmoid(_mm(_mm(x, p["kda_wg_down"], cd), p["kda_wg_up"], cd))
    o = rms_norm(o, p["kda_out_norm"], cfg.rms_norm_eps)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dk) * gate
    return _mm(o, p["kda_wo"], cd)


def rotary(t: int, width: int, theta: float):
    """``(cos, sin)`` each (t, width / 2) of the rotary angles: position
    ``i`` (counted inside the sequence) turns pair ``j`` by ``i *
    theta^(-2j / width)``.  Float32."""
    inverse = theta ** (-jnp.arange(0, width, 2, dtype=F32) / width)
    angle = jnp.arange(t, dtype=F32)[:, None] * inverse[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, cos, sin):
    """Rotary positions on the last axis of ``x``: channel ``j`` of the
    first half is paired with channel ``j`` of the second (half-split; the
    adjacent-pairs convention is a fixed permutation of the channels away,
    which random weights cannot tell apart).  ``cos``, ``sin`` broadcast
    against ``x``'s halves."""
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


_MLA_ATTENTION = telemetry.counter(
    "gordo_mla_attention_total",
    "Causal cores of latent attention traced, by the rule that gives them: "
    "causal_blocks (query blocks against their key prefixes, _causal_core), "
    "whole (one block: the whole square, masked)",
    labels=("rule",),
)
#: query rows a block of the causal core takes.  Smaller is faster on the
#: chip (the core alone at the GLM cell's shape: 11.5 ms whole, 8.0 in blocks
#: of 1,024, 5.7 of 512, 4.7 of 256; scripts/mla_core_chip.py) and dearer to
#: set up: every block is a shape of its own in each of the eleven places a
#: program traces a core.  At 256 a build compiled anew took 20 s longer to
#: its first program, at 512 ten (PERF.md section 6, PR 36)
MLA_BLOCK = 512


_GQA_ATTENTION = telemetry.counter(
    "gordo_gqa_attention_total",
    "Causal cores of grouped-query attention traced, by the rule that gives "
    "them: causal_blocks (query blocks against their key prefixes, "
    "_grouped_core), whole (one block: the whole square, masked)",
    labels=("rule",),
)


def _query_blocks(t: int, counter, prefix: str) -> List[Tuple[int, int]]:
    """The block rule of every causal core, latent or grouped: the ``(lo,
    hi)`` rows of each query block; block ``i`` attends to the keys before
    ``hi``.  ``t // MLA_BLOCK`` blocks; one, the whole square, where ``t`` is
    no longer than a block or no multiple of one.  Counts the core on
    ``counter`` and on the enclosing span (``<prefix>_attn_*``): runs where
    the core is traced."""
    n = t // MLA_BLOCK if t % MLA_BLOCK == 0 else 1
    counter.inc(1.0, "causal_blocks" if n > 1 else "whole")
    telemetry.add_to_span(**{
        f"{prefix}_attn_traces": 1, f"{prefix}_attn_blocks": n,
        f"{prefix}_attn_pairs_computed": n * (n + 1) // 2,
        f"{prefix}_attn_pairs_square": n * n})
    return [(i * (t // n), (i + 1) * (t // n)) for i in range(n)]


def _attend(spans, scores, scale: float, values):
    """The causal softmax of every query block and its product with the
    values: ``scores`` yields block ``(lo, hi)``'s unscaled scores ``(...,
    hi - lo, hi)``, of which only the last ``hi - lo`` columns hold masked
    pairs; ``values(probs, hi)`` multiplies a block's weights with the first
    ``hi`` values and returns ``(b, hi - lo, ...)``.  A row's softmax is over
    exactly the entries it has in the whole square (the masked ones weigh
    ``exp(-inf) = 0`` there), so the blocks are the square's arithmetic."""
    blocks = []
    for (lo, hi), block in zip(spans, scores):
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        block = jnp.where(causal, block * scale, -jnp.inf)
        blocks.append(values(jax.nn.softmax(block, axis=-1), hi))
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def _causal_core(cfg: BackboneConfig, q, k_n, k_r, v):
    """Causal softmax attention ``(b, t, heads, dv)``, float32: queries ``q``
    (b, t, heads, dn + dr), the keys' own channels ``k_n`` (b, t, heads, dn),
    the channels all heads share ``k_r`` (b, t, dr), rotated here with the
    queries' last ``dr`` where ``rope_theta`` is set, values ``v`` (b, t,
    heads, dv); scores ``(q_n k_n + q_r k_r) / sqrt(dn + dr)``.

    Computed in query blocks, each against the prefix of keys it may see
    (:func:`_query_blocks`, :func:`_attend`): a block's scores are ``(b,
    heads, B, (i + 1) B)``, and no fully masked block is multiplied,
    exponentiated, stored or differentiated.  One block is the whole square,
    operation for operation the program it was before there were blocks
    (which is why every block's first product comes before the rotation).
    Keys and values are cast to the compute dtype block by block, so that
    the blocks' gradients for them are summed in float32."""
    cd, dn, dr = cfg.compute_dtype, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    spans = _query_blocks(q.shape[1], _MLA_ATTENTION, "mla")
    q_n = q[..., :dn]
    own = [jnp.einsum("bthc,bshc->bhts", q_n[:, lo:hi].astype(cd), k_n[:, :hi].astype(cd),
                      preferred_element_type=F32) for lo, hi in spans]
    q_r = q[..., dn:]
    if cfg.rope_theta:
        cos, sin = rotary(q.shape[1], dr, cfg.rope_theta)
        q_r = rotate(q_r, cos[:, None, :], sin[:, None, :])
        k_r = rotate(k_r, cos, sin)
    scores = (
        first + jnp.einsum("bthc,bsc->bhts", q_r[:, lo:hi].astype(cd), k_r[:, :hi].astype(cd),
                           preferred_element_type=F32)
        for (lo, hi), first in zip(spans, own))
    return _attend(
        spans, scores, (dn + dr) ** -0.5,
        lambda probs, hi: jnp.einsum("bhts,bshv->bthv", probs.astype(cd), v[:, :hi].astype(cd),
                                     preferred_element_type=F32))


def _grouped_core(cfg: BackboneConfig, q, k, v):
    """Causal softmax attention ``(b, t, heads, hd)``, float32, for grouped
    keys and values: queries ``q`` (b, t, heads, hd), ``k`` and ``v`` (b, t,
    kv, hd), all rotated and normalised already; query head ``i`` reads
    key/value head ``i // (heads / kv)``; scores ``q k / sqrt(hd)``.  The
    block rule is :func:`_causal_core`'s (:func:`_query_blocks`,
    :func:`_attend`).  A key/value head is contracted against its group of
    query heads in one product: keys and values are never repeated."""
    cd = cfg.compute_dtype
    b, t, h, hd = q.shape
    kv = k.shape[2]
    spans = _query_blocks(t, _GQA_ATTENTION, "gqa")
    q = q.reshape(b, t, kv, h // kv, hd)
    scores = (
        jnp.einsum("btkgc,bskc->bkgts", q[:, lo:hi].astype(cd), k[:, :hi].astype(cd),
                   preferred_element_type=F32)
        for lo, hi in spans)
    o = _attend(
        spans, scores, hd ** -0.5,
        lambda probs, hi: jnp.einsum("bkgts,bskv->btkgv", probs.astype(cd), v[:, :hi].astype(cd),
                                     preferred_element_type=F32))
    return o.reshape(b, t, h, hd)


def mla_mixer(cfg: BackboneConfig, p: Dict[str, Any], x):
    """Latent attention.  Queries from one matrix, or (``q_lora_rank``) from
    a low-rank pair with a norm between; keys and values from a normalised
    latent, the decoupled key channels shared by all heads and, like the
    queries', rotated where ``rope_theta`` is set; a causal softmax
    (:func:`_causal_core`)."""
    cd, h = cfg.compute_dtype, cfg.num_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    b, t, _ = x.shape
    if cfg.q_lora_rank:
        c_q = rms_norm(_mm(x, p["mla_wq_a"], cd), p["mla_q_norm"], cfg.rms_norm_eps)
        q = _mm(c_q, p["mla_wq_b"], cd).reshape(b, t, h, dn + dr)
    else:
        q = _mm(x, p["mla_wq"], cd).reshape(b, t, h, dn + dr)
    kv_a = _mm(x, p["mla_wkv_a"], cd)
    c = rms_norm(kv_a[..., :r], p["mla_kv_norm"], cfg.rms_norm_eps)
    k_r = kv_a[..., r:]                                   # shared by all heads
    kv = _mm(c, p["mla_wkv_b"], cd).reshape(b, t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    with jax.named_scope("backbone.mla.attn"):
        o = _causal_core(cfg, q, k_n, k_r, v)
    return _mm(o.reshape(b, t, h * dv), p["mla_wo"], cd)


def gqa_mixer(cfg: BackboneConfig, p: Dict[str, Any], x):
    """Grouped-query attention: ``num_heads`` query heads over
    ``num_kv_heads`` key/value heads; queries and keys each through an
    RMSNorm over a head's channels (one weight vector for all query heads,
    one for all key heads), then rotary positions on all of a head's
    channels; a causal softmax (:func:`_grouped_core`)."""
    cd, h, kv, hd = cfg.compute_dtype, cfg.num_heads, cfg.num_kv_heads, cfg.gqa_head_dim
    b, t, _ = x.shape
    q = _mm(x, p["gqa_wq"], cd).reshape(b, t, h, hd)
    k = _mm(x, p["gqa_wk"], cd).reshape(b, t, kv, hd)
    v = _mm(x, p["gqa_wv"], cd).reshape(b, t, kv, hd)
    with jax.named_scope("backbone.gqa.attn"):
        cos, sin = rotary(t, hd, cfg.rope_theta)
        q, k = (rotate(rms_norm(a, p[f"gqa_{n}_norm"], cfg.rms_norm_eps),
                       cos[:, None, :], sin[:, None, :]) for a, n in ((q, "q"), (k, "k")))
        o = _grouped_core(cfg, q, k, v)
    return _mm(o.reshape(b, t, h * hd), p["gqa_wo"], cd)


def conv_mixer(cfg: BackboneConfig, p: Dict[str, Any], x):
    """The gated short convolution: ``[B ; C ; X] = x W_in``, ``y = (C *
    conv(B * X)) W_out`` with a depthwise causal convolution over time
    (:func:`short_conv`, ``short_conv_kernel_size`` taps).  No activation,
    no state beyond the taps' rows."""
    cd, d = cfg.compute_dtype, cfg.hidden_size
    bcx = _mm(x, p["conv_win"], cd)
    with jax.named_scope("backbone.conv.gate"):
        gate_in, gate_out, inner = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        gated = gate_out * short_conv(gate_in * inner, p["conv_taps"])
    return _mm(gated, p["conv_wout"], cd)


@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` whose inverse is given: the
    backward pass is a gather by the inverse, not a scatter."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], (order, inverse)


def _permute_bwd(res, ct):
    order, inverse = res
    return ct[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(cfg: BackboneConfig, router, x):
    """``(experts, weights)`` each (N, k): the selected experts of every
    position and their renormalised, scaled weights.  Float32 throughout."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(F32), router, precision=jax.lax.Precision.HIGHEST))
    top, experts = jax.lax.top_k(scores, cfg.num_experts_per_token)
    weights = cfg.routed_scaling_factor * top / (
        jnp.sum(top, axis=-1, keepdims=True) + cfg.route_eps)
    return experts, weights


def expert_layer(cfg: BackboneConfig, p: Dict[str, Any], x):
    """``(y, tokens)``: the held experts' part of the routed sum plus the
    shared expert (where the model has one), for ``x`` (N, D); ``tokens``
    (held,) counts the positions each held expert computed."""
    cd, k, held = cfg.compute_dtype, cfg.num_experts_per_token, cfg.experts_held
    n, d = x.shape
    with jax.named_scope("backbone.moe.route"):
        experts, weights = route(cfg, p["moe_router"], x)
        local = experts - cfg.experts_held_from
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).reshape(n * k)  # the absent sort last
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        tokens = jnp.sum(
            key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
        valid = (jnp.arange(n * k) < jnp.sum(tokens))[:, None]
    with jax.named_scope("backbone.moe.experts"):
        xs = jnp.broadcast_to(x.astype(cd)[:, None, :], (n, k, d)).reshape(n * k, d)
        xs = _permute(xs, order, inverse)
        # accumulated in float32 inside the kernel, kept in the compute dtype.
        # Rows past the held pairs belong to no group: the kernel leaves
        # them unwritten, in the result and in the gradient it hands back,
        # so both sides of every call are masked
        def rd(a, w):
            a = jnp.where(valid, a.astype(cd), 0)
            out = jax.lax.ragged_dot(a, w.astype(cd), tokens, preferred_element_type=cd)
            return jnp.where(valid, out, 0)

        mid = jax.nn.silu(rd(xs, p["moe_wg"]).astype(F32)) * rd(xs, p["moe_wu"])
        ys = _permute(rd(mid, p["moe_wd"]), inverse, order).reshape(n, k, d)
        y = jnp.sum(ys * jnp.where(mine, weights, 0.0)[..., None], axis=1)
        if cfg.num_shared_experts:
            y += swiglu(x, p["moe_shared_wg"], p["moe_shared_wu"], p["moe_shared_wd"], cd)
    return y, tokens


def _slice(stack: Dict[str, Any], slot) -> Dict[str, Any]:
    """One layer's parameters out of a kind's stack (``slot`` a number or a
    traced index)."""
    if isinstance(slot, int):
        return {name: a[slot] for name, a in stack.items()}
    return {name: jax.lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
            for name, a in stack.items()}


MIXERS = {"kda": kda_mixer, "mla": mla_mixer, "conv": conv_mixer, "gqa": gqa_mixer}
_MIXERS_TRACED = telemetry.counter(
    "gordo_backbone_mixers_total",
    "Mixers of the sequence backbone traced (forward, recomputation and "
    "held-out forecast each trace theirs), by kind: kda, mla, conv, gqa",
    labels=("kind",),
)


def _mixer_of(cfg: BackboneConfig, kind: str, p: Dict[str, Any], norm, h):
    """``Mixer(RMSNorm(h))`` of one kind for a group of sequences (G, T, D)."""
    x = rms_norm(h, norm, cfg.rms_norm_eps)
    _MIXERS_TRACED.inc(1.0, kind)  # runs where the mixer is traced
    with jax.named_scope("backbone." + kind):
        return MIXERS[kind](cfg, p, x)


def _groups(cfg: BackboneConfig, h):
    """``h`` (B, T, D) as (groups, ``mixer_group``, T, D)."""
    b = h.shape[0]
    group = cfg.mixer_group if b % cfg.mixer_group == 0 else b
    return h.reshape((b // group, group) + h.shape[1:])


def _which(cfg: BackboneConfig, mixers, stacked: bool = True) -> Dict[str, Any]:
    """What tells layers' mixers apart inside a program: for the layers whose
    ``(kind, slot in that kind's stack)`` are ``mixers``, ``is_<kind>`` for
    every kind the model's pattern can give but the first, and per kind the
    layer's slot in its stack (0 where it has none).  Arrays over the layers;
    scalars for the one layer where not ``stacked``."""
    kinds = cfg.mixer_kinds
    marks = {"is_" + kind: [of == kind for of, _ in mixers] for kind in kinds[1:]}
    slots = {kind: [slot if of == kind else 0 for of, slot in mixers] for kind in kinds}
    pick = (lambda v: v) if stacked else (lambda v: v[0])  # noqa: E731
    return {**{k: jnp.asarray(pick(v)) for k, v in marks.items()},
            **{k: jnp.asarray(pick(v), jnp.int32) for k, v in slots.items()}}


def _choose(which, kinds, branch):
    """``branch(kind)`` for the one of ``kinds`` that ``which`` marks: the
    kind itself where there is one, else a ``lax.cond`` on the last kind's
    mark whose other side chooses among the rest."""
    *rest, last = kinds
    if not rest:
        return branch(last)
    return jax.lax.cond(which["is_" + last], lambda: branch(last),
                        lambda: _choose(which, rest, branch))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mixer(cfg: BackboneConfig, mixers, norm, which, h):
    """``Mixer(RMSNorm(h))`` on the stream ``h`` (B, T, D) for one layer of
    the stacks ``mixers`` (kind -> that kind's stacked parameters, for the
    kinds the layers at hand have): ``which`` (:func:`_which`) says which
    kind the layer's mixer is and its slot in each stack, as traced values.

    A mixer reads ``mixer_group`` sequences at a time (sequences do not see
    each other, and a mixer's intermediates are what fills the memory).
    Backward is written out (:func:`_mixer_bwd`): it keeps the layer's input
    alone, takes the layer's slice of the stacks again, recomputes each
    group once inside the branch of its kind, and sums the groups' gradients
    at the size of one layer before it puts them into the stack's shape.
    Left to ``jax.checkpoint`` around a ``lax.cond``, every intermediate of
    every kind crosses from the forward conditional to the backward one, and
    a gradient in the stack's shape is added up once a group."""
    p = {kind: _slice(stack, which[kind]) for kind, stack in mixers.items()}

    def one(hg):
        return _choose(which, sorted(p), lambda kind: _mixer_of(cfg, kind, p[kind], norm, hg))

    groups = _groups(cfg, h)
    out = one(groups[0])[None] if groups.shape[0] == 1 else jax.lax.map(one, groups)
    return out.reshape(h.shape)


def _mixer_fwd(cfg, mixers, norm, which, h):
    return _mixer(cfg, mixers, norm, which, h), (mixers, norm, which, h)


def _mixer_bwd(cfg, res, ct):
    mixers, norm, which, h = res
    p = {kind: _slice(stack, which[kind]) for kind, stack in mixers.items()}

    def grads(kind, hg, ctg):
        """This group's gradient for every kind's slice (zeros for the
        kinds the layer is not), the norm and the group's input."""
        _, vjp = jax.vjp(functools.partial(_mixer_of, cfg, kind), p[kind], norm, hg)
        dp, dnorm, dhg = vjp(ctg)
        return ({k: dp if k == kind else jax.tree.map(jnp.zeros_like, p[k]) for k in p},
                dnorm), dhg

    def one(acc, pair):
        hg, ctg = pair
        d, dhg = _choose(which, sorted(p), lambda kind: grads(kind, hg, ctg))
        return jax.tree.map(jnp.add, acc, d), dhg

    zero = (jax.tree.map(jnp.zeros_like, p), jnp.zeros_like(norm))
    (dp, dnorm), dh = jax.lax.scan(one, zero, (_groups(cfg, h), _groups(cfg, ct)))
    d_mixers = {
        kind: {name: jax.lax.dynamic_update_index_in_dim(
            jnp.zeros_like(a), dp[kind][name], which[kind], 0)
            for name, a in stack.items()}
        for kind, stack in mixers.items()
    }
    no_gradient = {k: np.zeros(v.shape, jax.dtypes.float0) for k, v in which.items()}
    return d_mixers, dnorm, no_gradient, dh.reshape(h.shape)


_mixer.defvjp(_mixer_fwd, _mixer_bwd)


def _dense_ffn(cfg: BackboneConfig, p: Dict[str, Any], norm, h):
    """``FFN(RMSNorm(h))`` of a leading dense layer, ``h`` (B, T, D)."""
    x = rms_norm(h, norm, cfg.rms_norm_eps)
    with jax.named_scope("backbone.ffn"):
        return swiglu(x, p["dense_wg"], p["dense_wu"], p["dense_wd"], cfg.compute_dtype)


def _expert_ffn(cfg: BackboneConfig, p: Dict[str, Any], norm, h):
    """``(FFN(RMSNorm(h)), tokens)`` of an expert layer for the whole batch
    ``h`` (B, T, D): an expert sees a step's positions at once."""
    b, t, d = h.shape
    x = rms_norm(h, norm, cfg.rms_norm_eps)
    y, tokens = expert_layer(cfg, p, x.reshape(b * t, d))
    return y.reshape(b, t, d), tokens


def _head(cfg: BackboneConfig, params: Dict[str, Any], norm, h):
    """``RMSNorm(h) W_out + b``: the forecast of every position."""
    return _mm(rms_norm(h, norm, cfg.rms_norm_eps),
               params["out_proj"], cfg.compute_dtype) + params["out_bias"]


def _mtp(cfg: BackboneConfig, params: Dict[str, Any], h0, h):
    """The multi-token-prediction module on the embedded rows ``h0`` and the
    last layer's stream ``h`` (both (B, T, D)): ``(forecast of the row after
    next (B, T, F_out), tokens (held,))``.

    ``h'_i = W_eh [RMSNorm(h_i); RMSNorm(h0_{i+1})]``, one whole block of the
    expert-layer form with the module's own weights, then the main head's
    projection behind the module's own norm.  ``h0_{i+1}`` is the
    sequence's own next position: the last position has none, reads zeros
    and weighs nothing in the loss."""
    own = {k[len("mtp_"):]: v for k, v in params.items() if k.startswith("mtp_")}
    eps, cd = cfg.rms_norm_eps, cfg.compute_dtype
    ahead = jnp.concatenate([h0[:, 1:], jnp.zeros_like(h0[:, :1])], axis=1)
    both = jnp.concatenate([rms_norm(h, own["norm_h"][0], eps),
                            rms_norm(ahead, own["norm_e"][0], eps)], axis=-1)
    h = _mm(both, own["weh"][0], cd)
    mla = {k: v for k, v in own.items() if k.startswith("mla_")}
    h = h + _mixer(cfg, {"mla": mla}, own["mixer_norm"][0],
                   _which(cfg, [("mla", 0)], stacked=False), h)
    y, tokens = jax.checkpoint(functools.partial(_expert_ffn, cfg))(
        {k: v[0] for k, v in own.items() if k.startswith("moe_")},
        own["ffn_norm"][0], h)
    return _head(cfg, params, own["out_norm"][0], h + y), tokens


def forward(cfg: BackboneConfig, params: Dict[str, Any], x, counts: bool = False,
            mtp: bool = False):
    """``x`` (S, T, F) or (T, F) → the forecast of every position's next row.
    With ``mtp`` (a training pass of a model that has the module), the pair
    ``(next row, row after next)``.  With ``counts``, also ``{"tokens":
    (expert layers [+ the MTP's], held), "selected": pairs routed, "held":
    pairs that fell on held experts}``.

    The leading dense layers are traced one by one; the expert layers are
    ONE ``lax.scan`` over their stacked parameters, whose body chooses its
    layer's mixer by ``lax.cond`` (none where every layer's is of one kind).
    Every feed-forward is under ``jax.checkpoint`` and every mixer has its
    backward written out (:func:`_mixer`): backward keeps the residual
    stream alone and recomputes each part once."""
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    h = h0 = _mm(x, params["in_proj"], cfg.compute_dtype)
    stack = lambda kind: {  # noqa: E731
        k: v for k, v in params.items() if k.startswith(kind + "_")}
    # the mixers of some layers: the stacks of the kinds they have, and what
    # tells them apart (one kind: no conditional is traced)
    of_kinds = lambda layers: {  # noqa: E731
        kind: stack(kind) for kind in sorted({cfg.mixer(l) for l in layers})}
    which = lambda layers: _which(cfg, [  # noqa: E731
        (cfg.mixer(l), cfg.layers_of(cfg.mixer(l)).index(l)) for l in layers])
    n_dense = len(cfg.layers_of("dense"))
    for l in range(1, n_dense + 1):
        h = h + _mixer(cfg, of_kinds([l]), params["mixer_norm"][l - 1],
                       jax.tree.map(lambda a: a[0], which([l])), h)
        h = h + jax.checkpoint(functools.partial(_dense_ffn, cfg))(
            _slice(stack("dense"), l - 1), params["ffn_norm"][l - 1], h)
    tokens = jnp.zeros((0, cfg.experts_held), jnp.int32)
    if cfg.moe_layers:
        rest = cfg.moe_layers
        rest_mixers = of_kinds(rest)

        def expert_block(h, layer):
            h = h + _mixer(cfg, rest_mixers, layer["mixer_norm"], layer["which"], h)
            y, tokens = jax.checkpoint(functools.partial(_expert_ffn, cfg))(
                layer["moe"], layer["ffn_norm"], h)
            return h + y, tokens

        h, tokens = jax.lax.scan(expert_block, h, {
            "mixer_norm": params["mixer_norm"][n_dense:],
            "ffn_norm": params["ffn_norm"][n_dense:],
            "which": which(rest),
            "moe": stack("moe"),
        })
    y = _head(cfg, params, params["out_norm"], h)
    y = y[0] if squeeze else y
    if mtp:
        if not cfg.mtp_depth:
            raise ValueError("this model has no multi-token-prediction module")
        with jax.named_scope("backbone.mtp"):
            ahead, mtp_tokens = _mtp(cfg, params, h0, h)
        y = (y, ahead[0] if squeeze else ahead)
        tokens = jnp.concatenate([tokens, mtp_tokens[None]])
    if not counts:
        return y
    positions = h.shape[0] * h.shape[1]
    return y, {
        "tokens": tokens,
        "held": jnp.sum(tokens),
        "selected": jnp.asarray(
            positions * cfg.num_experts_per_token * tokens.shape[0], jnp.int32),
    }


class SequenceBackbone(nn.Module):
    """The block as a flax module: parameters in one flat dict
    (:func:`param_specs`), the forward pass in :func:`forward`."""

    cfg: BackboneConfig

    #: the fleet program runs this module's machines one after another
    #: (``lax.map``), not side by side under ``vmap``: one model fills the chip
    fleet_axis = "map"

    @property
    def mtp_weight(self) -> float:
        """Lambda of the loss's second term (``train.fit.make_loss_fn``
        ``second``): a training pass then asks for ``mtp=True``.  0 where
        the model has no module or does not train it."""
        return float(self.cfg.mtp_weight) if self.cfg.mtp_depth else 0.0

    @nn.compact
    def __call__(self, x, counts: bool = False, mtp: bool = False):
        params = {
            name: self.param(name, _initializer(init), shape, F32)
            for name, shape, init in param_specs(self.cfg)
        }
        if self.is_initializing():
            # the parameters' shapes do not depend on a forward pass: none is
            # traced where the model is only being initialised
            return jnp.zeros(x.shape[:-1] + (self.cfg.n_features_out,), F32)
        return forward(self.cfg, params, x, counts, mtp)

    def param_count(self) -> int:
        return sum(math.prod(shape) for _, shape, _ in param_specs(self.cfg))


def _backbone(kind: str, n_features, n_features_out, compute_dtype, preset, widths):
    known = {f.name for f in dataclasses.fields(BackboneConfig)}
    unknown = sorted(set(widths) - known)
    if unknown:
        raise TypeError(f"{kind} got unknown arguments {unknown}")
    if "layer_pattern" in widths:  # a YAML list: the configuration is hashed
        widths = {**widths, "layer_pattern": tuple(widths["layer_pattern"])}
    cfg = BackboneConfig(**{
        **preset, **widths,
        "n_features": int(n_features),
        "n_features_out": int(n_features_out or n_features),
        "compute_dtype": resolve_compute_dtype(compute_dtype),
    })
    if not 0 <= cfg.experts_held_from <= cfg.experts_held_from + cfg.experts_held <= cfg.num_experts:
        raise ValueError("experts_held is not a range of the model's experts")
    if cfg.mtp_depth not in (0, 1):
        raise ValueError("one multi-token-prediction module at most")
    if cfg.rope_theta and cfg.qk_rope_head_dim % 2:
        raise ValueError("rotary positions pair the channels: qk_rope_head_dim is odd")
    if len(cfg.pattern) != cfg.num_layers or set(cfg.pattern) - set(MIXER_KINDS):
        raise ValueError(
            f"layer_pattern names one mixer of {MIXER_KINDS} for each of the "
            f"{cfg.num_layers} layers; it is {cfg.pattern}")
    if "gqa" in cfg.pattern and (
            cfg.num_heads % cfg.num_kv_heads or cfg.gqa_head_dim % 2 or not cfg.rope_theta):
        raise ValueError(
            "grouped-query attention needs num_kv_heads a divisor of num_heads, "
            "an even hidden_size / num_heads and a rope_theta")
    return SequenceBackbone(cfg)


@register_model_builder(type="SequenceForecast")
def kimi_linear(
    n_features: int,
    n_features_out: int = None,
    compute_dtype: str = "auto",
    context: int = None,
    stride: int = None,
    seed: int = 0,
    **widths,
) -> nn.Module:
    """Kimi-Linear's block at its published widths (every width is a keyword
    of :class:`BackboneConfig`; tests pass a tiny preset).  ``num_layers``
    layers from layer 1 on, ``experts_held`` routed experts from
    ``experts_held_from`` of ``num_experts``.  ``context`` and ``stride`` are
    the estimator's."""
    del context, stride, seed
    return _backbone("kimi_linear", n_features, n_features_out, compute_dtype, {}, widths)


#: GLM-4.7-Flash's config.json as :class:`BackboneConfig` keywords
GLM_MOE_LITE = dict(
    hidden_size=2048, num_heads=20, full_attn_every=1, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    rope_theta=1e6, intermediate_size=10240, first_k_dense_replace=1,
    moe_intermediate_size=1536, num_experts=64, num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=1.8, experts_held=8,
    mtp_depth=1, mtp_weight=0.3,
)


@register_model_builder(type="SequenceForecast")
def glm_moe_lite(
    n_features: int,
    n_features_out: int = None,
    compute_dtype: str = "auto",
    context: int = None,
    stride: int = None,
    seed: int = 0,
    **widths,
) -> nn.Module:
    """GLM-4.7-Flash's block at its published widths (``model_type``
    ``glm4_moe_lite``): every layer's mixer is rotary latent attention with
    low-rank normalised queries, layer 0's feed-forward is dense and the
    others' the 64-way expert layer, and one multi-token-prediction module
    is trained beside the main head (``mtp_weight``; 0 leaves it untrained).
    ``num_layers`` layers from the source's layer 0 on, ``experts_held``
    routed experts from ``experts_held_from``.  Every width is a keyword of
    :class:`BackboneConfig`; tests pass a tiny preset."""
    del context, stride, seed
    return _backbone("glm_moe_lite", n_features, n_features_out, compute_dtype,
                     GLM_MOE_LITE, widths)


#: LFM2-24B-A2B's config.json as :class:`BackboneConfig` keywords.  The cut
#: starts at the source's layer 1 (its two leading dense layers, both
#: convolutions, are counted once), so ``layer_pattern`` is the source's
#: ``layer_types`` from there on: attention at the source's layers 2, 6, ...
LFM2_MOE = dict(
    hidden_size=2048, num_heads=32, num_kv_heads=8,
    short_conv_kernel_size=3, rope_theta=1e6, intermediate_size=11776,
    first_k_dense_replace=1, moe_intermediate_size=1536, num_experts=64,
    num_experts_per_token=4, num_shared_experts=0, routed_scaling_factor=1.0,
    route_eps=1e-6, experts_held=8, rms_norm_eps=1e-5,
)
LFM2_LAYER_TYPES = ("conv", "conv", "full_attention", "conv") * 10


@register_model_builder(type="SequenceForecast")
def lfm2_moe(
    n_features: int,
    n_features_out: int = None,
    compute_dtype: str = "auto",
    context: int = None,
    stride: int = None,
    seed: int = 0,
    **widths,
) -> nn.Module:
    """LFM2-24B-A2B's block at its published widths (``model_type``
    ``lfm2_moe``): gated short convolutions in three layers of four and
    grouped-query attention with normalised rotary heads in the fourth; the
    leading layer's feed-forward is dense, the others' the 64-way expert
    layer, which has no shared expert.  ``num_layers`` layers from the
    source's layer 1 on (``layer_pattern`` follows the source's
    ``layer_types`` unless given), ``experts_held`` routed experts from
    ``experts_held_from``.  Every width is a keyword of
    :class:`BackboneConfig`; tests pass a tiny preset."""
    del context, stride, seed
    n = int(widths.get("num_layers", BackboneConfig.num_layers))
    source = tuple("gqa" if kind == "full_attention" else "conv"
                   for kind in LFM2_LAYER_TYPES[1:1 + n])
    return _backbone("lfm2_moe", n_features, n_features_out, compute_dtype,
                     {**LFM2_MOE, "layer_pattern": source}, widths)
