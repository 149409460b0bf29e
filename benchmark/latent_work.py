"""Operations and bytes that the ``glm_moe_lite`` two-horizon forecaster's
build needs, from its shapes alone.  Kept with the benchmark so that no later
change to the program can move the yardstick.

The count is the algorithm's, as ``backbone_work.py``'s is: 2 operations per
multiply-add of every matrix product a position passes through in the forward
pass, three times that for a trained position (forward and backward), once
for a forecast position.  Attention counts the causal half of its scores and
of ``p v``; the routed experts the share of the selected pairs that uniform
routing sends to the experts held here.  The multi-token-prediction module
(``W_eh``, one block of latent attention and experts, the shared head once
more) runs on trained positions alone: a forecast does not run it.  Padding
slots, the recomputation of a part in the backward pass and everything that
is no matrix product (norms, the rotation, softmax, the optimiser) are not
counted.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.backbone_work import COMPUTE_BYTES, geometry


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The widths as the configuration's file states them; layers numbered
    from 0 as the source does."""
    depth = config["depth"]["layers_here"]
    dense_first = int(config["first_k_dense_replace"])
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "dn": int(config["qk_nope_head_dim"]),
        "dr": int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "held": int(config["experts"]["held_here"]),
        "layers": len(depth),
        "dense_layers": sum(1 for l in depth if l < dense_first),
        "moe_layers": sum(1 for l in depth if l >= dense_first),
        "mtp_modules": int(config["depth"]["mtp_modules_here"]),
        "context": int(config["model"]["context"]),
        "features": int(config["dataset"]["n_tags"]),
    }


def attention_flops(s: Dict[str, Any]) -> float:
    """Per position and block, the attention core between the projections
    (the span ``backbone.mla.attn``): the causal half of the scores over
    ``dn + dr`` channels and of ``p v`` over ``dv``."""
    return float(s["context"] * s["heads"] * (s["dn"] + s["dr"] + s["dv"]))


def attention_bytes(s: Dict[str, Any]) -> float:
    """Per position and block, the least the core moves, in the compute
    dtype: q read, k_n and the shared k_r read, v read, o written."""
    h = s["heads"]
    return float(COMPUTE_BYTES * (
        h * (s["dn"] + s["dr"]) + h * s["dn"] + s["dr"] + 2 * h * s["dv"]))


def position_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """Forward operations one position needs, by part."""
    d, h = s["d"], s["heads"]
    mla_proj = 2 * d * s["q_rank"] + 2 * s["q_rank"] * h * (s["dn"] + s["dr"]) \
        + 2 * d * (s["kv_rank"] + s["dr"]) \
        + 2 * s["kv_rank"] * h * (s["dn"] + s["dv"]) + 2 * h * s["dv"] * d
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        "in_out": 2.0 * s["features"] * d * 2,
        "mla": float(mla_proj) + attention_flops(s),
        "dense_ffn": 2.0 * 3 * d * s["dense"],
        "moe_route": 2.0 * d * s["experts"],
        "moe_experts": 2.0 * 3 * d * s["expert"] * (s["shared"] + routed_pairs),
        "mtp_merge": 2.0 * 2 * d * d,
        "mtp_head": 2.0 * d * s["features"],
    }


def forward_flops(s: Dict[str, Any]) -> float:
    """One position through the layers and the main head."""
    part = position_flops(s)
    return (part["in_out"] + part["mla"] * s["layers"]
            + part["dense_ffn"] * s["dense_layers"]
            + (part["moe_route"] + part["moe_experts"]) * s["moe_layers"])


def module_flops(s: Dict[str, Any]) -> float:
    """One position through the multi-token-prediction modules."""
    part = position_flops(s)
    return s["mtp_modules"] * (
        part["mtp_merge"] + part["mla"] + part["moe_route"] + part["moe_experts"]
        + part["mtp_head"])


def chunk_work(config: Dict[str, Any], machines: int) -> Dict[str, Any]:
    """What ``record["work_per_chunk"]`` holds: the chunk's operations for
    the reader ``program_mfu``, and one optimiser step's operations and
    bytes under the two spans that have a roofline.  Both spans occur in the
    layers and inside the MTP module: every block that has them is counted."""
    s = shape(config)
    g = geometry(config)
    forward, module = forward_flops(s), module_flops(s)
    per_model = 3.0 * (forward + module) * g["trained_positions"] \
        + forward * g["predicted_positions"]
    part = position_flops(s)
    n = g["positions_per_step"]
    attention_blocks = s["layers"] + s["mtp_modules"]
    expert_blocks = s["moe_layers"] + s["mtp_modules"]
    expert_weights = 3 * s["d"] * s["expert"] * (s["shared"] + s["held"])
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        **g,
        "forward_flops_per_position": forward,
        "module_flops_per_position": module,
        "flops_per_model": per_model,
        "flops": per_model * machines,
        "per_step": {
            "backbone.mla.attn": {
                "layers": attention_blocks,
                "flops": 3.0 * attention_flops(s) * n * attention_blocks,
                "bytes": 3.0 * attention_bytes(s) * n * attention_blocks,
            },
            "backbone.moe.experts": {
                "layers": expert_blocks,
                "flops": 3.0 * part["moe_experts"] * n * expert_blocks,
                # forward and backward each read the weights once and move a
                # position's activations in and out once per expert it visits
                "bytes": 3.0 * expert_blocks * COMPUTE_BYTES * (
                    expert_weights + 2 * s["d"] * n * (s["shared"] + routed_pairs)),
            },
        },
    }
