"""Operations and bytes that the ``kimi_linear`` forecaster's build needs,
from its shapes alone.  Kept with the benchmark so that no later change to
the program can move the yardstick.

The count is the algorithm's: 2 operations per multiply-add of every matrix
product a position passes through in the forward pass, three times that for
a trained position (forward and backward), once for a forecast position.
Attention counts the causal half of its scores, the delta rule its chunked
form at the configuration's chunk length, the routed experts the share of
the selected pairs that uniform routing sends to the experts held here.
Padding slots, the recomputation of a layer in the backward pass and
everything that is not a matrix product (norms, gates, the optimiser) are
not counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark.flops import time_series_folds

KDA_CHUNK = 64           # the program's chunk length (positions)
COMPUTE_BYTES = 2        # bfloat16 operands
STATE_BYTES = 4          # float32 state and decays


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The widths as the configuration's file states them."""
    linear = config["linear_attn_config"]
    depth = config["depth"]["layers_here"]
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "dk": int(linear["head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "conv": int(linear["short_conv_kernel_size"]),
        "gate_rank": int(linear["head_dim"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "dn": int(config["qk_nope_head_dim"]),
        "dr": int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_token"]),
        "shared": int(config["num_shared_experts"]),
        "held": int(config["experts"]["held_here"]),
        "kda_layers": [l for l in depth if l in linear["kda_layers"]],
        "mla_layers": [l for l in depth if l in linear["full_attn_layers"]],
        "dense_layers": [l for l in depth if l <= int(config["first_k_dense_replace"])],
        "moe_layers": [l for l in depth if l > int(config["first_k_dense_replace"])],
        "context": int(config["model"]["context"]),
        "features": int(config["dataset"]["n_tags"]),
    }


def kda_scan_flops(s: Dict[str, Any]) -> float:
    """Per position and layer: the chunked delta rule between the
    projections (the span ``backbone.kda.scan``)."""
    c, dk, dv = KDA_CHUNK, s["dk"], s["dk"]
    per_head = (
        2 * c * dk          # the strict half of K K^T and the lower half of Q K^T
        + c * (dv + dk)     # forward substitution of (I + beta A) [U | W] = ...
        + c * dv            # the lower half of (Q K^T) U
        + 6 * dk * dv       # W S, Q S and the state's K^T U
    )
    return float(per_head * s["kda_heads"])


def kda_scan_bytes(s: Dict[str, Any]) -> float:
    """Per position and layer, the least the scan moves: q, k, v read and o
    written in the compute dtype, the per-channel decay read in float32."""
    h, dk = s["kda_heads"], s["dk"]
    return float(h * dk * (4 * COMPUTE_BYTES + STATE_BYTES))


def position_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """Forward operations one position needs, by part."""
    d, h, dk, r = s["d"], s["kda_heads"], s["dk"], s["gate_rank"]
    kda_proj = 2 * d * 3 * h * dk + 2 * 2 * (d * r + r * h * dk) + 2 * d * h \
        + 2 * h * dk * d + 2 * s["conv"] * 3 * h * dk
    mla_proj = 2 * d * s["heads"] * (s["dn"] + s["dr"]) + 2 * d * (s["kv_rank"] + s["dr"]) \
        + 2 * s["kv_rank"] * s["heads"] * (s["dn"] + s["dv"]) + 2 * s["heads"] * s["dv"] * d
    mla_attn = s["context"] * s["heads"] * (s["dn"] + s["dr"] + s["dv"])  # causal half
    expert = 2 * 3 * d * s["expert"]
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        "in_out": 2.0 * s["features"] * d * 2,
        "kda": float(kda_proj) + kda_scan_flops(s),
        "mla": float(mla_proj + mla_attn),
        "dense_ffn": 2.0 * 3 * d * s["dense"],
        "moe_route": 2.0 * d * s["experts"],
        "moe_experts": expert * (s["shared"] + routed_pairs),
    }


def forward_flops(s: Dict[str, Any]) -> float:
    part = position_flops(s)
    return (
        part["in_out"]
        + part["kda"] * len(s["kda_layers"]) + part["mla"] * len(s["mla_layers"])
        + part["dense_ffn"] * len(s["dense_layers"])
        + (part["moe_route"] + part["moe_experts"]) * len(s["moe_layers"])
    )


def real_positions(n_rows: int, context: int, stride: int) -> int:
    """Positions that read a real row when ``n_rows`` rows are cut into
    sequences: rows 0 .. n_rows - 2, each once per sequence that covers it."""
    n_in = max(n_rows - 1, 0)
    n_seq = -(-max(n_in - context, 0) // stride) + 1
    return sum(max(min(context, n_in - i * stride), 0) for i in range(n_seq))


def fit_steps(n_rows: int, context: int, stride: int, batch: int) -> int:
    n_in = max(n_rows - 1, 0)
    n_seq = -(-max(n_in - context, 0) // stride) + 1
    return -(-n_seq // min(batch, n_seq))


def geometry(config: Dict[str, Any]) -> Dict[str, int]:
    """Trained and forecast positions and optimiser steps of one machine:
    the folds' fits with their held-out forecasts, then the final fit."""
    model, ds = config["model"], config["dataset"]
    context, stride = int(model["context"]), int(model["stride"])
    batch, epochs = int(model["batch_size"]), int(model["epochs"])
    rows = int(ds["rows"])
    fits: List[Tuple[int, int]] = time_series_folds(rows, int(config["cv"]["n_splits"]))
    trained = sum(real_positions(n, context, stride) for n, _ in fits)
    trained += real_positions(rows, context, stride)
    steps = sum(fit_steps(n, context, stride, batch) for n, _ in fits)
    steps += fit_steps(rows, context, stride, batch)
    return {
        "trained_positions": trained * epochs,
        "predicted_positions": sum(real_positions(n, context, stride) for _, n in fits),
        "steps_per_model": steps * epochs,
        "positions_per_step": batch * context,
    }


def chunk_work(config: Dict[str, Any], machines: int) -> Dict[str, Any]:
    """What ``record["work_per_chunk"]`` holds: the chunk's operations for
    the readers ``window_mfu`` and ``program_mfu``, and one optimiser step's
    operations and bytes under the two spans that have a roofline."""
    s = shape(config)
    g = geometry(config)
    forward = forward_flops(s)
    per_model = forward * (3.0 * g["trained_positions"] + g["predicted_positions"])
    part = position_flops(s)
    n = g["positions_per_step"]
    expert_weights = 3 * s["d"] * s["expert"] * (s["shared"] + s["held"])
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        **g,
        "forward_flops_per_position": forward,
        "flops_per_model": per_model,
        "flops": per_model * machines,
        "per_step": {
            "backbone.kda.scan": {
                "layers": len(s["kda_layers"]),
                "flops": 3.0 * kda_scan_flops(s) * n * len(s["kda_layers"]),
                "bytes": 3.0 * kda_scan_bytes(s) * n * len(s["kda_layers"]),
            },
            "backbone.moe.experts": {
                "layers": len(s["moe_layers"]),
                "flops": 3.0 * part["moe_experts"] * n * len(s["moe_layers"]),
                # forward and backward each read the weights once and move a
                # position's activations in and out once per expert it visits
                "bytes": 3.0 * len(s["moe_layers"]) * COMPUTE_BYTES * (
                    expert_weights + 2 * s["d"] * n * (s["shared"] + routed_pairs)),
            },
        },
    }
