"""Operations and bytes that the ``lfm2_moe`` forecaster's build needs, from
its configuration's file alone.  Kept with the benchmark so that no later
change to the program can move the yardstick.

The count is the algorithm's, by the convention of ``latent_work.py``: 2
operations per multiply-add of every matrix product a position passes through
in the forward pass, three times that for a trained position (forward and
backward), once for a forecast position.  Attention counts the causal half of
its scores and of ``p v``; the routed experts the share of the selected pairs
that uniform routing sends to the experts held here; the expert layer has no
shared expert.  Padding slots, the recomputation of a part in the backward
pass and everything that is no matrix product (norms, the convolution's gates
and its three taps, the rotation, softmax, the optimiser) are not counted.

The layers are read as the source numbers them: ``depth.layers_here`` names
the source's layers held, ``layer_types`` says which are convolutions and
which attention, ``num_dense_layers`` which feed-forwards are dense.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.backbone_work import COMPUTE_BYTES, geometry


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The widths as the configuration's file states them."""
    layers = [int(l) for l in config["depth"]["layers_here"]]
    types = [config["layer_types"][l] for l in layers]
    dense_first = int(config["num_dense_layers"])
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "d": d,
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads,          # the source gives head_dim null
        "taps": int(config["conv_L_cache"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "held": int(config["experts"]["held_here"]),
        "conv_layers": types.count("conv"),
        "gqa_layers": types.count("full_attention"),
        "dense_layers": sum(1 for l in layers if l < dense_first),
        "moe_layers": sum(1 for l in layers if l >= dense_first),
        "context": int(config["model"]["context"]),
        "features": int(config["dataset"]["n_tags"]),
    }


def conv_flops(s: Dict[str, Any]) -> float:
    """Per position and layer, the whole gated short convolution (the span
    ``backbone.conv``): ``W_in`` (d x 3d) and ``W_out`` (d x d)."""
    return 2.0 * s["d"] * 4 * s["d"]


def conv_bytes(s: Dict[str, Any]) -> float:
    """Per position and layer, the least the mixer moves in the compute
    dtype: the normed row read, its output written (a kernel that keeps the
    three thirds and the gates on the chip moves nothing else)."""
    return float(COMPUTE_BYTES * 2 * s["d"])


def conv_weight_bytes(s: Dict[str, Any]) -> float:
    """Per layer and pass: ``W_in`` and ``W_out`` read once."""
    return float(COMPUTE_BYTES * 4 * s["d"] * s["d"])


def attention_flops(s: Dict[str, Any]) -> float:
    """Per position and layer, the attention core between the projections
    (the span ``backbone.gqa.attn``): the causal half of the scores and of
    ``p v``, each over a head's width for every query head."""
    return float(s["context"] * s["heads"] * 2 * s["head_dim"])


def attention_bytes(s: Dict[str, Any]) -> float:
    """Per position and layer, the least the core moves, in the compute
    dtype: q read, the grouped k and v read, o written."""
    return float(COMPUTE_BYTES * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"]))


def position_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """Forward operations one position needs, by part."""
    d, hd = s["d"], s["head_dim"]
    gqa_proj = 2.0 * d * hd * (2 * s["heads"] + 2 * s["kv_heads"])
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        "in_out": 2.0 * s["features"] * d * 2,
        "conv": conv_flops(s),
        "gqa": gqa_proj + attention_flops(s),
        "dense_ffn": 2.0 * 3 * d * s["dense"],
        "moe_route": 2.0 * d * s["experts"],
        "moe_experts": 2.0 * 3 * d * s["expert"] * routed_pairs,
    }


def forward_flops(s: Dict[str, Any]) -> float:
    """One position through the layers and the head."""
    part = position_flops(s)
    return (part["in_out"] + part["conv"] * s["conv_layers"] + part["gqa"] * s["gqa_layers"]
            + part["dense_ffn"] * s["dense_layers"]
            + (part["moe_route"] + part["moe_experts"]) * s["moe_layers"])


def chunk_work(config: Dict[str, Any], machines: int) -> Dict[str, Any]:
    """What ``record["work_per_chunk"]`` holds: the chunk's operations for
    the reader ``program_mfu``, and one optimiser step's operations and
    bytes under the three spans that have a roofline."""
    s = shape(config)
    g = geometry(config)
    forward = forward_flops(s)
    per_model = forward * (3.0 * g["trained_positions"] + g["predicted_positions"])
    part = position_flops(s)
    n = g["positions_per_step"]
    expert_weights = 3 * s["d"] * s["expert"] * s["held"]
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        **g,
        "forward_flops_per_position": forward,
        "flops_per_model": per_model,
        "flops": per_model * machines,
        "per_step": {
            "backbone.conv": {
                "layers": s["conv_layers"],
                "flops": 3.0 * conv_flops(s) * n * s["conv_layers"],
                # forward and backward each read the two matrices once
                "bytes": 3.0 * s["conv_layers"] * (conv_weight_bytes(s) + conv_bytes(s) * n),
            },
            "backbone.gqa.attn": {
                "layers": s["gqa_layers"],
                "flops": 3.0 * attention_flops(s) * n * s["gqa_layers"],
                "bytes": 3.0 * attention_bytes(s) * n * s["gqa_layers"],
            },
            "backbone.moe.experts": {
                "layers": s["moe_layers"],
                "flops": 3.0 * part["moe_experts"] * n * s["moe_layers"],
                # forward and backward each read the weights once and move a
                # position's activations in and out once per expert it visits
                "bytes": 3.0 * s["moe_layers"] * COMPUTE_BYTES * (
                    expert_weights + 2 * s["d"] * n * routed_pairs),
            },
        },
    }
