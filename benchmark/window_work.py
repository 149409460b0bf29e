"""Operations and bytes that the ``afmoe`` forecaster's build needs, from its
configuration's file alone.  Kept with the benchmark so that no later change
to the program can move the yardstick.

The count is the algorithm's, by the convention of ``hybrid_work.py``: 2
operations per multiply-add of every matrix product a position passes through
in the forward pass, three times that for a trained position (forward and
backward), once for a forecast position.  Attention counts the (query, key)
pairs inside the causal mask and, in a windowed layer, inside the window:
``T (T + 1) / 2`` a sequence of ``T`` rows over its whole prefix, ``T W - W (W
- 1) / 2`` under a window of ``W <= T`` rows (a row sees itself and the ``W -
1`` before it), whatever blocks an implementation cuts them into.  The routed
experts count the share of the selected pairs that uniform routing sends to
the experts held here, the shared expert every position.  Padding slots, the
recomputation of a part in the backward pass and everything that is no matrix
product (norms, the rotation, softmax, the gates' sigmoids, the optimiser) are
not counted.

The layers are read as the source numbers them: ``depth.layers_here`` names
the source's layers held, ``layer_types`` says which attend to a window and
which to the whole prefix, ``num_dense_layers`` which feed-forwards are dense.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.backbone_work import COMPUTE_BYTES, geometry
from benchmark.hybrid_work import attention_bytes  # q read, grouped k and v read, o written


def shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The widths as the configuration's file states them."""
    layers = [int(l) for l in config["depth"]["layers_here"]]
    types = [config["layer_types"][l] for l in layers]
    dense_first = int(config["num_dense_layers"])
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["num_shared_experts"]),
        "held": int(config["experts"]["held_here"]),
        "swa_layers": types.count("sliding_attention"),
        "gqa_layers": types.count("full_attention"),
        "dense_layers": sum(1 for l in layers if l < dense_first),
        "moe_layers": sum(1 for l in layers if l >= dense_first),
        "context": int(config["model"]["context"]),
        "features": int(config["dataset"]["n_tags"]),
    }


def causal_pairs(t: int) -> int:
    """(query, key) pairs of one sequence under the causal mask alone."""
    return t * (t + 1) // 2


def window_pairs(t: int, window: int) -> int:
    """Pairs inside the causal mask AND a window of ``window`` rows."""
    w = min(window, t)
    return t * w - w * (w - 1) // 2


def attention_flops(s: Dict[str, Any], pairs: int) -> float:
    """Per position and layer, the core between the projections (the spans
    ``backbone.swa.attn`` / ``backbone.gqa.attn``): a pair is one multiply-add
    over a head's width for the score and one for ``p v``, in every query
    head; ``pairs`` of one sequence, spread over its positions."""
    return pairs / s["context"] * s["heads"] * 2 * 2 * s["head_dim"]


def position_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """Forward operations one position needs, by part."""
    d, hd = s["d"], s["head_dim"]
    # q, the gate z and o over all query heads, k and v over the grouped ones
    projections = 2.0 * d * hd * (3 * s["heads"] + 2 * s["kv_heads"])
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    return {
        "in_out": 2.0 * s["features"] * d * 2,
        "projections": projections,
        "swa_attn": attention_flops(s, window_pairs(s["context"], s["window"])),
        "gqa_attn": attention_flops(s, causal_pairs(s["context"])),
        "dense_ffn": 2.0 * 3 * d * s["dense"],
        "moe_route": 2.0 * d * s["experts"],
        "moe_experts": 2.0 * 3 * d * s["expert"] * (routed_pairs + s["shared"]),
    }


def forward_flops(s: Dict[str, Any]) -> float:
    """One position through the layers and the head."""
    part = position_flops(s)
    return (part["in_out"]
            + (part["projections"] + part["swa_attn"]) * s["swa_layers"]
            + (part["projections"] + part["gqa_attn"]) * s["gqa_layers"]
            + part["dense_ffn"] * s["dense_layers"]
            + (part["moe_route"] + part["moe_experts"]) * s["moe_layers"])


def chunk_work(config: Dict[str, Any], machines: int) -> Dict[str, Any]:
    """What ``record["work_per_chunk"]`` holds: the chunk's operations for
    the reader ``program_mfu``, and one optimiser step's operations and
    bytes under the spans that have a roofline."""
    s = shape(config)
    g = geometry(config)
    forward = forward_flops(s)
    per_model = forward * (3.0 * g["trained_positions"] + g["predicted_positions"])
    part = position_flops(s)
    n = g["positions_per_step"]
    expert_weights = 3 * s["d"] * s["expert"] * (s["shared"] + s["held"])
    routed_pairs = s["top_k"] * s["held"] / s["experts"]
    core = lambda name, layers: {  # noqa: E731
        "layers": layers,
        "flops": 3.0 * part[name] * n * layers,
        "bytes": 3.0 * attention_bytes(s) * n * layers,
    }
    return {
        **g,
        "forward_flops_per_position": forward,
        "flops_per_model": per_model,
        "flops": per_model * machines,
        "per_step": {
            "backbone.swa.attn": core("swa_attn", s["swa_layers"]),
            "backbone.gqa.attn": core("gqa_attn", s["gqa_layers"]),
            "backbone.moe.experts": {
                "layers": s["moe_layers"],
                "flops": 3.0 * part["moe_experts"] * n * s["moe_layers"],
                # forward and backward each read the weights once and move a
                # position's activations in and out once per expert it visits
                "bytes": 3.0 * s["moe_layers"] * COMPUTE_BYTES * (
                    expert_weights + 2 * s["d"] * n * (routed_pairs + s["shared"])),
            },
        },
    }
