"""``benchmark/hybrid_work.py`` against a hand count, at the published widths
of ``lfm2-moe-plant``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import hybrid_work as work  # noqa: E402
from benchmark.backbone_work import fit_steps, real_positions  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-moe-plant.json")) as fh:
        return json.load(fh)


def test_the_sequences_of_a_quarter(config):
    g = work.geometry(config)
    # 13,105 rows: folds train on 3,276 / 6,552 / 9,828 rows, then all of them:
    # 4, 10, 17 and 23 sequences of 2,048 at stride 512, eight to a step
    assert [fit_steps(n, 2048, 512, 8) for n in (3276, 6552, 9828, 13105)] == [1, 2, 3, 3]
    assert g["steps_per_model"] == 9 and g["positions_per_step"] == 16384
    # 3,275 inputs are 4 sequences from rows 0, 512, 1,024 and 1,536, the last cut short
    assert real_positions(3276, 2048, 512) == 3 * 2048 + (3275 - 1536) == 7883
    assert g["trained_positions"] == sum(
        real_positions(n, 2048, 512) for n in (3276, 6552, 9828, 13105))
    assert g["trained_positions"] == 7883 + 20375 + 34403 + 46896 == 109557
    assert g["predicted_positions"] == 2 * 7883 + real_positions(3277, 2048, 512) == 23650


def test_the_layers_are_read_as_the_source_numbers_them(config):
    s = work.shape(config)
    # the source's layers 1-5: conv + dense, attention, three convolutions
    assert (s["conv_layers"], s["gqa_layers"], s["dense_layers"], s["moe_layers"]) == (4, 1, 1, 4)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["taps"]) == (32, 8, 64, 3)
    deeper = {**config, "depth": {**config["depth"], "layers_here": [1, 2, 3, 4, 5, 6]}}
    assert work.shape(deeper)["gqa_layers"] == 2 and work.shape(deeper)["moe_layers"] == 5


def test_forward_operations_of_one_position_by_hand(config):
    s = work.shape(config)
    part = work.position_flops(s)
    d = 2048
    # the convolution: W_in (d x 3d) and W_out (d x d); gates and taps are no products
    assert part["conv"] == work.conv_flops(s) == 2 * (d * 3 * d + d * d) == 33554432
    # attention: q and o (d x 2048 each), k and v (d x 512 each), and half of
    # 2,048 keys for scores and values over 64 channels of 32 query heads
    proj = 2 * (d * 2048 + 2 * d * 512 + 2048 * d)
    assert proj == 20971520
    assert work.attention_flops(s) == 2048 * 32 * (64 + 64) == 8388608
    assert part["gqa"] == proj + 8388608
    # q in and o out of 32 heads, k and v of 8, two bytes each
    assert work.attention_bytes(s) == 2 * 64 * (32 + 8 + 8 + 32)
    assert part["dense_ffn"] == 2 * 3 * d * 11776
    # one expert: three d x 1536 matrices; 4 x 8 / 64 routed and NO shared one
    assert part["moe_experts"] == 2 * 3 * d * 1536 * 0.5
    assert part["moe_route"] == 2 * d * 64
    main = 2 * 2 * 50 * d + 4 * part["conv"] + part["gqa"] + part["dense_ffn"] \
        + 4 * (part["moe_route"] + part["moe_experts"])
    assert work.forward_flops(s) == main
    assert 0.347e9 < main < 0.348e9
    # by operations: the dense layer 42 %, the two new mixers 47 %, the experts 11 %
    assert 0.41 < part["dense_ffn"] / main < 0.42
    assert 0.46 < (4 * part["conv"] + part["gqa"]) / main < 0.48
    assert 0.11 < 4 * (part["moe_route"] + part["moe_experts"]) / main < 0.12


def test_a_chunks_work_and_the_three_spans(config):
    w = work.chunk_work(config, 1)
    main = w["forward_flops_per_position"]
    assert w["flops_per_model"] == main * (3 * w["trained_positions"] + w["predicted_positions"])
    # 9 steps hold 147,456 slots; 109,557 of them read a real row and are counted
    assert 122e12 < w["flops_per_model"] < 123e12
    assert work.chunk_work(config, 2)["flops"] == 2 * w["flops_per_model"]
    n = 16384
    conv = w["per_step"]["backbone.conv"]
    assert conv["layers"] == 4
    assert conv["flops"] == 3 * 33554432 * n * 4
    # a pass reads the two matrices once and moves a row in and a row out
    assert conv["bytes"] == 3 * 4 * (2 * 4 * 2048 * 2048 + 2 * 2 * 2048 * n)
    attn = w["per_step"]["backbone.gqa.attn"]
    assert attn["layers"] == 1
    assert attn["flops"] == 3 * 8388608 * n and attn["bytes"] == 3 * 10240 * n
    experts = w["per_step"]["backbone.moe.experts"]
    assert experts["layers"] == 4
    assert experts["flops"] == 3 * (2 * 3 * 2048 * 1536 * 0.5) * n * 4
    weights = 3 * 2048 * 1536 * 8                # the 8 held experts, no shared one
    moved = 2 * 2048 * n * 0.5                   # positions in and out
    assert experts["bytes"] == 3 * 4 * 2 * (weights + moved)
    assert set(w["per_step"]) == {"backbone.conv", "backbone.gqa.attn", "backbone.moe.experts"}
