"""``benchmark/backbone_work.py`` against a hand count, at the published
widths of ``kimi-linear-plant`` and at a size small enough to add up on
paper."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import backbone_work as work  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-plant.json")) as fh:
        return json.load(fh)


def test_the_sequences_of_a_quarter(config):
    g = work.geometry(config)
    # 13,105 rows: folds train on 3,276 / 6,552 / 9,828 rows, then all of them
    assert [work.fit_steps(n, 1024, 512, 8) for n in (3276, 6552, 9828, 13105)] == [1, 2, 3, 4]
    assert g["steps_per_model"] == 10 and g["positions_per_step"] == 8192
    # rows 0 .. n - 2 are read, twice where two sequences overlap: 3,275
    # inputs are 6 sequences from rows 0, 512, .. 2,560, the last cut short
    assert work.real_positions(3276, 1024, 512) == 5 * 1024 + (3275 - 5 * 512)
    assert work.real_positions(2, 1024, 512) == 1
    assert g["predicted_positions"] == 2 * work.real_positions(3276, 1024, 512) + \
        work.real_positions(3277, 1024, 512)


def test_forward_operations_of_one_position_by_hand(config):
    s = work.shape(config)
    part = work.position_flops(s)
    d = 2304
    # dense feed-forward: three d x 9216 matrices
    assert part["dense_ffn"] == 2 * 3 * d * 9216
    # one expert: three d x 1024 matrices; the shared one and 8 x 8 / 256 routed
    assert part["moe_experts"] == 2 * 3 * d * 1024 * (1 + 0.25)
    assert part["moe_route"] == 2 * d * 256
    # KDA: q, k, v and o (d x 4096), two low-rank gates (d x 128, 128 x 4096),
    # beta (d x 32), three width-4 convolutions, and the chunked delta rule
    kda_proj = 2 * d * 4096 * 4 + 2 * 2 * (d * 128 + 128 * 4096) + 2 * d * 32 + 2 * 4 * 3 * 4096
    scan = 32 * (2 * 64 * 128 + 64 * 256 + 64 * 128 + 6 * 128 * 128)
    assert work.kda_scan_flops(s) == scan == 4456448
    assert part["kda"] == kda_proj + scan
    # MLA: q (d x 32 x 192), the latent (d x 576), its expansion (512 x 32 x
    # 256), o (4096 x d), and half of 1,024 keys for scores and values
    mla = 2 * d * 32 * 192 + 2 * d * 576 + 2 * 512 * 32 * 256 + 2 * 4096 * d \
        + 1024 * 32 * (192 + 128)
    assert part["mla"] == mla
    # five layers: KDA x 4, MLA x 1, dense x 1, experts x 4, in and out
    total = 2 * 2 * 50 * d + 4 * part["kda"] + mla + part["dense_ffn"] \
        + 4 * (part["moe_route"] + part["moe_experts"])
    assert work.forward_flops(s) == total
    assert 0.60e9 < total < 0.61e9


def test_a_chunks_work_and_the_two_spans(config):
    w = work.chunk_work(config, 1)
    g = work.geometry(config)
    forward = w["forward_flops_per_position"]
    assert w["flops_per_model"] == forward * (
        3 * g["trained_positions"] + g["predicted_positions"])
    assert w["flops"] == w["flops_per_model"]
    assert work.chunk_work(config, 2)["flops"] == 2 * w["flops_per_model"]
    scan = w["per_step"]["backbone.kda.scan"]
    assert scan["layers"] == 4
    assert scan["flops"] == 3 * 4456448 * 8192 * 4
    # q, k, v in and o out in bfloat16, the decay in float32: 12 bytes a channel
    assert scan["bytes"] == 3 * (4096 * 12) * 8192 * 4
    experts = w["per_step"]["backbone.moe.experts"]
    assert experts["flops"] == 3 * (2 * 3 * 2304 * 1024 * 1.25) * 8192 * 4
    weights = 3 * 2304 * 1024 * 9                   # the shared and 8 held experts
    moved = 2 * 2304 * 8192 * 1.25                  # positions in and out
    assert experts["bytes"] == 3 * 4 * 2 * (weights + moved)
