"""``benchmark/latent_work.py`` against a hand count, at the published
widths of ``glm-flash-plant``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import latent_work as work  # noqa: E402
from benchmark.backbone_work import fit_steps, real_positions  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-flash-plant.json")) as fh:
        return json.load(fh)


def test_the_sequences_of_a_quarter(config):
    g = work.geometry(config)
    # 13,105 rows: folds train on 3,276 / 6,552 / 9,828 rows, then all of them:
    # 3, 6, 9 and 12 sequences of 2,048 at stride 1,024, four to a step
    assert [fit_steps(n, 2048, 1024, 4) for n in (3276, 6552, 9828, 13105)] == [1, 2, 3, 3]
    assert g["steps_per_model"] == 9 and g["positions_per_step"] == 8192
    # 3,275 inputs are 3 sequences from rows 0, 1,024 and 2,048, the last cut short
    assert real_positions(3276, 2048, 1024) == 2 * 2048 + (3275 - 2048)
    assert g["trained_positions"] == 59381 and g["predicted_positions"] == 15970


def test_forward_operations_of_one_position_by_hand(config):
    s = work.shape(config)
    part = work.position_flops(s)
    d = 2048
    assert part["dense_ffn"] == 2 * 3 * d * 10240
    # one expert: three d x 1536 matrices; the shared one and 4 x 8 / 64 routed
    assert part["moe_experts"] == 2 * 3 * d * 1536 * (1 + 0.5)
    assert part["moe_route"] == 2 * d * 64
    # MLA: queries down (d x 768) and up (768 x 20 x 256), the latent (d x 576),
    # its expansion (512 x 20 x 448), o (5120 x d), and half of 2,048 keys for
    # scores over 256 channels and values over 256
    proj = 2 * (d * 768 + 768 * 5120 + d * 576 + 512 * 8960 + 5120 * d)
    assert proj == 43515904
    assert work.attention_flops(s) == 2048 * 20 * (256 + 256) == 20971520
    assert part["mla"] == proj + 20971520
    # q, k_n, the shared k_r, v in and o out, two bytes each
    assert work.attention_bytes(s) == 2 * (20 * 256 + 20 * 192 + 64 + 2 * 20 * 256)
    # five layers: MLA x 5, dense x 1, experts x 4, in and out
    main = 2 * 2 * 50 * d + 5 * part["mla"] + part["dense_ffn"] \
        + 4 * (part["moe_route"] + part["moe_experts"])
    assert work.forward_flops(s) == main
    assert 0.56e9 < main < 0.57e9
    # the module: W_eh (4096 x 2048), one MLA, one expert layer, the head again
    module = 2 * 4096 * d + part["mla"] + part["moe_route"] + part["moe_experts"] + 2 * d * 50
    assert work.module_flops(s) == module
    assert 0.67e9 < main + module < 0.68e9


def test_a_chunks_work_and_the_two_spans(config):
    w = work.chunk_work(config, 1)
    main, module = w["forward_flops_per_position"], w["module_flops_per_position"]
    # the module runs where a position is trained, never where it is forecast
    assert w["flops_per_model"] == 3 * (main + module) * 59381 + main * 15970
    assert 128e12 < w["flops_per_model"] < 130e12
    assert work.chunk_work(config, 2)["flops"] == 2 * w["flops_per_model"]
    attn = w["per_step"]["backbone.mla.attn"]
    assert attn["layers"] == 6                   # five layers and the module's block
    assert attn["flops"] == 3 * 20971520 * 8192 * 6
    assert attn["bytes"] == 3 * 38528 * 8192 * 6
    experts = w["per_step"]["backbone.moe.experts"]
    assert experts["layers"] == 5                # four layers and the module's block
    assert experts["flops"] == 3 * (2 * 3 * 2048 * 1536 * 1.5) * 8192 * 5
    weights = 3 * 2048 * 1536 * 9                # the shared and 8 held experts
    moved = 2 * 2048 * 8192 * 1.5                # positions in and out
    assert experts["bytes"] == 3 * 5 * 2 * (weights + moved)
    assert set(w["per_step"]) == {"backbone.mla.attn", "backbone.moe.experts"}
