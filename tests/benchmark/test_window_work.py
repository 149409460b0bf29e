"""``benchmark/window_work.py`` against closed forms, a count by hand and a
brute-force count of the mask, at the published widths of
``trinity-mini-plant``."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, window_work as work  # noqa: E402
from benchmark.backbone_work import fit_steps, real_positions  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini-plant.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("t,window", [(1, 1), (5, 1), (5, 3), (8, 8), (8, 20), (32, 12),
                                      (64, 16), (100, 37)])
def test_the_pair_counts_are_the_masks(t, window):
    """Row ``i`` sees key ``j`` where ``j <= i`` and, under a window, ``i - j
    < W``: counted entry by entry."""
    apart = np.arange(t)[:, None] - np.arange(t)[None, :]
    assert work.causal_pairs(t) == int((apart >= 0).sum()) == t * (t + 1) // 2
    assert work.window_pairs(t, window) == int(((apart >= 0) & (apart < window)).sum())
    assert work.window_pairs(t, t) == work.window_pairs(t, 10 * t) == work.causal_pairs(t)


def test_the_pairs_at_the_cells_shape():
    t, w = 8192, 2048
    assert work.causal_pairs(t) == 33558528                      # 33.6 M
    assert work.window_pairs(t, w) == t * w - w * (w - 1) // 2 == 14681088   # 14.7 M
    # three quarters of a sequence's positions have a window shorter than their prefix
    assert sum(1 for i in range(t) if i + 1 > w) / t == 0.75


def test_the_sequences_of_four_months(config):
    g = work.geometry(config)
    # 16,273 rows: folds train on 4,069 / 8,137 / 12,205 rows, then all of
    # them: 1, 1, 2 and 3 sequences of 8,192 at stride 4,096, one to a step
    assert [fit_steps(n, 8192, 4096, 1) for n in (4069, 8137, 12205, 16273)] == [1, 1, 2, 3]
    assert g["steps_per_model"] == 7 and g["positions_per_step"] == 8192
    assert real_positions(4069, 8192, 4096) == 4068
    assert real_positions(12205, 8192, 4096) == 8192 + (12204 - 4096)
    assert g["trained_positions"] == 4068 + 8136 + 16300 + 24461 == 52965
    assert g["trained_positions"] / (7 * 8192) > 0.92
    assert g["predicted_positions"] == 3 * 4067 + 1 == 12202    # three blocks of 4,068 rows


def test_the_layers_are_read_as_the_source_numbers_them(config):
    s = work.shape(config)
    # the source's layers 1-5: windowed + dense, windowed, FULL, windowed, windowed
    assert (s["swa_layers"], s["gqa_layers"], s["dense_layers"], s["moe_layers"]) == (4, 1, 1, 4)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["window"]) == (32, 4, 128, 2048)
    assert (s["experts"], s["top_k"], s["shared"]) == (128, 8, 1)
    assert s["held"] == config["model"]["experts_held"] and s["held"] in (8, 16)
    deeper = {**config, "depth": {**config["depth"], "layers_here": list(range(1, 9))}}
    assert work.shape(deeper)["gqa_layers"] == 2 and work.shape(deeper)["swa_layers"] == 6
    first = {**config, "depth": {**config["depth"], "layers_here": [0, 1, 2]}}
    assert work.shape(first)["dense_layers"] == 2 and work.shape(first)["moe_layers"] == 1


def test_forward_operations_of_one_position_by_hand(config):
    s = work.shape(config)
    part = work.position_flops(s)
    d = 2048
    # q, the gate and o 2,048 x 4,096 each, k and v 2,048 x 512
    assert part["projections"] == 2 * (3 * d * 4096 + 2 * d * 512) == 54525952
    # a pair is 2 x 128 multiply-adds (score, p v) in each of 32 heads
    assert part["gqa_attn"] == pytest.approx(33558528 / 8192 * 32 * 4 * 128)
    assert part["gqa_attn"] == pytest.approx(67.1e6, rel=2e-3)
    assert part["swa_attn"] == pytest.approx(14681088 / 8192 * 32 * 4 * 128)
    assert part["swa_attn"] == pytest.approx(29.4e6, rel=2e-3)
    assert part["dense_ffn"] == 2 * 3 * d * 6144
    # 8 of 128 a position, the held share of them, and the shared expert whole
    assert part["moe_experts"] == 2 * 3 * d * 1024 * (8 * s["held"] / 128 + 1)
    assert part["moe_route"] == 2 * d * 128
    forward = work.forward_flops(s)
    assert forward == pytest.approx(
        part["in_out"] + 5 * part["projections"] + 4 * part["swa_attn"] + part["gqa_attn"]
        + part["dense_ffn"] + 4 * (part["moe_route"] + part["moe_experts"]))
    cores = 4 * part["swa_attn"] + part["gqa_attn"]
    assert 0.28 < cores / forward < 0.31      # the two kinds of core: three tenths of a position


def test_a_chunks_work_and_the_spans_that_have_a_roofline(config):
    one, three = work.chunk_work(config, 1), work.chunk_work(config, 3)
    s, g = work.shape(config), work.geometry(config)
    assert one["flops_per_model"] == pytest.approx(
        work.forward_flops(s) * (3 * g["trained_positions"] + g["predicted_positions"]))
    assert three["flops"] == pytest.approx(3 * one["flops"])
    assert set(one["per_step"]) == {"backbone.swa.attn", "backbone.gqa.attn",
                                    "backbone.moe.experts"}
    swa, gqa = one["per_step"]["backbone.swa.attn"], one["per_step"]["backbone.gqa.attn"]
    assert (swa["layers"], gqa["layers"]) == (4, 1)
    # one sequence a step: forward and backward of the pairs of one sequence a layer
    pair = 32 * 4 * 128
    assert swa["flops"] == pytest.approx(3 * 4 * 14681088 * pair)
    assert gqa["flops"] == pytest.approx(3 * 33558528 * pair)
    # q read and o written over 32 heads, k and v over 4, two bytes each, a position
    assert gqa["bytes"] == 3 * 8192 * 2 * 128 * (32 + 4 + 4 + 32)
    assert swa["bytes"] == 4 * gqa["bytes"]
    peaks = device.peaks("TPU v5 lite")
    for span in (swa, gqa):                   # both bound by operations
        assert span["flops"] / peaks["flops_per_s"] > 5 * span["bytes"] / peaks["bytes_per_s"]
    # under a window the same layer needs less than half the full layer's operations
    assert swa["flops"] / 4 < 0.45 * gqa["flops"]
