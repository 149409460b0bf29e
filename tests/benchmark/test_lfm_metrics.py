"""The sixteen ``lfm.*`` per-layer metrics of ``lfm2-moe.build-fortnight``:
each is listed on that cell alone and names a reader that is there, and each
reads what it says from a hand-made record, a hand-made trace or the trace
recorded on the chip at the tiny preset (PR 37: two executions of a
three-step fit and a forecast, hidden 64, five layers (conv + dense,
attention + experts, three times conv + experts), sequences of four blocks,
``benchmark/testdata/tiny_hybrid.xplane.pb``: the recording's device plane
alone, its host and metadata planes taken off)."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, hybrid_work, readers, trace as tr  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.readers import trace_scope_seconds  # noqa: E402

MANIFEST = Manifest(ROOT)
CELL = "lfm2-moe.build-fortnight"
TRACE = os.path.join(ROOT, "benchmark", "testdata", "tiny_hybrid.xplane.pb")
STAGE = "gordo_build_pipeline_stage_seconds"
STAGES = {
    "lfm.program_s_per_model": ["program"], "lfm.device_gap_s_per_model": ["device_gap"],
    "lfm.load_s_per_model": ["load"], "lfm.fetch_exposed_s_per_model": ["fetch_exposed"],
    "lfm.write_s_per_model": ["write"], "lfm.stage_s_per_model": ["stage", "enqueue"],
}
SCOPES = {"lfm.conv_s_per_step": "backbone.conv", "lfm.gqa_s_per_step": "backbone.gqa",
          "lfm.moe_s_per_step": "backbone.moe"}
ROOFLINES = {"lfm.conv_mixer_roofline": "backbone.conv",
             "lfm.gqa_attn_roofline": "backbone.gqa.attn",
             "lfm.moe_experts_roofline": "backbone.moe.experts"}
OTHERS = {"lfm.gap_load_s", "lfm.program_mfu", "lfm.compile_backend_s",
          "lfm.expert_load_max_over_mean"}
NAMES = sorted({*STAGES, *SCOPES, *ROOFLINES, *OTHERS})


def spec(name):
    return MANIFEST.metric_spec(name)


def histogram(**by_label):
    return {"series": {json.dumps([label]): {"sum": s, "count": c}
                       for label, (s, c) in by_label.items()}}


@pytest.fixture(scope="module")
def config():
    return MANIFEST.config("lfm2-moe-plant")


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_listed_on_its_cell_alone_and_names_a_reader_that_is_there(name):
    assert len(NAMES) == 16
    (metric,) = [m for m in MANIFEST.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == ("setup_s" if name == "lfm.compile_backend_s"
                               else "build.models_per_h_per_chip")
    body = spec(name)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", body["reader"] + ".py"))
    assert len(body["what"]) > 20
    # the accepted metric of the same reading is on the same reader, in the
    # same layer, and keeps its one-cell list
    twin = "glm." + name.split(".", 1)[1]
    for other in MANIFEST.doc["per_layer"]:
        if other["name"] == twin:
            assert other["workloads"] == ["glm-flash.build-horizons"]
            assert other["layer"] == metric["layer"] and other["unit"] == metric["unit"]
            assert spec(twin)["reader"] == body["reader"]


def test_the_cell_lists_the_sixteen_and_no_accepted_metric_names_it():
    names = {m["name"] for m in MANIFEST.doc["per_layer"]}
    assert {n for n in names if n.startswith("lfm.")} == set(NAMES)
    assert {m["name"] for m in MANIFEST.metrics_of(CELL, "per_layer")} == set(NAMES)
    assert {m["name"] for m in MANIFEST.metrics_of(CELL, "end_to_end")} == {
        "build.models_per_h_per_chip", "setup_s"}
    # the share of the whole step's peak and every kernel's share are among them
    assert {n for n in NAMES if "mfu" in n or "roofline" in n} == {
        "lfm.program_mfu", *ROOFLINES}


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_seconds_of_the_window_per_machine(name):
    """Eleven machines observed by the window's end, one of them in set-up."""
    labels = STAGES[name]
    start = histogram(**{label: (2.0, 1) for label in labels})
    end = histogram(**{label: (2.0 + 10 * 1.5, 11) for label in labels})
    record = {"chunk_machines": 1, "snap_start": {STAGE: start}, "snap_end": {STAGE: end}}
    assert readers.read(spec(name), record) == pytest.approx(1.5 * len(labels))
    bare = {**record, "snap_start": {STAGE: histogram(other=(1.0, 1))},
            "snap_end": {STAGE: histogram(other=(3.0, 3))}}
    assert readers.read(spec(name), bare) is None


def test_the_whole_programs_share_of_the_peak(config):
    work = hybrid_work.chunk_work(config, 1)
    peak = device.peaks("TPU v5 lite")["flops_per_s"]
    record = {"device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work,
              "snap_start": {STAGE: histogram(program=(4.0, 1))},
              "snap_end": {STAGE: histogram(program=(4.0 + 10 * 4.0, 11))}}
    value = readers.read(spec("lfm.program_mfu"), record)
    assert value == pytest.approx(100 * work["flops_per_model"] / (4.0 * peak))
    assert 15.4 < value < 15.7               # 122.4 TFLOP in 4 s of a 197 TFLOP/s chip
    record["snap_end"] = {STAGE: histogram(program=(4.0 + 10 * work["flops"] / peak, 11))}
    assert readers.read(spec("lfm.program_mfu"), record) == pytest.approx(100.0)


def test_compile_seconds_and_the_experts_balance():
    counter = lambda **v: {"series": {json.dumps([k]): x for k, x in v.items()}}  # noqa: E731
    record = {"snap_start": {"gordo_compile_jax_seconds_total":
                             counter(backend=60.0, trace=25.0, lower=20.0)},
              "snap_end": {}}
    assert readers.read(spec("lfm.compile_backend_s"), record) == pytest.approx(105.0)
    tokens = lambda scale: {"series": {  # noqa: E731
        json.dumps([layer, str(e)]): scale * (1400.0 if (layer, e) == ("4", 3) else 1000.0)
        for layer in ("2", "3", "4", "5") for e in range(8)}}
    record = {"snap_start": {"gordo_moe_tokens_total": tokens(1)},
              "snap_end": {"gordo_moe_tokens_total": tokens(7)}}
    # thirty-two series, four expert layers of eight held: one 1.4 times the rest
    assert readers.read(spec("lfm.expert_load_max_over_mean"), record) == pytest.approx(
        1400 / ((31 * 1000 + 1400) / 32))
    assert readers.read(spec("lfm.expert_load_max_over_mean"),
                        {"snap_start": {}, "snap_end": {}}) is None


def test_the_gaps_overlap_with_the_next_machines_load():
    ops = [("%fusion.1", 0.0, 0.2), ("%while.7", 1.5, 4.0)]
    trace = tr.Trace(
        devices=[tr.DeviceTrace("/device:TPU:0", ops=ops, modules=[])],
        spans=[("bench.window", 0.0, 4.0)],
        host=[("gordo.build.load.fetch", 0.3, 0.6), ("gordo.build.load.finalize", 0.6, 0.7),
              ("gordo.build.enqueue", 1.4, 1.45)])
    assert readers.read(spec("lfm.gap_load_s"), {"trace": trace}) == pytest.approx(0.4)
    assert readers.read(spec("lfm.gap_load_s"), {}) is None
    assert {k: v for k, v in spec("lfm.gap_load_s").items() if k != "what"} == {
        k: v for k, v in spec("glm.gap_load_s").items() if k != "what"}


# -- the scopes, on the trace recorded on the chip ------------------------------

@pytest.fixture(scope="module")
def found():
    if trace_scope_seconds.xplane_messages() is None:
        pytest.skip("no xplane_pb2 in this installation")
    scopes = sorted({*SCOPES.values(), *ROOFLINES.values(), "backbone.conv.gate",
                     "backbone.ffn", "backbone.moe.route", "backbone.kda", "backbone.mla"})
    return trace_scope_seconds.scope_seconds(TRACE, scopes)


def record_of(tmp_path, work):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir(parents=True)
    shutil.copy(TRACE, trace_dir / "host.xplane.pb")
    return {"trace_dir": str(trace_dir), "snap_start": {}, "snap_end": {},
            "device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work}


def test_two_whole_programs_hold_every_new_scope(found):
    totals, programs = found
    assert programs == 2
    for absent in ("backbone.kda", "backbone.mla"):      # no such layer in this model
        assert totals[absent] == 0.0
    for scope in ("backbone.conv", "backbone.conv.gate", "backbone.gqa", "backbone.gqa.attn",
                  "backbone.moe", "backbone.moe.experts", "backbone.moe.route", "backbone.ffn"):
        assert totals[scope] > 0, scope
    # a scope holds the scopes nested in it
    assert totals["backbone.conv"] > totals["backbone.conv.gate"]
    assert totals["backbone.gqa"] > totals["backbone.gqa.attn"]
    assert totals["backbone.moe"] > totals["backbone.moe.experts"]


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_scope_seconds_per_step(name, found, tmp_path):
    totals, _ = found
    record = record_of(tmp_path, {"steps_per_model": 3})
    assert readers.read(spec(name), record) == pytest.approx(totals[SCOPES[name]] / 6)


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_roofline_shares_read_the_work_count_under_their_scope(name, found, tmp_path, config):
    totals, _ = found
    scope = ROOFLINES[name]
    per_step = hybrid_work.chunk_work(config, 1)["per_step"]
    assert scope in per_step
    peaks = device.peaks("TPU v5 lite")
    # bound by operations, as all three are at the published widths
    assert per_step[scope]["flops"] / peaks["flops_per_s"] > \
        per_step[scope]["bytes"] / peaks["bytes_per_s"]
    work = {"steps_per_model": 3, "per_step": {
        scope: {"flops": peaks["flops_per_s"] * 1e-6, "bytes": 1.0}}}
    share = readers.read(spec(name), record_of(tmp_path, work))
    assert share == pytest.approx(100.0 * 1e-6 / (totals[scope] / 6))
    assert 0 < share < 100
    # bound by bytes where the bytes say so
    work["per_step"][scope] = {"flops": 1.0, "bytes": peaks["bytes_per_s"] * 2e-6}
    assert readers.read(spec(name), record_of(tmp_path / "b", work)) == pytest.approx(2 * share)
    # a program without the scope (the parent's): nothing to read, no error
    bare = tmp_path / "bare"
    bare.mkdir()
    assert readers.read(spec(name), record_of(bare, {"steps_per_model": 3, "per_step": {}})) is None


@pytest.mark.parametrize("name", sorted(n for n in {*SCOPES, *ROOFLINES} if "moe" not in n))
def test_a_trace_of_another_backbone_has_nothing_under_the_new_scopes(name, tmp_path, config):
    """What the parent's program gives these readers: ``None``, no error (the
    latent backbone's trace; it has expert layers too, so those are left out)."""
    if trace_scope_seconds.xplane_messages() is None:
        pytest.skip("no xplane_pb2 in this installation")
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(os.path.join(ROOT, "benchmark", "testdata", "tiny_latent.xplane.pb"),
                trace_dir / "host.xplane.pb")
    record = {"trace_dir": str(trace_dir), "snap_start": {}, "snap_end": {},
              "device_kind": "TPU v5 lite", "chips": 1,
              "work_per_chunk": hybrid_work.chunk_work(config, 1)}
    assert readers.read(spec(name), record) is None
