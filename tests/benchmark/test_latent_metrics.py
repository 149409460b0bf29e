"""The fifteen ``glm.*`` per-layer metrics of ``glm-flash.build-horizons``:
each is listed on that cell alone and names a reader that is there, and each
reads what it says from a hand-made record, a hand-made trace or the trace
recorded on the chip at the tiny preset (PR 34: two executions of a
three-step fit of both horizons and a forecast, hidden 64, three layers and
the multi-token-prediction module, ``benchmark/testdata/tiny_latent.xplane.pb``: the
recording's device plane alone, its host and metadata planes taken off)."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, latent_work, readers, trace as tr  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.readers import trace_scope_seconds  # noqa: E402

MANIFEST = Manifest(ROOT)
CELL = "glm-flash.build-horizons"
TRACE = os.path.join(ROOT, "benchmark", "testdata", "tiny_latent.xplane.pb")
STAGE = "gordo_build_pipeline_stage_seconds"
STAGES = {
    "glm.program_s_per_model": ["program"], "glm.device_gap_s_per_model": ["device_gap"],
    "glm.load_s_per_model": ["load"], "glm.fetch_exposed_s_per_model": ["fetch_exposed"],
    "glm.write_s_per_model": ["write"], "glm.stage_s_per_model": ["stage", "enqueue"],
}
SCOPES = {"glm.mla_s_per_step": "backbone.mla", "glm.moe_s_per_step": "backbone.moe",
          "glm.mtp_s_per_step": "backbone.mtp"}
ROOFLINES = {"glm.mla_attn_roofline": "backbone.mla.attn",
             "glm.moe_experts_roofline": "backbone.moe.experts"}
OTHERS = {"glm.gap_load_s", "glm.program_mfu", "glm.compile_backend_s",
          "glm.expert_load_max_over_mean"}
NAMES = sorted({*STAGES, *SCOPES, *ROOFLINES, *OTHERS})


def spec(name):
    return MANIFEST.metric_spec(name)


def histogram(**by_label):
    return {"series": {json.dumps([label]): {"sum": s, "count": c}
                       for label, (s, c) in by_label.items()}}


@pytest.fixture(scope="module")
def config():
    return MANIFEST.config("glm-flash-plant")


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_listed_on_its_cell_alone_and_names_a_reader_that_is_there(name):
    assert len(NAMES) == 15
    (metric,) = [m for m in MANIFEST.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == ("setup_s" if name == "glm.compile_backend_s"
                               else "build.models_per_h_per_chip")
    body = spec(name)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", body["reader"] + ".py"))
    assert len(body["what"]) > 20
    # the accepted metric of the same reading keeps its one-cell list
    twin = "seq." + name.split(".", 1)[1]
    for other in MANIFEST.doc["per_layer"]:
        if other["name"] == twin:
            assert other["workloads"] == ["kimi-linear.build-series"]


def test_no_copy_of_the_two_metrics_that_mislead():
    names = {m["name"] for m in MANIFEST.doc["per_layer"]}
    assert "glm.fetch_s_per_model" not in names and "glm.launch_lag_s" not in names
    assert {n for n in names if n.startswith("glm.")} == set(NAMES)


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_seconds_of_the_window_per_machine(name):
    """Seven machines observed by the window's end, one of them in set-up."""
    labels = STAGES[name]
    start = histogram(**{label: (2.0, 1) for label in labels})
    end = histogram(**{label: (2.0 + 6 * 1.5, 7) for label in labels})
    record = {"chunk_machines": 1, "snap_start": {STAGE: start}, "snap_end": {STAGE: end}}
    assert readers.read(spec(name), record) == pytest.approx(1.5 * len(labels))
    bare = {**record, "snap_start": {STAGE: histogram(other=(1.0, 1))},
            "snap_end": {STAGE: histogram(other=(3.0, 3))}}
    assert readers.read(spec(name), bare) is None


def test_the_whole_programs_share_of_the_peak(config):
    work = latent_work.chunk_work(config, 1)
    peak = device.peaks("TPU v5 lite")["flops_per_s"]
    record = {"device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work,
              "snap_start": {STAGE: histogram(program=(5.0, 1))},
              "snap_end": {STAGE: histogram(program=(5.0 + 6 * 5.0, 7))}}
    value = readers.read(spec("glm.program_mfu"), record)
    assert value == pytest.approx(100 * work["flops_per_model"] / (5.0 * peak))
    assert 13.0 < value < 13.2               # 128.9 TFLOP in 5 s of a 197 TFLOP/s chip
    record["snap_end"] = {STAGE: histogram(program=(5.0 + 6 * work["flops"] / peak, 7))}
    assert readers.read(spec("glm.program_mfu"), record) == pytest.approx(100.0)


def test_compile_seconds_and_the_experts_balance():
    counter = lambda **v: {"series": {json.dumps(list(k) if isinstance(k, tuple) else [k]): x  # noqa: E731
                                      for k, x in v.items()}}
    record = {"snap_start": {"gordo_compile_jax_seconds_total":
                             counter(backend=80.0, trace=30.0, lower=26.0)},
              "snap_end": {}}
    assert readers.read(spec("glm.compile_backend_s"), record) == pytest.approx(136.0)
    tokens = lambda scale: {"series": {  # noqa: E731
        json.dumps([layer, str(e)]): scale * (700.0 if (layer, e) == ("mtp", 3) else 500.0)
        for layer in ("2", "3", "4", "5", "mtp") for e in range(8)}}
    record = {"snap_start": {"gordo_moe_tokens_total": tokens(1)},
              "snap_end": {"gordo_moe_tokens_total": tokens(7)}}
    # forty series, the module's layer among them: one of them 1.4 times the rest
    assert readers.read(spec("glm.expert_load_max_over_mean"), record) == pytest.approx(
        700 / ((39 * 500 + 700) / 40))
    assert readers.read(spec("glm.expert_load_max_over_mean"),
                        {"snap_start": {}, "snap_end": {}}) is None


def test_the_gaps_overlap_with_the_next_machines_load():
    ops = [("%fusion.1", 0.0, 0.2), ("%while.7", 1.5, 4.0)]
    trace = tr.Trace(
        devices=[tr.DeviceTrace("/device:TPU:0", ops=ops, modules=[])],
        spans=[("bench.window", 0.0, 4.0)],
        host=[("gordo.build.load.fetch", 0.3, 0.6), ("gordo.build.load.finalize", 0.6, 0.7),
              ("gordo.build.enqueue", 1.4, 1.45)])
    assert readers.read(spec("glm.gap_load_s"), {"trace": trace}) == pytest.approx(0.4)
    assert readers.read(spec("glm.gap_load_s"), {}) is None
    assert {k: v for k, v in spec("glm.gap_load_s").items() if k != "what"} == {
        k: v for k, v in spec("seq.gap_load_s").items() if k != "what"}


# -- the scopes, on the trace recorded on the chip ------------------------------

@pytest.fixture(scope="module")
def found():
    if trace_scope_seconds.xplane_messages() is None:
        pytest.skip("no xplane_pb2 in this installation")
    scopes = sorted({*SCOPES.values(), *ROOFLINES.values(), "backbone.ffn",
                     "backbone.moe.route", "backbone.kda"})
    return trace_scope_seconds.scope_seconds(TRACE, scopes)


def record_of(tmp_path, work):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(TRACE, trace_dir / "host.xplane.pb")
    return {"trace_dir": str(trace_dir), "snap_start": {}, "snap_end": {},
            "device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work}


def test_two_whole_programs_hold_every_new_scope(found):
    totals, programs = found
    assert programs == 2
    assert totals["backbone.kda"] == 0.0         # no such layer in this model
    for scope in ("backbone.mla", "backbone.mla.attn", "backbone.mtp", "backbone.moe",
                  "backbone.moe.experts", "backbone.moe.route", "backbone.ffn"):
        assert totals[scope] > 0, scope
    # a scope holds the scopes nested in it; the module holds a block of each
    assert totals["backbone.mla"] > totals["backbone.mla.attn"]
    assert totals["backbone.moe"] > totals["backbone.moe.experts"]
    assert totals["backbone.mtp"] < totals["backbone.mla"] + totals["backbone.moe"]


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_scope_seconds_per_step(name, found, tmp_path):
    totals, _ = found
    record = record_of(tmp_path, {"steps_per_model": 3})
    assert readers.read(spec(name), record) == pytest.approx(totals[SCOPES[name]] / 6)


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_roofline_shares_read_the_work_count_under_their_scope(name, found, tmp_path, config):
    totals, _ = found
    scope = ROOFLINES[name]
    per_step = latent_work.chunk_work(config, 1)["per_step"]
    assert scope in per_step
    peaks = device.peaks("TPU v5 lite")
    # one microsecond of work at the peak, bound by operations as both are
    # at the published widths
    assert per_step[scope]["flops"] / peaks["flops_per_s"] > \
        per_step[scope]["bytes"] / peaks["bytes_per_s"]
    work = {"steps_per_model": 3, "per_step": {
        scope: {"flops": peaks["flops_per_s"] * 1e-6, "bytes": 1.0}}}
    share = readers.read(spec(name), record_of(tmp_path, work))
    assert share == pytest.approx(100.0 * 1e-6 / (totals[scope] / 6))
    assert 0 < share < 100
    # a program without the scope (the parent's): nothing to read, no error
    bare = tmp_path / "bare"
    bare.mkdir()
    assert readers.read(spec(name), record_of(bare, {"steps_per_model": 3, "per_step": {}})) is None
