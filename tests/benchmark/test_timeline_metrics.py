"""The per-layer metrics that read the program's chunk timeline (PR 25):
the four new readers on hand-made records and a hand-made trace, and a
traced run on the CPU that reports the counter-based ones and leaves the
device-trace ones out."""

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, readers, run as bench_run, trace as tr  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

STAGE = "gordo_build_pipeline_stage_seconds"
COMPILE = "gordo_compile_jax_seconds_total"
CELLS = ("lstm-hourglass.build-plant", "lstm-symmetric.build-plant")
NEW = {
    "build.device_program_s_per_model", "build.device_gap_s_per_model",
    "build.stage_s_per_model", "build.fetch_exposed_s_per_model",
    "build.program_mfu", "compile.window_s",
    "device.gap_load_s", "device.launch_lag_s",
}
FROM_THE_TRACE = {"device.gap_load_s", "device.launch_lag_s"}


def spec(name):
    return Manifest(ROOT).metric_spec(name)


def histogram(**by_label):
    return {"series": {json.dumps([label]): {"sum": s, "count": c}
                       for label, (s, c) in by_label.items()}}


def test_every_new_metric_is_listed_on_both_cells_with_a_spec_file():
    doc = Manifest(ROOT).doc
    listed = {m["name"]: m for m in doc["per_layer"] if m["name"] in NEW}
    assert set(listed) == NEW
    for name, metric in listed.items():
        assert tuple(metric["workloads"]) == CELLS
        assert metric["moves"] == "build.models_per_h_per_chip"
        assert spec(name)["reader"] in {
            "histogram_sum_delta", "program_mfu", "counter_window_delta",
            "trace_gap_span_overlap", "trace_launch_lag"}


@pytest.mark.parametrize("name, per_model", [
    ("build.device_program_s_per_model", (58.5 - 19.5) / (2 * 32)),
    ("build.device_gap_s_per_model", (9.0 - 3.0) / (2 * 32)),
    ("build.stage_s_per_model", (0.9 - 0.3 + 0.06 - 0.02) / (2 * 32)),
    ("build.fetch_exposed_s_per_model", (0.3 - 0.1) / (2 * 32)),
])
def test_stage_seconds_of_the_window_per_machine(name, per_model):
    """Three chunks observed by the window's end, one of them in set-up."""
    record = {
        "chunk_machines": 32,
        "snap_start": {STAGE: histogram(
            program=(19.5, 1), device_gap=(3.0, 1), stage=(0.3, 1),
            enqueue=(0.02, 1), fetch_exposed=(0.1, 1))},
        "snap_end": {STAGE: histogram(
            program=(58.5, 3), device_gap=(9.0, 3), stage=(0.9, 3),
            enqueue=(0.06, 3), fetch_exposed=(0.3, 3))},
    }
    assert readers.read(spec(name), record) == pytest.approx(per_model)
    # a program that does not observe these label values: nothing to read
    bare = {**record, "snap_start": {STAGE: histogram(load=(1.0, 1))},
            "snap_end": {STAGE: histogram(load=(3.0, 3))}}
    assert readers.read(spec(name), bare) is None


def test_program_mfu_against_a_hand_count_and_never_above_100():
    peak = device.peaks("TPU v5 lite")["flops_per_s"]
    flops = 44.6e12
    record = {
        "device_kind": "TPU v5 lite", "chips": 1,
        "work_per_chunk": {"flops": flops},
        "snap_start": {STAGE: histogram(program=(19.0, 1))},
        "snap_end": {STAGE: histogram(program=(19.0 + 2 * 19.5, 3))},
    }
    value = readers.read(spec("build.program_mfu"), record)
    assert value == pytest.approx(100 * 44.6 / (19.5 * 197))
    assert 1.1 < value < 1.2
    # the least a chunk can take is its operations at the peak: 100 %, and
    # anything the chip can really do reads below
    record["snap_end"] = {STAGE: histogram(program=(19.0 + 2 * flops / peak, 3))}
    assert readers.read(spec("build.program_mfu"), record) == pytest.approx(100.0)
    record["chips"] = 4  # four chips' peak under the same seconds
    assert readers.read(spec("build.program_mfu"), record) == pytest.approx(25.0)
    record["snap_end"] = {STAGE: histogram(load=(1.0, 3))}
    assert readers.read(spec("build.program_mfu"), record) is None
    record["snap_end"] = record["snap_start"]  # no chunk inside the window
    assert readers.read(spec("build.program_mfu"), record) is None


def test_window_delta_of_a_counter():
    counter = lambda **v: {"series": {json.dumps([k]): x for k, x in v.items()}}  # noqa: E731
    record = {
        "snap_start": {COMPILE: counter(backend=80.0, trace=30.0, lower=26.0)},
        "snap_end": {COMPILE: counter(backend=80.5, trace=30.0, lower=26.25)},
    }
    assert readers.read(spec("compile.window_s"), record) == pytest.approx(0.75)
    # a label first seen inside the window counts from zero
    record["snap_start"] = {COMPILE: counter(backend=80.0)}
    assert readers.read(spec("compile.window_s"), record) == pytest.approx(
        0.5 + 30.0 + 26.25)
    assert readers.read(spec("compile.window_s"),
                        {"snap_start": {}, "snap_end": {}}) is None


def hand_made_trace(host):
    """A 4 s window: the previous chunk's last operations, 3.05 s with no
    operation, then the next program from 3.25 s to the window's end."""
    ops = [("%fusion.1", 0.0, 0.2), ("%while.7", 3.25, 4.0)]
    return tr.Trace(
        devices=[tr.DeviceTrace("/device:TPU:0", ops=ops, modules=[])],
        spans=[("bench.window", 0.0, 4.0)], host=list(host))


def test_gap_overlap_with_the_load_and_the_launch_lag():
    trace = hand_made_trace([
        ("gordo.build.handoff", 0.1, 0.4),
        ("gordo.build.load", 0.5, 3.0),       # loader thread
        ("gordo.build.load_wait", 0.45, 3.01),
        ("gordo.build.stage", 3.02, 3.1),
        ("gordo.build.enqueue", 3.1, 3.2),
        ("gordo.build.enqueue", 3.9, 3.95),   # a later call: after the gap
        ("XlaLinearize", 3.03, 3.09),
    ])
    record = {"trace": trace}
    assert tr.longest_gap(trace) == pytest.approx(3.05)
    assert readers.read(spec("device.gap_load_s"), record) == pytest.approx(2.5)
    assert readers.read(spec("device.launch_lag_s"), record) == pytest.approx(0.05)
    # the load began before the session and is not in the trace: its stages
    # that began inside count, as one union with what there is of the load
    stages = hand_made_trace([
        ("gordo.build.load.fetch", 0.3, 0.4), ("gordo.build.load.fetch", 0.4, 2.0),
        ("gordo.build.load.resample", 2.0, 2.6), ("gordo.build.load.assemble", 2.6, 2.9),
        ("gordo.build.load.finalize", 2.7, 2.8),  # nested in assemble
        ("gordo.build.load_wait", 0.1, 3.0),      # another span, not a stage
        ("gordo.build.enqueue", 3.1, 3.2)])
    assert readers.read(spec("device.gap_load_s"), {"trace": stages}) == pytest.approx(2.6)
    # the load began before the gap: only its part inside counts
    early = hand_made_trace([("gordo.build.load", -1.0, 1.2),
                             ("gordo.build.enqueue", 3.1, 3.3)])
    assert readers.read(spec("device.gap_load_s"), {"trace": early}) == pytest.approx(1.0)
    # the device started before the call returned: a negative lag
    assert readers.read(spec("device.launch_lag_s"), {"trace": early}) == pytest.approx(-0.05)


def test_trace_readers_on_a_program_without_spans_and_without_a_trace():
    parent = hand_made_trace([("XlaLinearize", 3.03, 3.09)])
    for name in FROM_THE_TRACE:
        assert readers.read(spec(name), {"trace": parent}) is None
        assert readers.read(spec(name), {}) is None
    # spans, but the load was not open in the gap and no enqueue began
    # before its end: the next program was queued already
    queued = hand_made_trace([("gordo.build.fetch", 0.0, 0.1),
                              ("gordo.build.enqueue", 3.9, 3.95)])
    assert readers.read(spec("device.gap_load_s"), {"trace": queued}) == 0.0
    assert readers.read(spec("device.launch_lag_s"), {"trace": queued}) == 0.0


# ---------------------------------------------------------------------------
# a traced run on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A manifest of its own with a tiny cell that lists the real
    ``BENCHMARK.json``'s new per-layer entries, their spec files copied."""
    root = tmp_path_factory.mktemp("timeline-checkout")
    base = root / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (base / sub).mkdir(parents=True)
    real = Manifest(ROOT)
    config = real.config("lstm-hourglass-plant")
    config["model"].update(epochs=2, batch_size=32)
    config["dataset"].update(
        n_tags=6, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    config["layer_units"] = [5, 4, 3, 3, 4, 5]
    config["deployment"].update(max_bucket_size=2, project_machines=8)
    config["check"] = {"machines": 1, "fold_machines": 1, "limits": {
        "loss_first_gap": 1e-5, "loss_last_gap": 1e-5,
        "update_norm_gap": 1e-3, "threshold_gap": 1e-4, "nonfinite": 0}}
    (base / "configs" / "tiny.json").write_text(json.dumps(config))
    (base / "traffic" / "build-two.json").write_text(json.dumps({
        "kind": "fleet_build", "trace_seconds": 0.2}))
    cell = "tiny.build-two"
    e2e = [m for m in real.doc["end_to_end"]]
    per_layer = [{**m, "workloads": [cell]}
                 for m in real.doc["per_layer"] if m["name"] in NEW]
    for metric in e2e + per_layer:
        shutil.copy(
            os.path.join(ROOT, "benchmark", "metrics", metric["name"] + ".json"),
            base / "metrics" / (metric["name"] + ".json"))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "-m", "benchmark.run"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": config["source"],
                     "file": "extra/configs/tiny.json",
                     "reduced": ["dataset"], "why": "CPU test size"}],
        "workloads": [{"name": cell, "config": "tiny", "traffic": "build-two",
                       "chips": 1, "why": "CPU test"}],
        "end_to_end": e2e, "per_layer": per_layer,
    }))
    return str(root), cell


def test_cpu_traced_run_reports_the_counter_metrics_and_not_the_trace_ones(
        checkout, monkeypatch):
    root, cell = checkout
    # the CPU is not in the table of peaks, and must not be: the share is
    # of a made-up peak here, so only its sign is looked at
    monkeypatch.setattr(device, "peaks", lambda kind: {"flops_per_s": 1e12})
    out = io.StringIO()
    with redirect_stdout(out):
        code = bench_run.main(
            ["--workload", cell, "--seed", "3000000023", "--seconds", "2",
             "--trace", "1"], require_chip=False, root=root)
    text = out.getvalue()
    assert code == 0, text
    line = json.loads(text.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert set(metrics) == NEW - FROM_THE_TRACE
    for name in ("build.device_program_s_per_model", "build.device_gap_s_per_model",
                 "build.stage_s_per_model", "build.program_mfu"):
        assert metrics[name]["value"] > 0, name
    # nothing compiles inside a window, and at this size a watcher thread
    # may stamp a program's end after its microseconds of fetch are over
    for name in ("compile.window_s", "build.fetch_exposed_s_per_model"):
        assert metrics[name]["value"] >= 0, name
    for name in FROM_THE_TRACE:
        assert f"metric {name}: nothing to read, left out" in text
