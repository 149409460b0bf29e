"""The ``trinity.*`` per-layer metrics of ``trinity-mini.build-longcontext``.
``BENCHMARK.json`` cannot list them yet (an accepted test holds the end of
``per_layer`` to PR 39's ten, and the driver takes an entry anywhere else as
a change), so the nineteen entries wait in
``benchmark/pending/trinity.per_layer.json`` and the cell reports four
accepted metrics whose readers are the ones it needs: the file holds AT LEAST
those its issue names (a later PR may add one), each on that cell alone and
on a reader that is there; the scope readers read the
two new scopes from a hand-made trace (the message classes of the installed
``xplane.proto``, one device plane of two whole programs), and nothing, with
no error, from a program that lacks them."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, readers, window_work  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.readers import trace_scope_seconds  # noqa: E402

MANIFEST = Manifest(ROOT)
CELL = "trinity-mini.build-longcontext"
with open(os.path.join(ROOT, "benchmark", "pending", "trinity.per_layer.json")) as _fh:
    PENDING = json.load(_fh)["per_layer"]
ACCEPTED = {"build.load_s_per_model", "build.write_s_per_model", "build.mfu",
            "compile.backend_s"}
STAGE = "gordo_build_pipeline_stage_seconds"
STAGES = {
    "trinity.program_s_per_model": ["program"], "trinity.device_gap_s_per_model": ["device_gap"],
    "trinity.load_s_per_model": ["load"], "trinity.fetch_exposed_s_per_model": ["fetch_exposed"],
    "trinity.write_s_per_model": ["write"], "trinity.write_fsync_s_per_model": ["write.fsync"],
    "trinity.stage_s_per_model": ["stage", "enqueue"],
}
SCOPES = {"trinity.swa_s_per_step": "backbone.swa", "trinity.gqa_s_per_step": "backbone.gqa"}
ROOFLINES = {"trinity.swa_attn_roofline": "backbone.swa.attn",
             "trinity.gqa_attn_roofline": "backbone.gqa.attn"}
NAMED = {"trinity.moe_with_kernels_s_per_step", "trinity.ragged_dot_roofline",
         "trinity.optimizer_s_per_step", "trinity.unnamed_share"}
OTHERS = {"trinity.gap_load_s", "trinity.program_mfu", "trinity.compile_backend_s",
          "trinity.expert_load_max_over_mean"}
NAMES = sorted({*STAGES, *SCOPES, *ROOFLINES, *NAMED, *OTHERS})


def spec(name):
    return MANIFEST.metric_spec(name)


def histogram(**by_label):
    return {"series": {json.dumps([label.replace("_dot_", ".")]): {"sum": s, "count": c}
                       for label, (s, c) in by_label.items()}}


@pytest.fixture(scope="module")
def config():
    return MANIFEST.config("trinity-mini-plant")


def test_the_pending_entries_are_at_least_the_metrics_the_issue_names():
    assert len(NAMES) == 19
    listed = {m["name"] for m in PENDING}
    assert listed >= set(NAMES) and len(listed) == len(PENDING)
    assert {n for n in listed if not n.startswith("trinity.")} == set()
    for metric in PENDING:
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert {m["name"] for m in MANIFEST.metrics_of(CELL, "end_to_end")} == {
        "build.models_per_h_per_chip", "setup_s"}
    # the share of the whole step's peak and every kernel's share are among them
    assert {n for n in listed if "mfu" in n or "roofline" in n} >= {
        "trinity.program_mfu", "trinity.ragged_dot_roofline", *ROOFLINES}
    # the two that PERF.md queues for retirement are not carried over
    assert not {n for n in listed if n.endswith((".moe_s_per_step", ".moe_experts_roofline"))}


def test_the_cell_reports_four_accepted_metrics_on_the_readers_of_its_own():
    """Appended to the lists of four accepted metrics, each the spec of a
    ``trinity.*`` one but for its words; the older cells keep their places."""
    mine = {m["name"]: m for m in MANIFEST.metrics_of(CELL, "per_layer")}
    assert set(mine) >= ACCEPTED
    for name in ACCEPTED:
        assert mine[name]["workloads"][:2] == [
            "lstm-hourglass.build-plant", "lstm-symmetric.build-plant"]
    bare = lambda name: {k: v for k, v in spec(name).items() if k != "what"}  # noqa: E731
    assert bare("build.load_s_per_model") == bare("trinity.load_s_per_model")
    assert bare("build.write_s_per_model") == bare("trinity.write_s_per_model")
    assert bare("compile.backend_s") == bare("trinity.compile_backend_s")
    assert mine["compile.backend_s"]["moves"] == "setup_s"
    assert {m["name"] for m in MANIFEST.metrics_of(CELL, "end_to_end")} == {
        "build.models_per_h_per_chip", "setup_s"}
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-plant", "build-longcontext", 1)
    traffic = MANIFEST.traffic("build-longcontext")
    assert traffic["kind"] == "backbone_build"
    assert traffic["completion"] == {"series": "gordo_build_pipeline_chunks_total",
                                     "labels": ["pipelined"]}


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_on_its_cell_alone_and_on_the_reader_of_its_accepted_twin(name):
    (metric,) = [m for m in PENDING if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == ("setup_s" if name == "trinity.compile_backend_s"
                               else "build.models_per_h_per_chip")
    body = spec(name)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", body["reader"] + ".py"))
    assert len(body["what"]) > 20
    reading = name.split(".", 1)[1]
    twins = [prefix + reading for prefix in ("lfm.", "glm-flash.")] + [
        "lfm.gqa" + reading[3:] if reading.startswith("swa") else ""]
    found = [m for m in MANIFEST.doc["per_layer"] if m["name"] in twins]
    assert found, name
    for twin in found:
        assert twin["layer"] == metric["layer"] and twin["unit"] == metric["unit"]
        assert twin["source"] == metric["source"] and twin["better"] == metric["better"]
        theirs = spec(twin["name"])
        assert {k: v for k, v in theirs.items() if k not in ("what", "scope")} == {
            k: v for k, v in body.items() if k not in ("what", "scope")}


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_seconds_of_the_window_per_machine(name):
    """Eleven machines observed by the window's end, one of them in set-up."""
    labels = [label.replace(".", "_dot_") for label in STAGES[name]]
    start = histogram(**{label: (2.0, 1) for label in labels})
    end = histogram(**{label: (2.0 + 10 * 1.5, 11) for label in labels})
    record = {"chunk_machines": 1, "snap_start": {STAGE: start}, "snap_end": {STAGE: end}}
    assert readers.read(spec(name), record) == pytest.approx(1.5 * len(labels))
    bare = {**record, "snap_start": {STAGE: histogram(other=(1.0, 1))},
            "snap_end": {STAGE: histogram(other=(3.0, 3))}}
    assert readers.read(spec(name), bare) is None


def test_the_whole_programs_share_of_the_peak(config):
    work = window_work.chunk_work(config, 1)
    peak = device.peaks("TPU v5 lite")["flops_per_s"]
    record = {"device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work,
              "snap_start": {STAGE: histogram(program=(4.0, 1))},
              "snap_end": {STAGE: histogram(program=(4.0 + 10 * 4.0, 11))}}
    value = readers.read(spec("trinity.program_mfu"), record)
    assert value == pytest.approx(100 * work["flops_per_model"] / (4.0 * peak))
    assert 10 < value < 20                 # about a hundred TFLOP in 4 s of a 197 TFLOP/s chip
    record["snap_end"] = {STAGE: histogram(program=(4.0 + 10 * work["flops"] / peak, 11))}
    assert readers.read(spec("trinity.program_mfu"), record) == pytest.approx(100.0)


# -- the two new scopes, on a hand-made trace -----------------------------------

PS = 10 ** 12
OPS = [  # (name, tf_op, start s, seconds)
    ("%fusion.1", "jit(fleet_exact)/while/body/backbone.swa/dot_general", 0.10, 0.020),
    ("%fusion.2", "jit(fleet_exact)/while/body/backbone.swa/backbone.swa.attn/reduce", 0.13, 0.030),
    ("%while.3", "jit(fleet_exact)/while/body/backbone.swa/backbone.swa.attn/while", 0.13, 0.050),
    ("%fusion.4", "jit(fleet_exact)/transpose(jvp(backbone.gqa))/backbone.gqa.attn/dot", 0.20, 0.040),
    ("%fusion.5", "jit(fleet_exact)/checkpoint/backbone.gqa/mul", 0.25, 0.010),
    ("%fusion.6", "jit(fleet_exact)/backbone.moe.experts/dot_general", 0.30, 0.015),
    ("%fusion.1", "jit(fleet_exact)/while/body/backbone.swa/dot_general", 1.10, 0.020),
    ("%fusion.4", "jit(fleet_exact)/transpose(jvp(backbone.gqa))/backbone.gqa.attn/dot", 1.20, 0.040),
    ("%fusion.2", "jit(fleet_exact)/while/body/backbone.swa/backbone.swa.attn/reduce", 2.05, 0.030),
]
MODULES = [(0.0, 1.0), (1.0, 1.0), (2.0, 0.3)]      # the third is cut by the window's end


def write_trace(path, ops=OPS, modules=MODULES):
    messages = trace_scope_seconds.xplane_messages()
    if messages is None:
        pytest.skip("no xplane_pb2 in this installation")
    space = messages.XSpace()
    plane = space.planes.add()
    plane.name = "/device:TPU:0"
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    ids = {}
    for name, scope, _, _ in ops:
        if (name, scope) not in ids:
            ids[name, scope] = len(ids) + 1
            meta = plane.event_metadata[ids[name, scope]]
            meta.id, meta.name = ids[name, scope], name
            stat = meta.stats.add()
            stat.metadata_id, stat.str_value = 1, scope
    plane.event_metadata[99].id = 99
    plane.event_metadata[99].name = "jit_fleet_exact"
    line = plane.lines.add()
    line.name = "XLA Modules"
    for start, seconds in modules:
        ev = line.events.add()
        ev.metadata_id, ev.offset_ps, ev.duration_ps = 99, int(start * PS), int(seconds * PS)
    line = plane.lines.add()
    line.name = "XLA Ops"
    for name, scope, start, seconds in ops:
        ev = line.events.add()
        ev.metadata_id = ids[name, scope]
        ev.offset_ps, ev.duration_ps = int(start * PS), int(seconds * PS)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def record_of(tmp_path, work, **kwargs):
    write_trace(str(tmp_path / "trace" / "host.xplane.pb"), **kwargs)
    return {"trace_dir": str(tmp_path / "trace"), "snap_start": {}, "snap_end": {},
            "device_kind": "TPU v5 lite", "chips": 1, "work_per_chunk": work}


@pytest.mark.parametrize("name,seconds", [
    # two whole programs of 7 steps; a loop's own event is left out, the cut program too
    ("trinity.swa_s_per_step", (0.020 + 0.030 + 0.020) / 14),
    ("trinity.gqa_s_per_step", (0.040 + 0.010 + 0.040) / 14),
])
def test_scope_seconds_per_step(name, seconds, tmp_path):
    record = record_of(tmp_path, {"steps_per_model": 7})
    assert spec(name)["scope"] == SCOPES[name]
    assert readers.read(spec(name), record) == pytest.approx(seconds)


@pytest.mark.parametrize("name,seconds", [
    ("trinity.swa_attn_roofline", 0.030 / 14), ("trinity.gqa_attn_roofline", 0.080 / 14)])
def test_roofline_shares_read_the_work_count_under_their_scope(name, seconds, tmp_path, config):
    scope = ROOFLINES[name]
    assert spec(name)["scope"] == scope
    per_step = window_work.chunk_work(config, 1)["per_step"]
    peaks = device.peaks("TPU v5 lite")
    work = {"steps_per_model": 7, "per_step": {
        scope: {"flops": peaks["flops_per_s"] * 1e-4, "bytes": 1.0}}}
    share = readers.read(spec(name), record_of(tmp_path, work))
    assert share == pytest.approx(100.0 * 1e-4 / seconds)
    assert 0 < share < 100
    # at the published widths the least a step's cores need at the peak
    real = {"steps_per_model": 7, "per_step": per_step}
    least = per_step[scope]["flops"] / peaks["flops_per_s"]
    assert readers.read(spec(name), record_of(tmp_path / "real", real)) == pytest.approx(
        100.0 * least / seconds)
    # a program without the scope (the parent's): nothing to read, no error
    other = [op for op in OPS if "moe" in op[1]]
    assert readers.read(spec(name), record_of(tmp_path / "bare", real, ops=other)) is None
    assert readers.read(spec(name), {**record_of(tmp_path / "none", real), "trace_dir": None}) is None


def test_the_readers_of_named_operations_are_the_accepted_ones():
    for reading in ("moe_with_kernels_s_per_step", "ragged_dot_roofline",
                    "optimizer_s_per_step", "unnamed_share"):
        ours, theirs = spec("trinity." + reading), spec("glm-flash." + reading)
        assert {k: v for k, v in ours.items() if k != "what"} == {
            k: v for k, v in theirs.items() if k != "what"}


def test_the_split_script_reads_every_pending_metric_of_the_cell(tmp_path):
    """``scripts/sequence_trace_split.py`` reads what ``benchmark.run``
    cannot list: the nineteen by name, a value where the record holds what a
    reader reads, ``None`` and no error where it does not."""
    import importlib.util

    at = os.path.join(ROOT, "scripts", "sequence_trace_split.py")
    module_spec = importlib.util.spec_from_file_location("sequence_trace_split", at)
    script = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(script)
    record = record_of(tmp_path, {"steps_per_model": 7})
    found = script.pending(record, CELL)
    assert set(found) >= set(NAMES)
    assert found["trinity.swa_s_per_step"] == pytest.approx((0.020 + 0.030 + 0.020) / 14)
    assert found["trinity.load_s_per_model"] is None
    assert script.pending(record, "lfm2-moe.build-fortnight") == {}
