"""The readers PR 39 added (``trace_named_seconds``, ``trace_kernel_roofline``,
``trace_unnamed_share``) and the ten per-layer metrics on them: each metric
is listed on its one cell and names a reader that is there, and each reads
what it says from a hand-made trace, a hand-made record and the trace
recorded on the chip WITH the program's new names
(``benchmark/testdata/tiny_named.xplane.pb``, ``scripts/record_named_trace.py``:
two executions of the tiny ``lfm2_moe`` backbone's whole fleet program, three
folds' fits and forecasts and the final fit; the device plane's two lines and
the five fields the readers read) beside the one recorded before them
(``tiny_hybrid.xplane.pb``, PR 37: a bare fit's step, the parent's names)."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device, readers  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.readers import (  # noqa: E402
    trace_kernel_roofline, trace_named_seconds, trace_scope_seconds)

MANIFEST = Manifest(ROOT)
TESTDATA = os.path.join(ROOT, "benchmark", "testdata")
CELLS = {"kimi-linear": "kimi-linear.build-series", "glm-flash": "glm-flash.build-horizons"}
READINGS = {
    "moe_with_kernels_s_per_step": ("trace_named_seconds", "backbone", "s"),
    "ragged_dot_roofline": ("trace_kernel_roofline", "kernels", "%"),
    "optimizer_s_per_step": ("trace_named_seconds", "fleet program", "s"),
    "unnamed_share": ("trace_unnamed_share", "fleet program", "%"),
    "write_fsync_s_per_model": ("histogram_sum_delta", "pack write", "s"),
}
NAMES = sorted(f"{prefix}.{reading}" for prefix in CELLS for reading in READINGS)
STEPS = 3
HELD, SELECTED, BLOCKS = (
    "gordo_moe_held_pairs_total", "gordo_moe_selected_pairs_total", "gordo_moe_row_blocks_total")


def spec(name):
    return MANIFEST.metric_spec(name)


def record_of(tmp_path, trace, **more):
    """A run record whose traced window wrote ``trace`` (a file of
    ``benchmark/testdata`` or bytes)."""
    os.makedirs(tmp_path, exist_ok=True)
    dest = os.path.join(str(tmp_path), "t.xplane.pb")
    if isinstance(trace, bytes):
        with open(dest, "wb") as fh:
            fh.write(trace)
    else:
        shutil.copy(os.path.join(TESTDATA, trace), dest)
    return {"trace_dir": str(tmp_path), "device_kind": "TPU v5 lite", "chips": 1,
            "work_per_chunk": {"steps_per_model": STEPS},
            "snap_start": {}, "snap_end": {}, **more}


def hand_made(modules, ops):
    """An ``.xplane.pb`` with one device plane: ``modules`` ``[(start_s,
    end_s)]`` and ``ops`` ``[(instruction text, tf_op, category, flops,
    bytes, start_s, end_s)]``."""
    messages = trace_scope_seconds.xplane_messages()
    space = messages.XSpace()
    plane = space.planes.add()
    plane.name = "/device:TPU:0"
    fields = {name: i + 1 for i, name in enumerate(
        ("tf_op", "hlo_category", "flops", "bytes_accessed"))}
    for name, i in fields.items():
        plane.stat_metadata[i].id, plane.stat_metadata[i].name = i, name
    ps = lambda s: int(round(s * 1e12))  # noqa: E731
    line = plane.lines.add()
    line.name = "XLA Modules"
    plane.event_metadata[1].id, plane.event_metadata[1].name = 1, "jit_fleet_exact(1)"
    for start, end in modules:
        ev = line.events.add()
        ev.metadata_id, ev.offset_ps, ev.duration_ps = 1, ps(start), ps(end - start)
    line = plane.lines.add()
    line.name = "XLA Ops"
    for n, (text, tf_op, category, flops, moved, start, end) in enumerate(ops):
        meta = plane.event_metadata[n + 2]
        meta.id, meta.name = n + 2, text
        for name, value in (("tf_op", tf_op), ("hlo_category", category)):
            stat = meta.stats.add()
            stat.metadata_id, stat.str_value = fields[name], value
        for name, value in (("flops", flops), ("bytes_accessed", moved)):
            stat = meta.stats.add()
            stat.metadata_id, stat.uint64_value = fields[name], value
        ev = line.events.add()
        ev.metadata_id, ev.offset_ps, ev.duration_ps = n + 2, ps(start), ps(end - start)
    return space.SerializeToString()


PATH = "jit(fleet_exact)/while/body/closed_call/"
OPS = [
    # the first whole program, 0..1 s
    ("%fusion.1 = f32[8]{0} fusion(...)", PATH + "jvp(SequenceBackbone)/backbone.moe.experts/mul",
     "loop fusion", 10, 100, 0.00, 0.10),
    ("%ragged-dot-none.4 = bf16[8,4] custom-call(...)", "ragged-dot-none:", "custom-call",
     4_000_000, 1_000, 0.10, 0.30),
    ("%ragged-dot-metadata.2 = s32[8] custom-call(...)", "ragged-dot-metadata:", "custom-call",
     0, 0, 0.30, 0.31),
    ("%multiply_add_fusion.7 = f32[8]{0} fusion(...)", PATH + "while/body/fit.optimizer/add",
     "loop fusion", 8, 64, 0.31, 0.41),
    ("%fusion.9 = f32[8]{0} fusion(...)",
     PATH + "cond/branch_1_fun/fit.forecast/SequenceBackbone/backbone.conv/dot_general",
     "convolution fusion", 8, 64, 0.41, 0.51),
    ("%fusion.11 = f32[8]{0} fusion(...)",
     PATH + "cond/branch_1_fun/fit.forecast/SequenceBackbone/dot_general",
     "convolution fusion", 8, 64, 0.51, 0.56),
    ("%copy-done.3 = f32[8]{0} copy-done(...)", "", "copy-done", 0, 0, 0.56, 0.60),
    # control flow's own events span the operations above and are left out
    ("%while.5 = (s32[]) while(...)", PATH + "while", "while", 0, 0, 0.00, 0.60),
    ("%cond.36.clone.4 = (f32[1]) conditional(...)", PATH + "cond", "conditional", 0, 0, 0.41, 0.56),
    # a program cut by the window's end: 1.2..1.5 s, not whole
    ("%fusion.21 = f32[8]{0} fusion(...)", PATH + "backbone.moe.route/sort", "sort", 0, 0, 1.2, 1.4),
]


@pytest.fixture()
def made(tmp_path):
    return record_of(tmp_path, hand_made([(0.0, 1.0), (1.2, 1.5)], OPS))


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_listed_on_its_cell_alone_and_names_a_reader_that_is_there(name):
    assert len(NAMES) == 10
    prefix, reading = name.split(".", 1)
    reader, layer, unit = READINGS[reading]
    (metric,) = [m for m in MANIFEST.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELLS[prefix]]
    assert metric["moves"] == "build.models_per_h_per_chip"
    assert (metric["layer"], metric["unit"]) == (layer, unit)
    assert metric["source"] == ("program_span" if reader == "histogram_sum_delta"
                                else "device_trace")
    body = spec(name)
    assert body["reader"] == reader and len(body["what"]) > 20
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", reader + ".py"))
    # the same reading in the other cell: the same reader under the same spec
    other = spec(("glm-flash." if prefix == "kimi-linear" else "kimi-linear.") + reading)
    assert {k: v for k, v in other.items() if k != "what"} == {
        k: v for k, v in body.items() if k != "what"}


def test_the_entries_are_appended_and_the_cells_keep_their_older_metrics():
    names = [m["name"] for m in MANIFEST.doc["per_layer"]]
    assert names[-10:] == [f"{prefix}.{reading}" for prefix in CELLS for reading in READINGS]
    for prefix, cell in CELLS.items():
        mine = {m["name"] for m in MANIFEST.metrics_of(cell, "per_layer")}
        older = {n for n in mine if not n.startswith(prefix + ".")}
        assert len(mine - older) == 5 and len(older) in (18, 15)


def test_seconds_by_name_and_by_kernel_of_the_whole_programs(made):
    per_step = lambda **s: readers.read({"reader": "trace_named_seconds", **s}, made)  # noqa: E731
    # one whole program: the second module is cut by the window's end
    assert trace_named_seconds.table_of(made)["programs"] == 1
    assert per_step(names=["backbone.moe"]) == pytest.approx(0.10 / STEPS)
    assert per_step(kernels=["ragged-dot"]) == pytest.approx(0.21 / STEPS)
    assert per_step(names=["backbone.moe"], kernels=["ragged-dot"]) == pytest.approx(0.31 / STEPS)
    assert per_step(names=["fit.optimizer"]) == pytest.approx(0.10 / STEPS)
    assert per_step(names=["backbone.kda"]) is None
    assert readers.read(spec("kimi-linear.moe_with_kernels_s_per_step"), made) == pytest.approx(
        0.31 / STEPS)
    assert readers.read(spec("glm-flash.optimizer_s_per_step"), made) == pytest.approx(0.10 / STEPS)


def test_a_stalled_program_drops_none_of_the_sound_ones(tmp_path):
    """Four executions, one of them 15 % long beside a slow fetch, between a
    program cut by each end of the window and two small programs: the four
    are whole (by the longest module alone the three sound ones were not)."""
    modules = [(0.0, 1.4), (2.0, 5.0), (5.5, 8.5), (9.0, 12.45), (13.0, 16.0),
               (16.5, 17.0), (17.2, 17.21), (17.3, 17.31)]
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", PATH + "backbone.moe.experts/mul",
            "loop fusion", 0, 0, start + 0.1, start + 0.2) for start, _ in modules[:6]]
    record = record_of(tmp_path, hand_made(modules, ops))
    found = trace_named_seconds.table_of(record)
    assert found["programs"] == 4 and found["events"] == 4
    assert found["program_s"] == pytest.approx((3 * 3.0 + 3.45) / 4)
    assert len(trace_scope_seconds.whole_programs(
        [(int(a * 1e12), int(b * 1e12)) for a, b in modules], None)) == 1


def test_the_share_nothing_names_leaves_control_flow_out_and_the_pass_mark_unnamed(made):
    # 0.60 s of operations: the copy-done (0.04) and the forecast's operation
    # that has the pass's mark alone (0.05) are unnamed; the while and the
    # conditional, which span the rest, are no operations
    assert readers.read(spec("kimi-linear.unnamed_share"), made) == pytest.approx(
        100 * 0.09 / 0.60)
    assert readers.read(spec("glm-flash.unnamed_share"), made) == pytest.approx(15.0)
    everything = {"reader": "trace_unnamed_share", "prefixes": ["backbone.", "fit."]}
    assert readers.read(everything, made) == pytest.approx(100 * (0.04 + 0.21) / 0.60)


def test_the_kernels_share_of_the_roofline_by_the_events_counts_and_the_held_rows(made):
    peaks = device.peaks("TPU v5 lite")
    alone = readers.read({"reader": "trace_kernel_roofline", "kernels": ["ragged-dot"]}, made)
    assert alone == pytest.approx(100 * (4_000_000 / peaks["flops_per_s"]) / 0.21)
    counter = lambda **v: {"series": {json.dumps(list(k.split("|")) if k else []): x  # noqa: E731
                                      for k, x in v.items()}}
    # a quarter of the selected pairs is held; the program handed the kernel
    # half of them (2 of 4 blocks ran): half of the counted rows hold a pair
    made["snap_start"] = {HELD: counter(**{"": 100.0}), SELECTED: counter(**{"": 400.0}),
                          BLOCKS: counter(run=2.0, full=4.0)}
    made["snap_end"] = {HELD: counter(**{"": 300.0}), SELECTED: counter(**{"": 1200.0}),
                        BLOCKS: counter(run=6.0, full=12.0)}
    assert trace_kernel_roofline.rows_held_share(
        spec("glm-flash.ragged_dot_roofline")["rows"], made) == pytest.approx(0.5)
    assert readers.read(spec("glm-flash.ragged_dot_roofline"), made) == pytest.approx(alone / 2)
    # the worst-case buffer (no row blocks counted): all selected pairs are handed over
    del made["snap_start"][BLOCKS], made["snap_end"][BLOCKS]
    assert readers.read(spec("kimi-linear.ragged_dot_roofline"), made) == pytest.approx(alone / 4)
    # counters that did not move: nothing to scale by
    made["snap_end"] = dict(made["snap_start"])
    assert readers.read(spec("kimi-linear.ragged_dot_roofline"), made) is None


def test_the_fsync_seconds_of_the_window_per_machine():
    stage = "gordo_build_pipeline_stage_seconds"
    hist = lambda **by: {"series": {json.dumps([k.replace("_", ".")]): {"sum": s, "count": c}  # noqa: E731
                                    for k, (s, c) in by.items()}}
    record = {"chunk_machines": 1, "snap_start": {stage: hist(write_fsync=(0.0, 1), write=(4.0, 1))},
              "snap_end": {stage: hist(write_fsync=(6 * 0.5, 7), write=(30.0, 7))}}
    for name in ("kimi-linear.write_fsync_s_per_model", "glm-flash.write_fsync_s_per_model"):
        assert readers.read(spec(name), record) == pytest.approx(0.5)
    # a parent's snapshot has no such stage
    bare = {**record, "snap_end": {stage: hist(write=(30.0, 7))}}
    assert readers.read(spec("kimi-linear.write_fsync_s_per_model"), bare) is None


# -- the traces recorded on the chip ----------------------------------------

@pytest.fixture()
def named(tmp_path):
    return record_of(tmp_path / "named", "tiny_named.xplane.pb")


@pytest.fixture()
def parent(tmp_path):
    return record_of(tmp_path / "parent", "tiny_hybrid.xplane.pb")


def test_the_recorded_program_is_named_for_its_registry_name_and_is_small():
    path = os.path.join(TESTDATA, "tiny_named.xplane.pb")
    assert os.path.getsize(path) < 2_000_000
    messages = trace_scope_seconds.xplane_messages()
    space = messages.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    (plane,) = space.planes
    modules = [plane.event_metadata[ev.metadata_id].name
               for line in plane.lines if line.name == "XLA Modules" for ev in line.events]
    assert len(modules) == 2 and all(m.startswith("jit_fleet_exact") for m in modules)


def test_the_kernels_have_seconds_and_the_expert_layers_reading_holds_them(named):
    read = lambda **s: readers.read({"reader": "trace_named_seconds", **s}, named)  # noqa: E731
    assert trace_named_seconds.table_of(named)["programs"] == 2
    kernels, scope = read(kernels=["ragged-dot"]), read(names=["backbone.moe"])
    both = read(names=["backbone.moe"], kernels=["ragged-dot"])
    assert kernels > 0 and scope > 0
    assert both == pytest.approx(scope + kernels) and both > scope
    # the accepted reader reads the scope alone, and the same seconds
    old = trace_scope_seconds.per_step({"scope": "backbone.moe"}, dict(named))
    assert old == pytest.approx(scope, rel=1e-9)


def test_the_recorded_kernels_share_of_their_roofline_is_a_share(named):
    value = readers.read({"reader": "trace_kernel_roofline", "kernels": ["ragged-dot"]}, named)
    assert 0 < value <= 100


@pytest.mark.parametrize("name", ["fit.optimizer", "fit.loss", "fit.draw", "fit.forecast",
                                  "backbone.embed", "backbone.head", "backbone.norm",
                                  "backbone.stack", "backbone.residual"])
def test_the_new_names_have_seconds_on_the_chip(named, name):
    assert readers.read({"reader": "trace_named_seconds", "names": [name]}, named) > 0


def test_less_of_the_named_program_is_unnamed_than_of_the_parents(named, parent):
    body = spec("kimi-linear.unnamed_share")
    new, old = readers.read(body, named), readers.read(body, parent)
    assert 0 < new < old < 100
    # the parent has none of the new names: nothing to read there
    assert readers.read(spec("kimi-linear.optimizer_s_per_step"), parent) is None
    assert readers.read(spec("kimi-linear.moe_with_kernels_s_per_step"), parent) > 0


def test_one_parse_serves_every_metric_of_a_run(named, monkeypatch):
    calls = []
    real = trace_named_seconds.table
    monkeypatch.setattr(trace_named_seconds, "table",
                        lambda path: (calls.append(path), real(path))[1])
    values = [readers.read(spec(name), named) for name in NAMES if "fsync" not in name]
    assert len(calls) == 1
    # the roofline's counters are not in this record; the other six are numbers
    assert sum(v is not None for v in values) == 6


@pytest.mark.parametrize("name", [n for n in NAMES if "fsync" not in n])
def test_nothing_to_read_without_a_trace(name, tmp_path):
    bare = {"trace_dir": None, "work_per_chunk": {"steps_per_model": STEPS},
            "device_kind": "TPU v5 lite", "snap_start": {}, "snap_end": {}}
    assert readers.read(spec(name), bare) is None
    # a traced window that wrote nothing the reader can parse
    assert readers.read(spec(name), {**bare, "trace_dir": str(tmp_path)}) is None
