"""The two readers that open a run's ``.xplane.pb`` themselves, on a trace
recorded on the chip (PR 29): two executions of a three-step fit and a
forecast of the backbone's tiny preset (hidden 64, two layers: KDA + dense,
MLA + experts), ``benchmark/testdata/tiny_backbone.xplane.pb``."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.readers import trace_scope_roofline, trace_scope_seconds  # noqa: E402

TRACE = os.path.join(ROOT, "benchmark", "testdata", "tiny_backbone.xplane.pb")
SCOPES = ["backbone.kda", "backbone.kda.scan", "backbone.mla", "backbone.moe",
          "backbone.moe.experts", "backbone.ffn"]


@pytest.fixture(scope="module")
def found():
    if trace_scope_seconds.xplane_messages() is None:
        pytest.skip("no xplane_pb2 in this installation")
    return trace_scope_seconds.scope_seconds(TRACE, SCOPES)


def test_two_whole_programs_and_every_scope_has_seconds(found):
    totals, programs = found
    assert programs == 2
    assert all(totals[scope] > 0 for scope in SCOPES)
    # a scope holds the scopes nested in it
    assert totals["backbone.kda"] > totals["backbone.kda.scan"]
    assert totals["backbone.moe"] > totals["backbone.moe.experts"]
    # the recorded values, to the microsecond (the reduction is the yardstick)
    assert totals["backbone.kda"] == pytest.approx(703.6e-6, abs=1e-6)
    assert totals["backbone.kda.scan"] == pytest.approx(520.3e-6, abs=1e-6)
    assert totals["backbone.mla"] == pytest.approx(79.7e-6, abs=1e-6)


def test_a_program_cut_by_the_window_is_not_read():
    # both executions took 0.63 ms: against a program said to take 1 ms
    # neither is whole, against 0.6 ms both are
    assert trace_scope_seconds.scope_seconds(TRACE, SCOPES, program_seconds=1e-3) is None
    _, programs = trace_scope_seconds.scope_seconds(TRACE, SCOPES, program_seconds=0.6e-3)
    assert programs == 2
    assert trace_scope_seconds.whole_programs([(0, 10), (20, 24)], None) == [(0, 10)]
    assert trace_scope_seconds.whole_programs([(0, 10 ** 12)], 2.0) == []


def record_of(tmp_path, steps):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(TRACE, trace_dir / "host.xplane.pb")
    return {
        "trace_dir": str(trace_dir), "snap_start": {}, "snap_end": {},
        "device_kind": "TPU v5 lite", "chips": 1,
        "work_per_chunk": {"steps_per_model": steps, "per_step": {
            "backbone.kda.scan": {"flops": 197e12 * 1e-6, "bytes": 1.0}}},
    }


def test_the_readers_divide_by_the_steps_of_the_whole_programs(found, tmp_path):
    totals, _ = found
    record = record_of(tmp_path, steps=3)
    seconds = trace_scope_seconds.read({"scope": "backbone.kda.scan"}, record)
    assert seconds == pytest.approx(totals["backbone.kda.scan"] / 6)
    # one microsecond of work at the peak over the seconds a step took
    share = trace_scope_roofline.read({"scope": "backbone.kda.scan"}, record)
    assert share == pytest.approx(100.0 * 1e-6 / seconds)
    assert 0 < share < 100


def test_nothing_to_read_is_none_not_an_error(found, tmp_path):
    record = record_of(tmp_path, steps=3)
    assert trace_scope_seconds.read({"scope": "no.such.scope"}, record) is None
    assert trace_scope_roofline.read({"scope": "no.such.scope"}, record) is None
    assert trace_scope_seconds.read({"scope": "backbone.kda"}, {"trace_dir": None}) is None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert trace_scope_seconds.read(
        {"scope": "backbone.kda"}, {**record, "trace_dir": str(empty)}) is None
