"""The chunk timeline from inside ``build_project`` (PR 25): one span per
phase with id, parent, start and end under one trace id, a ready stamp for
every fleet program, the device's gaps and their split, the repaired
device-idle series, and nothing of it with telemetry off."""

import json
import pickle
import threading

import pytest

from gordo_tpu import artifacts, telemetry
from gordo_tpu.builder import timeline as timeline_mod
from gordo_tpu.builder.fleet_build import build_project
from gordo_tpu.parallel import anomaly

from tests.test_build_pipeline import _machines, _scrub_timings, _strip_meta

PHASES = ("load", "load_wait", "stage", "enqueue", "program_wait", "fetch",
          "assemble", "handoff", "write")
NEW_LABELS = ("load_wait", "stage", "enqueue", "handoff", "program",
              "device_gap", "fetch_exposed")
OLD_LABELS = ("load", "dispatch", "fetch", "assemble", "device", "write")
N_CHUNKS = 3
WATCHER = "gordo-program-wait"


def stage_counts():
    series = telemetry.REGISTRY.get("gordo_build_pipeline_stage_seconds")
    return {label: series.snapshot_series(label)["count"]
            for label in NEW_LABELS + OLD_LABELS}


def watchers_alive():
    return [t for t in threading.enumerate() if t.name == WATCHER]


def read_spans(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Three chunks of two machines through the pipelined drive, the span
    log on."""
    tmp = tmp_path_factory.mktemp("timeline")
    log = tmp / "spans.jsonl"
    patch = pytest.MonkeyPatch()
    patch.setenv("GORDO_SPAN_LOG", str(log))
    telemetry.set_trace_id(None)
    before = stage_counts()
    idle_before = telemetry.REGISTRY.get("gordo_build_device_idle_seconds").value()
    try:
        result = build_project(
            _machines(2 * N_CHUNKS, prefix="tl"), str(tmp / "out"),
            max_bucket_size=2, artifact_format="v2",
        )
    finally:
        patch.undo()
    assert not result.failed and not result.demoted
    return {
        "result": result, "out": tmp / "out", "spans": read_spans(log),
        "before": before, "after": stage_counts(),
        "idle_counted": telemetry.REGISTRY.get(
            "gordo_build_device_idle_seconds").value() - idle_before,
    }


def test_every_phase_of_every_chunk_is_a_span_with_start_before_end(built):
    by_chunk = {}
    for doc in built["spans"]:
        if doc["span"].startswith("gordo.build.") and "chunk" in doc:
            by_chunk.setdefault(doc["chunk"], {}).setdefault(
                doc["span"].rsplit(".", 1)[1], []).append(doc)
    assert sorted(by_chunk) == list(range(N_CHUNKS))
    for chunk, spans in by_chunk.items():
        assert set(spans) == set(PHASES) | {"device_gap"}, chunk
        for name, docs in spans.items():
            assert len(docs) == 1, (chunk, name)
            if name in ("device_gap", "load_wait"):
                # nothing to wait for: a program queued behind its
                # predecessor, a load that was there already (the log
                # rounds to microseconds)
                assert docs[0]["start"] <= docs[0]["end"], (chunk, name)
            else:
                assert docs[0]["start"] < docs[0]["end"], (chunk, name)
            assert docs[0]["seconds"] == pytest.approx(
                docs[0]["end"] - docs[0]["start"], abs=2e-6)
    # the transfer is counted where it happens, in mesh.place()
    staged = by_chunk[1]["stage"][0]
    assert staged["leaves"] == 3 and staged["bytes"] > 0 and staged["machines"] == 2


def test_one_trace_id_and_one_parent_chain(built):
    spans = [d for d in built["spans"] if d["span"].startswith("gordo.build.")]
    assert len({d["trace"] for d in spans}) == 1
    by_id = {d["id"]: d for d in spans}
    assert len(by_id) == len(spans)
    roots = [d for d in spans if d["parent"] is None]
    assert [d["span"] for d in roots] == ["gordo.build.project"]
    for doc in spans:
        hops = 0
        while doc["parent"] is not None:
            doc, hops = by_id[doc["parent"]], hops + 1
            assert hops < 4
        assert doc is roots[0]
    # threads that were handed a copy of the drive's context keep the chain:
    # the loader's, the watcher's, and the writer's under its hand-off
    parent_of = lambda name: {by_id[d["parent"]]["span"] for d in spans  # noqa: E731
                              if d["span"] == name}
    assert parent_of("gordo.build.load") == {"gordo.build.project"}
    assert parent_of("gordo.build.program_wait") == {"gordo.build.project"}
    assert parent_of("gordo.build.write") == {"gordo.build.handoff"}
    # the build's trace id does not outlive it
    assert telemetry.current_trace_id() is None


def test_program_intervals_are_disjoint_and_close_with_the_gaps(built):
    rows = built["result"].timeline
    assert [row["chunk"] for row in rows] == list(range(N_CHUNKS))
    programs = [p for row in rows for p in row["programs"]]
    assert len(programs) == N_CHUNKS
    for before, after in zip(programs, programs[1:]):
        assert before["ready"] <= after["start"]
    for p in programs:
        assert p["enqueued"] <= p["start"] <= p["ready"]
        assert p["device_gap_s"] >= 0 and p["program_s"] >= 0
        named = sum(v for k, v in p["gap_split"].items())
        assert named == pytest.approx(p["device_gap_s"], abs=1e-6)
    # stamps count from build start, where the first gap begins
    total = sum(p["program_s"] + p["device_gap_s"] for p in programs)
    assert total == pytest.approx(programs[-1]["ready"], abs=1e-5)
    inner = sum(p["program_s"] for p in programs) + sum(
        p["device_gap_s"] for p in programs[1:])
    assert inner == pytest.approx(
        programs[-1]["ready"] - programs[0]["start"], abs=1e-5)
    # the repaired series is the sum of those gaps, on the result and counted
    idle = built["result"].device_idle_seconds
    assert idle == pytest.approx(sum(p["device_gap_s"] for p in programs))
    assert built["idle_counted"] == pytest.approx(idle)
    for row in rows:
        assert set(row["phases"]) == set(PHASES)
        assert row["fetch_exposed_s"] >= 0
        assert row["program_s"] == pytest.approx(
            sum(p["program_s"] for p in row["programs"]))


def test_each_label_value_is_observed_once_per_chunk(built):
    for label in NEW_LABELS + OLD_LABELS:
        assert built["after"][label] - built["before"][label] == N_CHUNKS, label


def test_the_timeline_is_written_beside_the_snapshot(built):
    directory = built["out"] / telemetry.SNAPSHOT_DIR
    doc = json.loads((directory / "timeline-000-of-001.json").read_text())
    assert doc["chunks"] == json.loads(json.dumps(built["result"].timeline))
    assert doc["trace"] == built["spans"][0]["trace"]
    # the snapshot merge passes the timeline over
    assert len(telemetry.load_snapshot_dir(str(directory))) == 1
    assert watchers_alive() == []


def test_telemetry_off_starts_no_watcher_and_writes_the_same_packs(
        built, tmp_path, monkeypatch):
    started = []
    monkeypatch.setattr(
        anomaly, "_watch_program",
        lambda *a, **k: started.append(a) or pytest.fail("a watcher started"))
    telemetry.set_enabled(False)
    try:
        quiet = build_project(
            _machines(2 * N_CHUNKS, prefix="tl"), str(tmp_path / "out"),
            max_bucket_size=2, artifact_format="v2",
        )
    finally:
        telemetry.set_enabled(True)
    assert not quiet.failed and started == []
    assert quiet.timeline == [] and quiet.device_idle_seconds == 0.0
    assert not (tmp_path / "out" / telemetry.SNAPSHOT_DIR).exists()
    on, off = (artifacts.open_store(str(built["out"])),
               artifacts.open_store(str(tmp_path / "out")))
    assert sorted(on.names()) == sorted(off.names())
    for name in on.names():
        a, b = on.load_model(name), off.load_model(name)
        _scrub_timings(a)
        _scrub_timings(b)
        assert pickle.dumps(a) == pickle.dumps(b), name
        assert _strip_meta(on.load_metadata(name)) == _strip_meta(
            off.load_metadata(name)), name


class _FailedLeaf:
    """What a program that failed on the device leaves behind: an output
    whose wait raises."""

    size = 1

    def block_until_ready(self):
        raise RuntimeError("INTERNAL: the program failed on the device")


def test_an_async_failure_leaves_no_watcher_and_demotes_the_chunk(
        tmp_path, monkeypatch):
    real = anomaly.FleetDiffBuilder._group_program
    calls = []

    def failing_first(self, ctx, padded, warm):
        calls.append(1)
        if len(calls) == 1:
            return lambda *args: {"aggregate_threshold": _FailedLeaf()}
        return real(self, ctx, padded, warm)

    monkeypatch.setattr(anomaly.FleetDiffBuilder, "_group_program", failing_first)
    log = tmp_path / "spans.jsonl"
    monkeypatch.setenv("GORDO_SPAN_LOG", str(log))
    machines = _machines(4, prefix="tlf")
    result = build_project(
        machines, str(tmp_path / "out"), max_bucket_size=2,
        artifact_format="v2",
    )
    assert not result.failed
    assert sorted(result.demoted) == [m.name for m in machines[:2]]
    assert all(r.startswith("collect:") for r in result.demoted.values())
    assert sorted(result.single_built) == sorted(result.demoted)
    assert sorted(result.fleet_built) == [m.name for m in machines[2:]]
    for thread in watchers_alive():
        thread.join(5.0)
    assert watchers_alive() == []
    waits = [d for d in read_spans(log) if d["span"] == "gordo.build.program_wait"]
    assert [d.get("error") for d in sorted(waits, key=lambda d: d["chunk"])] == [
        "RuntimeError", None]
    # the failed program still has its place in the device's order
    rows = result.timeline
    assert [len(row["programs"]) for row in rows] == [1, 1]
    assert rows[0]["programs"][0]["ready"] <= rows[1]["programs"][0]["start"]


def test_device_occupancy_counts_idle_in_a_steady_pipelined_drive():
    """A fake clock, no sleep.  The drive of PERF.md §5: program k+1 is
    enqueued 3 s after program k ended, so one program is always
    dispatched and not yet collected — the old series read zero here."""
    counter = telemetry.REGISTRY.get("gordo_build_device_idle_seconds")
    before = counter.value()
    programs = []
    occ = timeline_mod.DeviceOccupancy(100.0, programs.append)
    k0 = occ.enqueued(105.0, chunk=0)          # plan, load, compile: 5 s
    k1 = occ.enqueued(106.0, chunk=1)          # queued behind program 0
    occ.ready(k0, 115.0)
    assert occ.busy() == [(105.0, 115.0), (115.0, float("inf"))]
    occ.ready(k1, 125.0)
    for k in range(2, 6):                      # steady state: 3 s gaps
        enqueued = 125.0 + 13.0 * (k - 2) + 3.0
        assert occ.enqueued(enqueued, chunk=k) == k
        occ.ready(k, enqueued + 10.0)
    assert [p["chunk"] for p in programs] == list(range(6))
    assert [p["start"] - p["idle_from"] for p in programs] == [5.0, 0.0, 3.0, 3.0, 3.0, 3.0]
    assert [p["ready"] - p["start"] for p in programs] == [10.0] * 6
    assert occ.idle_seconds == 17.0
    assert counter.value() - before == 17.0
    assert occ.busy()[-1] == (167.0, 177.0)


def test_ready_stamps_out_of_order_resolve_in_order():
    programs = []
    occ = timeline_mod.DeviceOccupancy(0.0, programs.append)
    a, b = occ.enqueued(1.0, chunk=0), occ.enqueued(1.5, chunk=0)
    occ.ready(b, 9.0)                          # its watcher ran first
    assert programs == []
    occ.ready(a, 9.000004)
    first, second = programs
    assert (first["start"], first["ready"]) == (1.0, 9.000004)
    assert second["start"] == second["ready"] == 9.000004
    assert occ.idle_seconds == 1.0


def test_a_gap_is_split_over_the_host_phases_open_in_it():
    timeline = timeline_mod.BuildTimeline(t0=0.0)
    phases = {
        0: {"fetch": [(9.0, 9.6)], "assemble": [(9.6, 9.8)],
            "handoff": [(9.8, 10.4)]},
        1: {"load": [(10.6, 12.7)], "load_wait": [(10.5, 12.8)],
            "stage": [(12.8, 12.9)], "enqueue": [(12.9, 13.0)]},
    }
    for chunk, by_name in phases.items():
        timeline._row(chunk)["phases"].update(by_name)
    split = timeline.split(10.0, 13.0)
    assert split == pytest.approx({
        "load": 2.1, "load_wait": 0.2, "stage": 0.1, "enqueue": 0.1,
        "finish": 0.4, "other": 0.1})
    assert sum(split.values()) == pytest.approx(3.0)
    # a gap in which nothing was open is all `other`
    assert timeline.split(20.0, 21.0)["other"] == pytest.approx(1.0)


class TestSpanRecord:
    def test_spans_nest_by_id_and_carry_their_interval(self):
        with telemetry.span("test.outer") as outer:
            with telemetry.span("test.inner", rows=3) as inner:
                telemetry.add_to_span(bytes=10, leaves=1)
                telemetry.add_to_span(bytes=5, leaves=1)
                assert "end" not in inner
            assert inner["parent"] == outer["id"] and outer["parent"] is None
        assert inner["bytes"] == 15 and inner["leaves"] == 2 and inner["rows"] == 3
        assert "bytes" not in outer
        assert outer["start"] <= inner["start"] < inner["end"] <= outer["end"]
        telemetry.add_to_span(bytes=1)  # no span open: nothing to add to

    def test_a_worker_thread_in_a_copied_context_keeps_trace_and_parent(self):
        import contextvars

        seen = {}

        def work():
            with telemetry.span("test.worker") as sp:
                seen.update(sp, trace=telemetry.current_trace_id())

        telemetry.set_trace_id("feedfacefeedface")
        try:
            with telemetry.span("test.driver") as driver:
                thread = threading.Thread(
                    target=contextvars.copy_context().run, args=(work,))
                thread.start()
                thread.join(10.0)
                assert not thread.is_alive()
        finally:
            telemetry.set_trace_id(None)
        assert seen["parent"] == driver["id"]
        assert seen["trace"] == "feedfacefeedface"

    def test_record_span_feeds_histogram_and_log(self, tmp_path, monkeypatch):
        log = tmp_path / "spans.jsonl"
        monkeypatch.setenv("GORDO_SPAN_LOG", str(log))
        series = telemetry.REGISTRY.get("gordo_span_seconds")
        before = series.snapshot_series("test.after_the_fact")
        telemetry.record_span("test.after_the_fact", 10.0, 12.5, chunk=4)
        after = series.snapshot_series("test.after_the_fact")
        assert after["count"] == before["count"] + 1
        assert after["sum"] == pytest.approx(before["sum"] + 2.5)
        (doc,) = read_spans(log)
        assert (doc["start"], doc["end"], doc["seconds"]) == (10.0, 12.5, 2.5)
        assert doc["chunk"] == 4 and doc["parent"] is None and doc["id"]

    def test_a_span_lies_in_an_open_profiler_session(self, tmp_path):
        """The third sink: the same name, on the profiler's own clock."""
        import jax
        import jax.numpy as jnp
        from benchmark import trace as trace_mod

        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("gordo.test.annotated", chunk=7):
                jnp.ones(8).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        trace = trace_mod.load(trace_mod.find_xplane(str(tmp_path)))
        events = [ev for ev in trace.host if ev[0] == "gordo.test.annotated"]
        assert len(events) == 1 and events[0][1] < events[0][2]
