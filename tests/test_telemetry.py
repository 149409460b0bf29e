"""Telemetry plane tests: metrics core (concurrent increments, histogram
bucket edges, golden Prometheus rendering), snapshots + merging, trace-id
propagation client → server → response header, span log, and the
``profiling.trace`` always-on recording satellite."""

import asyncio
import json
import os
import threading

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from gordo_tpu import telemetry
from gordo_tpu.telemetry import metrics as metrics_mod

GOLDEN_PATH = os.path.join(
    os.path.dirname(__file__), "data", "telemetry_golden.prom"
)


def _fresh() -> metrics_mod.MetricsRegistry:
    return metrics_mod.MetricsRegistry(enabled=True)


def _golden_registry() -> metrics_mod.MetricsRegistry:
    """Deterministic registry content behind the golden exposition file."""
    reg = _fresh()
    c = reg.counter(
        "gordo_golden_requests_total", "Requests by route and status",
        labels=("route", "status"),
    )
    c.inc(3, "/metrics", "200")
    c.inc(1, "/gordo/v0/{project}/", "404")
    c.inc(1, 'we"ird\\route', "200")  # label escaping exercised
    g = reg.gauge("gordo_golden_queue_depth", "Queue depth")
    g.set(4)
    # a gordo_machine_* family pins the fleet-health gauge rendering
    # (top-K per-machine series with the machine label)
    d = reg.gauge(
        "gordo_machine_drift", "Baseline-vs-live drift", labels=("machine",)
    )
    d.set(0.75, "m-001")
    d.set(0.5, "m-002")
    h = reg.histogram(
        "gordo_golden_request_seconds", "Latency", labels=("route",),
        buckets=(0.005, 0.05, 0.5),
    )
    h.observe(0.004, "/a")
    h.observe(0.05, "/a")  # exactly on a bound: le is inclusive
    h.observe(3.2, "/a")   # over the last bound: +Inf only
    # a gordo_stream_* family pins the streaming-plane catalog rendering
    # (per-event-type counter with the type label)
    s = reg.counter(
        "gordo_stream_events_pushed_total",
        "Events pushed to stream subscribers",
        labels=("type",),
    )
    s.inc(5, "verdict")
    s.inc(1, "threshold")
    # r22 placement-plane families pin the mesh catalog rendering: the
    # mesh-width gauge, the per-kind placement counter, and the
    # per-device transfer counter (labels mirror gordo_tpu/mesh/)
    reg.gauge(
        "gordo_mesh_devices",
        "Device count of the most recently constructed fleet mesh",
    ).set(4)
    p = reg.counter(
        "gordo_fleet_placements_total",
        "Fleet-stack device placements by kind (sharded mesh vs single "
        "device)",
        labels=("kind",),
    )
    p.inc(2, "sharded")
    p.inc(1, "single")
    t = reg.counter(
        "gordo_mesh_device_transfers_total",
        "Array leaves transferred to each device by the placement plane",
        labels=("device",),
    )
    t.inc(6, "0")
    t.inc(6, "1")
    return reg


class TestMetricsCore:
    def test_name_convention_enforced(self):
        reg = _fresh()
        for bad in ("requests_total", "gordo_BadCase", "gordo_", "gordo-x"):
            with pytest.raises(ValueError, match="catalog convention"):
                reg.counter(bad, "x")

    def test_get_or_create_and_kind_conflicts(self):
        reg = _fresh()
        c1 = reg.counter("gordo_x_total", "x")
        assert reg.counter("gordo_x_total", "x") is c1
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("gordo_x_total", "x")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("gordo_x_total", "x", labels=("other",))

    def test_concurrent_increments_are_exact(self):
        """The core thread-safety contract: N threads hammering the same
        counter + histogram lose no updates."""
        reg = _fresh()
        c = reg.counter("gordo_conc_total", "x", labels=("t",))
        h = reg.histogram("gordo_conc_seconds", "x")
        n, n_threads = 2000, 8

        def work(i):
            for _ in range(n):
                c.inc(1.0, str(i % 2))
                h.observe(0.01)

        threads = [
            threading.Thread(target=work, args=(i,))
            for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value("0") + c.value("1") == n * n_threads
        snap = h.snapshot_series()
        assert snap["count"] == n * n_threads
        assert snap["sum"] == pytest.approx(0.01 * n * n_threads)

    def test_histogram_bucket_edges_le_inclusive(self):
        """A value exactly on a bound lands in THAT bucket (Prometheus
        ``le`` semantics), and cumulative rendering reflects it."""
        reg = _fresh()
        h = reg.histogram("gordo_edges_seconds", "x", buckets=(0.1, 1.0))
        for v in (0.1, 1.0, 1.0001):
            h.observe(v)
        assert h.snapshot_series()["counts"] == [1, 1, 1]
        text = reg.render()
        assert 'gordo_edges_seconds_bucket{le="0.1"} 1' in text
        assert 'gordo_edges_seconds_bucket{le="1"} 2' in text
        assert 'gordo_edges_seconds_bucket{le="+Inf"} 3' in text
        assert "gordo_edges_seconds_count 3" in text

    def test_kill_switch_stops_recording(self):
        reg = _fresh()
        c = reg.counter("gordo_switch_total", "x")
        c.inc()
        reg.set_enabled(False)
        c.inc(100)
        reg.set_enabled(True)
        c.inc()
        assert c.value() == 2

    def test_rendering_matches_golden_file(self):
        with open(GOLDEN_PATH) as f:
            golden = f.read()
        assert _golden_registry().render() == golden


class TestSnapshots:
    def test_snapshot_render_roundtrip(self):
        reg = _golden_registry()
        assert telemetry.render_snapshot(reg.snapshot()) == reg.render()

    def test_merge_adds_counters_and_histograms(self):
        snap = _golden_registry().snapshot()
        merged = telemetry.merge_snapshots([snap, snap, snap])
        text = telemetry.render_snapshot(merged)
        assert 'gordo_golden_requests_total{route="/metrics",status="200"} 9' in text
        assert 'gordo_golden_request_seconds_count{route="/a"} 9' in text
        # gauges are last-write, not summed
        assert "gordo_golden_queue_depth 4" in text

    def test_merge_gauge_latest_snapshot_wins(self):
        old = _fresh()
        old.gauge("gordo_g_depth", "x").set(1)
        new = _fresh()
        new.gauge("gordo_g_depth", "x").set(7)
        snap_old, snap_new = old.snapshot(), new.snapshot()
        snap_old["time"], snap_new["time"] = 100.0, 200.0
        for order in ([snap_old, snap_new], [snap_new, snap_old]):
            text = telemetry.render_snapshot(telemetry.merge_snapshots(order))
            assert "gordo_g_depth 7" in text

    def test_write_and_load_snapshot_dir(self, tmp_path):
        reg = _golden_registry()
        d = str(tmp_path / "snaps")
        reg.write_snapshot(os.path.join(d, "shard-000-of-002.json"))
        reg.write_snapshot(os.path.join(d, "shard-001-of-002.json"))
        (tmp_path / "snaps" / "junk.json").write_text("{not json")
        snaps = telemetry.load_snapshot_dir(d)
        assert len(snaps) == 2
        text = telemetry.render_snapshot(telemetry.merge_snapshots(snaps))
        assert 'gordo_golden_requests_total{route="/metrics",status="200"} 6' in text

    def test_add_instance_label(self):
        text = _golden_registry().render()
        labeled = telemetry.add_instance_label(text, "http://a:5555")
        assert 'gordo_golden_queue_depth{instance="http://a:5555"} 4' in labeled
        assert (
            'gordo_golden_requests_total{route="/metrics",status="200",'
            'instance="http://a:5555"} 3' in labeled
        )
        # comments pass through untouched
        assert "# TYPE gordo_golden_queue_depth gauge" in labeled

    def test_merge_expositions_groups_families(self):
        """Merged multi-target output keeps each family's samples in ONE
        block under a single HELP/TYPE header (text-format requirement a
        naive concat violates)."""
        text = _golden_registry().render()
        merged = telemetry.merge_expositions([("a", text), ("b", text)])
        assert merged.count("# TYPE gordo_golden_queue_depth gauge") == 1
        lines = merged.splitlines()
        idx = [
            i for i, line in enumerate(lines)
            if line.startswith("gordo_golden_queue_depth{")
        ]
        assert len(idx) == 2 and idx[1] == idx[0] + 1  # contiguous block
        assert 'instance="a"' in lines[idx[0]]
        assert 'instance="b"' in lines[idx[1]]

    def test_scrape_metrics_merges_extra_pairs(self):
        from gordo_tpu.watchman.endpoints_status import scrape_metrics

        text = _golden_registry().render()
        merged, n = asyncio.run(
            scrape_metrics([], extra=[("watchman", text)])
        )
        assert n == 0
        assert 'gordo_golden_queue_depth{instance="watchman"} 4' in merged


class TestTracePropagation:
    """One trace id stitches client → HTTP header → server → response."""

    def _server_app(self):
        from gordo_tpu.serve.server import ModelCollection, build_app

        return build_app(ModelCollection({}, project="traceproj"))

    def test_server_echoes_and_mints_trace_ids(self):
        async def run():
            client = TestClient(TestServer(self._server_app()))
            await client.start_server()
            try:
                sent = await client.get(
                    "/gordo/v0/traceproj/",
                    headers={telemetry.TRACE_HEADER: "feedbeefcafe0123"},
                )
                unsent = await client.get("/gordo/v0/traceproj/")
                return (
                    sent.headers.get(telemetry.TRACE_HEADER),
                    unsent.headers.get(telemetry.TRACE_HEADER),
                )
            finally:
                await client.close()

        echoed, minted = asyncio.run(run())
        assert echoed == "feedbeefcafe0123"
        assert minted and len(minted) == 16 and minted != echoed

    def test_error_responses_carry_the_trace_id(self):
        async def run():
            client = TestClient(TestServer(self._server_app()))
            await client.start_server()
            try:
                resp = await client.get(
                    "/gordo/v0/traceproj/nope/healthcheck",
                    headers={telemetry.TRACE_HEADER: "abcdef0123456789"},
                )
                return resp.status, resp.headers.get(telemetry.TRACE_HEADER)
            finally:
                await client.close()

        status, tid = asyncio.run(run())
        assert status == 404 and tid == "abcdef0123456789"

    def test_client_io_sends_trace_header(self):
        """client/io.request_json injects the context's trace id into
        every outbound request (minting one when unbound)."""
        from gordo_tpu.client.io import post_json

        seen = {}

        async def handler(request: web.Request) -> web.Response:
            seen["trace"] = request.headers.get(telemetry.TRACE_HEADER)
            return web.json_response({"data": {}})

        async def run():
            app = web.Application()
            app.router.add_post("/score", handler)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            import aiohttp

            telemetry.set_trace_id("0123456789abcdef")
            async with aiohttp.ClientSession() as session:
                await post_json(
                    session, f"http://127.0.0.1:{port}/score", {"X": []}
                )
            await runner.cleanup()

        asyncio.run(run())
        assert seen["trace"] == "0123456789abcdef"


class TestSpans:
    def test_span_log_jsonl(self, tmp_path, monkeypatch):
        log_path = str(tmp_path / "spans.jsonl")
        monkeypatch.setenv("GORDO_SPAN_LOG", log_path)
        telemetry.set_trace_id("1111222233334444")
        with telemetry.span("test.section", machine="m-1") as attrs:
            attrs["batch"] = 3
        with open(log_path) as f:
            doc = json.loads(f.readline())
        assert doc["span"] == "test.section"
        assert doc["trace"] == "1111222233334444"
        assert doc["machine"] == "m-1" and doc["batch"] == 3
        assert doc["seconds"] >= 0

    def test_span_feeds_histogram(self):
        h = telemetry.REGISTRY.get("gordo_span_seconds")
        before = h.snapshot_series("test.histo")["count"]
        with telemetry.span("test.histo"):
            pass
        assert h.snapshot_series("test.histo")["count"] == before + 1

    def test_ensure_trace_id_mints_once(self):
        telemetry.set_trace_id(None)
        tid = telemetry.ensure_trace_id()
        assert telemetry.ensure_trace_id() == tid == (
            telemetry.current_trace_id()
        )


def test_profiling_trace_records_without_profile_dir(monkeypatch):
    """profiling.trace is a thin caller of telemetry.span: without
    GORDO_PROFILE_DIR the section's wall time still reaches
    gordo_span_seconds, once, with the pre-'/' head as the bounded label."""
    monkeypatch.delenv("GORDO_PROFILE_DIR", raising=False)
    from gordo_tpu.utils import profiling

    h = telemetry.REGISTRY.get("gordo_span_seconds")
    before = h.snapshot_series("profile.unit_test_section")["count"]
    with profiling.trace("unit_test_section/512"):
        pass
    after = h.snapshot_series("profile.unit_test_section")["count"]
    assert after == before + 1


def test_events_are_counted_and_single_line(caplog):
    import logging

    events = telemetry.REGISTRY.get("gordo_events_total")
    before = events.value("unit_test_event")
    test_logger = logging.getLogger("gordo_tpu.tests.events")
    with caplog.at_level(logging.WARNING, logger=test_logger.name):
        telemetry.log_event(
            test_logger, "unit_test_event", cooldown_s=0.5, streak=2
        )
    assert events.value("unit_test_event") == before + 1
    lines = [r.getMessage() for r in caplog.records]
    assert lines == ["EVENT unit_test_event cooldown_s=0.5 streak=2"]
