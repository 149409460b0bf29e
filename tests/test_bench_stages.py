"""bench.py CLI surface: ``--stage`` selection (the knob that lets an
operator run ONE stage without paying for the rest), ``--round``
persistence wiring, and the no-hidden-fallback contract of ``main()``:
no accelerator means no run, a stage that raises means a non-zero exit,
and a stage that measures through CPU-pinned children is refused while
the parent holds a chip."""

import json
import subprocess
import types

import pytest

import bench


def test_default_runs_every_stage_in_priority_order():
    assert bench.parse_stages([]) == [
        "build", "build_throughput", "artifact_io", "hot_reload", "serving",
        "serving_precision", "serving_sharded", "serving_wire",
        "serving_openloop", "telemetry_overhead", "health_overhead",
        "cold_start", "multi_device", "refresh", "backfill",
        "scores_lifecycle", "streaming", "lstm",
    ]


def test_backfill_stage_selectable():
    assert bench.parse_stages(["--stage", "backfill"]) == ["backfill"]


def test_build_throughput_stage_selectable():
    assert bench.parse_stages(["--stage", "build_throughput"]) == [
        "build_throughput"
    ]


def test_cold_start_stage_selectable():
    assert bench.parse_stages(["--stage", "cold_start"]) == ["cold_start"]


def test_refresh_stage_selectable():
    assert bench.parse_stages(["--stage", "refresh"]) == ["refresh"]


def test_serving_wire_stage_selectable():
    assert bench.parse_stages(["--stage", "serving_wire"]) == [
        "serving_wire"
    ]


def test_artifact_io_stage_selectable():
    assert bench.parse_stages(["--stage", "artifact_io"]) == ["artifact_io"]


def test_multi_device_stage_selectable():
    assert bench.parse_stages(["--stage", "multi_device"]) == [
        "multi_device"
    ]


def test_scores_lifecycle_stage_selectable():
    assert bench.parse_stages(["--stage", "scores_lifecycle"]) == [
        "scores_lifecycle"
    ]


def test_single_stage_selection():
    assert bench.parse_stages(["--stage", "serving_openloop"]) == [
        "serving_openloop"
    ]


def test_multi_stage_selection_is_canonically_ordered():
    # selection order must not reorder execution: build always precedes
    # lstm regardless of flag order
    assert bench.parse_stages(
        ["--stage", "lstm", "--stage", "build"]
    ) == ["build", "lstm"]


def test_unknown_stage_rejected():
    with pytest.raises(SystemExit):
        bench.parse_stages(["--stage", "nope"])


def test_round_flag_and_env(monkeypatch):
    monkeypatch.delenv("BENCH_ROUND", raising=False)
    assert bench.parse_cli([])[1] is None
    assert bench.parse_cli(["--round", "9"])[1] == 9
    monkeypatch.setenv("BENCH_ROUND", "7")
    assert bench.parse_cli([])[1] == 7
    # explicit flag beats the env
    assert bench.parse_cli(["--round", "9"])[1] == 9


def test_persist_round_atomic_write(tmp_path, monkeypatch):
    """The round artifact lands complete via tmp+rename, and a write
    failure is loud (nonzero exit code), not silent — the r6 round file
    was referenced from CHANGES.md but never actually committed."""
    monkeypatch.setattr(bench, "_REPO_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_ROUND", 42)
    monkeypatch.setattr(bench, "_round_write_failed", False)
    doc = {"metric": "x", "value": 1.0}
    bench.persist_round(doc)
    path = tmp_path / "BENCH_r42.json"
    assert path.exists()
    assert json.loads(path.read_text()) == doc
    assert bench.exit_code() == 0
    # no stray tmp files
    assert [p.name for p in tmp_path.iterdir()] == ["BENCH_r42.json"]

    # unwritable target -> loud failure, nonzero exit
    monkeypatch.setattr(bench, "_REPO_DIR", str(tmp_path / "nope" / "deeper"))
    bench.persist_round(doc)
    assert bench.exit_code() == 1


@pytest.fixture
def fake_chip(monkeypatch):
    """main() sees one v5e; nothing real is touched."""
    import jax

    device = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [device])
    monkeypatch.setattr(bench, "_failed_stages", {})
    monkeypatch.setattr(bench, "_emitted", False)
    monkeypatch.setattr(bench, "_ROUND", None)
    monkeypatch.delenv("BENCH_ROUND", raising=False)


def test_main_without_a_chip_fails_and_starts_nothing(monkeypatch, capsys):
    """conftest pins the cpu backend: no result line, non-zero exit, and
    no subprocess (the CPU re-run is gone)."""
    def no_children(*a, **k):
        raise AssertionError(f"bench started a child process: {a}")

    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.setattr(subprocess, "Popen", no_children)
    assert bench.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err


def test_stage_that_raises_makes_the_exit_code_nonzero(
    fake_chip, monkeypatch, capsys
):
    def boom(out):
        raise RuntimeError("device said no")

    monkeypatch.setattr(bench, "bench_serving", boom)
    monkeypatch.setattr(
        bench, "bench_streaming", lambda out: out.update(streamed=1)
    )
    rc = bench.main(["--stage", "serving", "--stage", "streaming"])
    assert rc != 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["stages_failed"] == {"serving": "device said no"}
    # the failure cost no other stage its numbers, and the result names
    # the device it ran on
    assert doc["stages_done"] == ["streaming"] and doc["streamed"] == 1
    assert (doc["platform"], doc["device_kind"], doc["n_chips"]) == (
        "tpu", "TPU v5 lite", 1,
    )


@pytest.mark.parametrize("stage", sorted(bench.CHILD_PROCESS_STAGES))
def test_child_process_stage_is_refused_on_a_chip(fake_chip, capsys, stage):
    """Those stages pin their children to JAX_PLATFORMS=cpu; selected with
    the parent on a chip they would publish CPU numbers under a TPU
    headline."""
    assert bench.main(["--stage", stage]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert stage in captured.err


def test_mfu_needs_a_known_device_kind():
    """A rate is never divided by another device's peak."""
    out = {"device_kind": "cpu", "n_chips": 1}
    model = types.SimpleNamespace(
        base_estimator=types.SimpleNamespace(params_={"w": bench.np.ones((3, 3))})
    )
    with pytest.raises(RuntimeError, match="no peak FLOP/s on record"):
        bench._flop_fields(out, "build", model, 1000.0)
    out["device_kind"] = "TPU v5 lite"
    bench._flop_fields(out, "build", model, 1000.0)
    assert out["build_mfu_estimate"] >= 0


def test_persist_round_noop_without_round(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_REPO_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_ROUND", None)
    monkeypatch.setattr(bench, "_round_write_failed", False)
    bench.persist_round({"metric": "x"})
    assert list(tmp_path.iterdir()) == []
    assert bench.exit_code() == 0


@pytest.mark.slow
def test_cold_start_stage_smoke(monkeypatch):
    """The CI slow-lane cold_start smoke (ISSUE 5 satellite): one trial of
    the full stage — build, forked cold/warm children, cached restart —
    must produce the acceptance fields with the gates holding on CPU."""
    monkeypatch.setenv("BENCH_COLD_TRIALS", "1")
    out = {}
    bench.bench_cold_start(out)
    assert out["cold_start_warmed_5x_ok"] is True
    assert (
        out["cold_start_unwarmed_first_request_p99_ms"]
        >= 5.0 * out["cold_start_warmed_first_request_p99_ms"]
    )
    assert out["cold_start_cached_restart_ok"] is True
    assert out["cold_start_cache_hit_metrics"], (
        "persistent-cache hits must be attested in the child's exposition"
    )


@pytest.mark.slow
def test_serving_wire_stage_smoke(monkeypatch):
    """The CI slow-lane serving_wire smoke (ISSUE 15 satellite): a tiny
    fleet, one chunk per leg — the stage must produce both wire legs,
    the speedup ratio, and the fp32 value-identity attestation. The gate
    fields exist but are only ENFORCED at full scale (--round)."""
    monkeypatch.setenv("BENCH_WIRE_MACHINES", "8")
    monkeypatch.setenv("BENCH_WIRE_CHUNKS", "1")
    monkeypatch.setenv("BENCH_WIRE_MSGPACK_CHUNKS", "1")
    monkeypatch.setenv("BENCH_WIRE_ROWS", "256")
    monkeypatch.setenv("BENCH_WIRE_REPEATS", "1")
    out = {}
    bench.bench_serving_wire(out)
    assert out["serving_wire_columnar_samples_per_sec"] > 0
    assert out["serving_wire_msgpack_samples_per_sec"] > 0
    assert out["serving_wire_speedup_vs_msgpack"] == pytest.approx(
        out["serving_wire_columnar_samples_per_sec"]
        / out["serving_wire_msgpack_samples_per_sec"],
        rel=5e-3,
    )
    assert out["serving_wire_value_identity_ok"] is True
    assert "serving_wire_ge_3x_r18_ok" in out


@pytest.mark.slow
def test_multi_device_stage_smoke(monkeypatch):
    """The CI slow-lane multi_device smoke (r22 placement plane): forked
    children over a tiny {1,2} device sweep must report the per-count
    throughput curve, the speedup map, fp32 BYTE PARITY of the sharded
    fit + scoring vs the 1-device child, per-device placement attested
    via addressable_shards, exactly one sharded executable per bucket,
    and the honesty note when the host has fewer cores than forced
    devices. The >=1.6x-at-2 gate field exists but is only meaningful
    on real multi-core/multi-chip hosts."""
    monkeypatch.setenv("BENCH_MULTI_DEVICE_COUNTS", "1,2")
    monkeypatch.setenv("BENCH_MULTI_DEVICE_MACHINES", "8")
    monkeypatch.setenv("BENCH_MULTI_DEVICE_ROWS", "256")
    monkeypatch.setenv("BENCH_MULTI_DEVICE_ROUNDS", "2")
    out = {}
    bench.bench_multi_device(out)
    assert out["multi_device_counts"] == [1, 2]
    assert out["multi_device_samples_per_sec"]["1"] > 0
    assert out["multi_device_samples_per_sec"]["2"] > 0
    assert out["multi_device_speedup_at_2"] == pytest.approx(
        out["multi_device_samples_per_sec"]["2"]
        / out["multi_device_samples_per_sec"]["1"],
        rel=5e-3,
    )
    assert "multi_device_ge_1_6x_at_2_ok" in out
    # the r22 correctness gates
    assert out["multi_device_byte_parity"] == {"2": True}
    assert out["multi_device_byte_parity_ok"] is True
    assert out["multi_device_placement_ok"] is True
    att = out["multi_device_placement"]["2"]
    assert att["fit"]["n_shards"] == 2
    assert att["fit"]["device_ids"] == [0, 1]
    assert att["score"]["n_shards"] == 2
    assert att["one_executable_per_bucket_ok"] is True


@pytest.mark.slow
def test_scores_lifecycle_stage_smoke(monkeypatch, tmp_path):
    """The CI slow-lane scores_lifecycle smoke (ISSUE 16 tentpole): a
    tiny fleet-archive run of the full stage — build, scan, compact,
    aggregate byte-identity, server pushdown vs fetch-and-aggregate,
    gc — must produce every acceptance field with the CORRECTNESS
    attestations holding. The perf-ratio gates exist but are only
    ENFORCED at full scale (--round)."""
    monkeypatch.setenv("BENCH_SCORES_MACHINES", "8")
    monkeypatch.setenv("BENCH_SCORES_CHUNK_ROWS", "256")
    monkeypatch.setenv("BENCH_SCORES_CHUNKS", "4")
    monkeypatch.setenv("BENCH_SCORES_TAGS", "3")
    monkeypatch.setenv("BENCH_SCORES_DIR", str(tmp_path))
    out = {}
    bench.bench_scores_lifecycle(out)
    assert out["scores_machines"] == 8
    assert out["scores_compact_segments_merged"] >= 2
    assert out["scores_aggregate_bytes_identical_ok"] is True
    assert out["scores_pushdown_parity_ok"] is True
    assert out["scores_pushdown_speedup"] > 0
    assert "scores_compact_ge_half_scan_ok" in out
    assert "scores_pushdown_ge_10x_ok" in out
    assert out["scores_scan_mb_per_s"] > 0
    assert out["scores_compact_mb_per_s"] > 0
