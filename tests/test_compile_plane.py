"""Compile plane: AOT program registry, warmup manifest round-trip,
warming→ready readiness, persistent-cache reuse across a process restart.

The acceptance-critical pins (ISSUE 5):

- serving results byte-identical with warmup on vs off (the AOT
  executable and the jit path are the same HLO);
- build → manifest → server pre-compile round-trip: what the builder
  records is what warmup compiles, and the first request after warmup
  dispatches a cache HIT, not a compile;
- ``/healthz`` reports ``warming`` under concurrent traffic and flips to
  ``ready`` exactly when the warmup future resolves;
- a forked process pointed at the same ``JAX_COMPILATION_CACHE_DIR``
  reuses the parent population's compiles (slow lane).
"""

import asyncio
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_tpu import compile as compile_plane
from gordo_tpu import telemetry
from gordo_tpu.builder import build_project
from gordo_tpu.compile import (
    load_warmup_manifest,
    warmup_collection,
    write_warmup_manifest,
)
from gordo_tpu.serve import ModelCollection, build_app
from gordo_tpu.workflow import NormalizedConfig

PROJECT = {
    "machines": [
        {
            "name": f"cp-machine-{i}",
            "dataset": {
                "type": "RandomDataset",
                "tags": ["tag-1", "tag-2", "tag-3"],
                "train_start_date": "2017-12-25T06:00:00Z",
                "train_end_date": "2017-12-27T06:00:00Z",
            },
        }
        for i in range(3)
    ],
    "globals": {
        "model": {
            "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "gordo_tpu.pipeline.Pipeline": {
                        "steps": [
                            "gordo_tpu.ops.scalers.MinMaxScaler",
                            {
                                "gordo_tpu.models.estimator.AutoEncoder": {
                                    "kind": "feedforward_hourglass",
                                    "epochs": 2,
                                    "batch_size": 64,
                                }
                            },
                        ]
                    }
                }
            }
        }
    },
}


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("cp-artifacts")
    cfg = NormalizedConfig(PROJECT, "cpproj")
    result = build_project(cfg.machines, str(out))
    assert not result.failed
    return str(out)


# ---------------------------------------------------------------------------
# Program registry
# ---------------------------------------------------------------------------

def test_program_aot_matches_jit_bitwise():
    import jax.numpy as jnp

    def f(mode, stats, x):
        y = x * stats["a"] + stats["b"]
        return {"out": y if mode == "double" else -y}

    prog = compile_plane.Program("test.parity", f, static_argnames=("mode",))
    stats = {"a": jnp.full((4,), 1.5), "b": jnp.full((4,), -0.25)}
    x = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
    via_plane = prog("double", stats, x)
    via_jit = prog._jitted("double", stats, x)
    np.testing.assert_array_equal(
        np.asarray(via_plane["out"]), np.asarray(via_jit["out"])
    )


def test_program_warm_precompiles_and_call_hits():
    import jax
    import jax.numpy as jnp

    def g(x):
        return x + 1.0

    prog = compile_plane.Program("test.warm", g)
    sds = jax.ShapeDtypeStruct((5,), jnp.float32)
    first = prog.warm(sds)
    assert first > 0.0  # compiled now
    assert prog.warm(sds) == 0.0  # second warm is a no-op
    reg = telemetry.REGISTRY.snapshot()
    before = _counter(reg, "gordo_compile_cache_hits_total", "programs")
    out = prog(np.arange(5, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(out), np.arange(5, dtype=np.float32) + 1.0
    )
    after = _counter(
        telemetry.REGISTRY.snapshot(), "gordo_compile_cache_hits_total",
        "programs",
    )
    assert after == before + 1  # the real call hit the warmed executable


def _counter(snapshot, name, label_value):
    metric = snapshot["metrics"].get(name) or {}
    for key, value in metric.get("series", {}).items():
        if label_value in json.loads(key):
            return value
    return 0.0


def test_registry_lru_evicts_executables():
    import jax
    import jax.numpy as jnp

    reg = compile_plane.CompileRegistry(max_executables=2)

    def h(x):
        return x * 3.0

    prog = compile_plane.Program("test.evict", h, registry=reg)
    for n in (2, 3, 4):
        prog.warm(jax.ShapeDtypeStruct((n,), jnp.float32))
    assert reg.n_executables() == 2  # the first signature evicted


def test_cached_closure_shares_one_policy():
    calls = []

    def factory():
        calls.append(1)
        return object()

    a = compile_plane.cached_closure(("test.closure", 1), factory)
    b = compile_plane.cached_closure(("test.closure", 1), factory)
    assert a is b and len(calls) == 1


def test_plane_kill_switch_uses_plain_jit(monkeypatch):
    monkeypatch.setenv("GORDO_COMPILE_PLANE", "off")

    def f(x):
        return x - 2.0

    prog = compile_plane.Program("test.off", f)
    out = prog(np.arange(3, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(out), np.arange(3, dtype=np.float32) - 2.0
    )
    assert prog._registry._get_executable is not None  # nothing cached:
    # plain-jit dispatch leaves the AOT cache untouched for this call
    # (the registry may hold entries from other tests; assert via name)
    assert not any(
        key[0] == "test.off" for key in prog._registry._executables
    )


def test_closure_program_warm_precompiles_and_call_hits():
    """r23: the fleet-build closures get the Program warm/call contract —
    a warmed signature dispatches the AOT executable (cache HIT), and the
    result is bitwise the jitted closure's."""
    import jax
    import jax.numpy as jnp

    scale = 2.5  # the closed-over configuration

    def f(x):
        return x * scale

    prog = compile_plane.closure_program(f, name="test.closure_warm")
    sds = jax.ShapeDtypeStruct((6,), jnp.float32)
    assert prog.warm(sds) > 0.0   # compiled now
    assert prog.warm(sds) == 0.0  # idempotent
    before = _counter(
        telemetry.REGISTRY.snapshot(), "gordo_compile_cache_hits_total",
        "programs",
    )
    x = np.arange(6, dtype=np.float32)
    out = prog(x)
    np.testing.assert_array_equal(np.asarray(out), x * scale)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(prog._jitted(x))
    )
    after = _counter(
        telemetry.REGISTRY.snapshot(), "gordo_compile_cache_hits_total",
        "programs",
    )
    assert after == before + 1


def test_closure_program_cold_and_unwarmed_signatures_fall_through():
    """A never-warmed closure (the common cold build) and a warmed one
    called at a DIFFERENT signature both dispatch through plain jit —
    same numerics, nothing cached for the unseen shape."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return x + 10.0

    cold = compile_plane.closure_program(f, name="test.closure_cold")
    assert not cold._exes
    x = np.arange(4, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(cold(x)), x + 10.0)
    assert not cold._exes  # __call__ never populates the AOT dict

    warmed = compile_plane.closure_program(f, name="test.closure_other")
    warmed.warm(jax.ShapeDtypeStruct((4,), jnp.float32))
    y = np.arange(7, dtype=np.float32)  # signature never warmed
    np.testing.assert_array_equal(np.asarray(warmed(y)), y + 10.0)
    assert len(warmed._exes) == 1


def test_closure_program_kill_switch_uses_plain_jit(monkeypatch):
    monkeypatch.setenv("GORDO_COMPILE_PLANE", "off")

    def f(x):
        return x - 1.0

    prog = compile_plane.closure_program(f, name="test.closure_off")
    import jax
    import jax.numpy as jnp

    assert prog.warm(jax.ShapeDtypeStruct((3,), jnp.float32)) == 0.0
    assert not prog._exes  # plane off: nothing compiles ahead of time
    x = np.arange(3, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(prog(x)), x - 1.0)


def test_fleet_builder_warm_precompiles_group_program():
    """FleetDiffBuilder.warm pre-compiles the bucket's program from shapes
    alone: the subsequent dispatch of a matching group is an AOT hit."""
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition
    from gordo_tpu.serializer import from_definition

    definition = {
        "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.pipeline.Pipeline": {
                    "steps": [
                        "gordo_tpu.ops.scalers.MinMaxScaler",
                        {
                            "gordo_tpu.models.estimator.AutoEncoder": {
                                "kind": "feedforward_hourglass",
                                "epochs": 1,
                                "batch_size": 64,
                            }
                        },
                    ]
                }
            }
        }
    }
    spec = analyze_definition(from_definition(definition))
    builder = FleetDiffBuilder(spec)
    dt = builder.warm(m=2, n_rows=220, n_features=3)
    assert dt > 0.0
    assert builder.warm(m=2, n_rows=220, n_features=3) == 0.0
    before = _counter(
        telemetry.REGISTRY.snapshot(), "gordo_compile_cache_hits_total",
        "programs",
    )
    rng = np.random.default_rng(3)
    Xs = [rng.standard_normal((220, 3)).astype(np.float32) for _ in range(2)]
    dets = builder.dispatch(Xs).collect()
    assert len(dets) == 2
    after = _counter(
        telemetry.REGISTRY.snapshot(), "gordo_compile_cache_hits_total",
        "programs",
    )
    assert after == before + 1  # the dispatch hit the warmed executable


# ---------------------------------------------------------------------------
# warmup manifest round-trip
# ---------------------------------------------------------------------------

def test_build_writes_warmup_manifest(model_dir):
    manifest = load_warmup_manifest(model_dir)
    assert manifest is not None
    machines = {
        name for entry in manifest["programs"] for name in entry["machines"]
    }
    assert machines == {f"cp-machine-{i}" for i in range(3)}
    entry = manifest["programs"][0]
    assert entry["n_features"] == 3 and entry["n_outputs"] == 3
    assert entry["signature"]
    assert manifest["row_buckets"] == [256, 2048]


def test_manifest_merge_keeps_disjoint_entries(tmp_path):
    out = str(tmp_path)
    write_warmup_manifest(
        out, [{"signature": "aaa", "machines": ["m1"], "n_machines": 1,
               "n_features": 2, "n_outputs": 2, "lookback": 1}]
    )
    # a later partial rebuild of a DIFFERENT machine merges, not clobbers
    write_warmup_manifest(
        out, [{"signature": "bbb", "machines": ["m2"], "n_machines": 1,
               "n_features": 2, "n_outputs": 2, "lookback": 1}]
    )
    # rebuilding m1 replaces its entry
    write_warmup_manifest(
        out, [{"signature": "ccc", "machines": ["m1"], "n_machines": 1,
               "n_features": 2, "n_outputs": 2, "lookback": 1}]
    )
    manifest = load_warmup_manifest(out)
    by_machine = {e["machines"][0]: e["signature"]
                  for e in manifest["programs"]}
    assert by_machine == {"m1": "ccc", "m2": "bbb"}
    # an empty (fully-cached) re-run leaves the manifest untouched
    assert write_warmup_manifest(out, []) is None
    assert load_warmup_manifest(out)["programs"] == manifest["programs"]


def test_manifest_carries_serving_dtype(tmp_path, monkeypatch):
    """v2 manifests record the build-time serving dtype: the env knob at
    write time wins, an explicit argument overrides it, and a v1
    manifest (no dtype field) reads back as float32."""
    out = str(tmp_path)
    entry = [{"signature": "sig", "machines": ["m1"], "n_machines": 1,
              "n_features": 2, "n_outputs": 2, "lookback": 1}]
    monkeypatch.setenv("GORDO_SERVE_DTYPE", "bf16")
    write_warmup_manifest(out, entry)
    manifest = load_warmup_manifest(out)
    assert manifest["dtype"] == "bfloat16"
    monkeypatch.delenv("GORDO_SERVE_DTYPE")
    # explicit argument beats the (now unset) env
    write_warmup_manifest(out, entry, serve_dtype="float32")
    assert load_warmup_manifest(out)["dtype"] == "float32"
    # a v1 manifest (pre-dtype) reads as float32
    import os as _os

    shard = _os.path.join(out, ".gordo-warmup",
                          "shard-000-of-001.json")
    doc = json.load(open(shard))
    doc.pop("dtype")
    doc["version"] = 1
    json.dump(doc, open(shard, "w"))
    assert load_warmup_manifest(out)["dtype"] == "float32"


def test_manifest_mixed_shard_dtypes_yield_none(tmp_path):
    """Shards disagreeing on dtype (a half-finished precision migration)
    must not let warmup guess — the manifest dtype reads as None and the
    serve plane falls back to its env resolution."""
    out = str(tmp_path)
    write_warmup_manifest(
        out, [{"signature": "a", "machines": ["m1"], "n_machines": 1,
               "n_features": 2, "n_outputs": 2, "lookback": 1}],
        shard=(0, 2), serve_dtype="float32",
    )
    write_warmup_manifest(
        out, [{"signature": "b", "machines": ["m2"], "n_machines": 1,
               "n_features": 2, "n_outputs": 2, "lookback": 1}],
        shard=(1, 2), serve_dtype="bfloat16",
    )
    assert load_warmup_manifest(out)["dtype"] is None


def test_bf16_manifest_warms_bf16_executables(model_dir, tmp_path, monkeypatch):
    """The dtype round-trip pin (ISSUE 7 satellite): a manifest written
    under bf16 must warm bf16 executables, not fp32 ones — and the
    collection built over it must DISPATCH bf16, so the warmed
    executables are the ones requests hit."""
    import shutil

    from gordo_tpu.compile.registry import REGISTRY

    # private copy: rewriting the shared module fixture's manifest would
    # leak bf16 into every other test using model_dir
    work = str(tmp_path / "bf16-artifacts")
    shutil.copytree(model_dir, work)
    manifest = load_warmup_manifest(work)
    monkeypatch.setenv("GORDO_SERVE_DTYPE", "bfloat16")
    write_warmup_manifest(
        work,
        [e for e in manifest["programs"]],
    )
    monkeypatch.delenv("GORDO_SERVE_DTYPE")
    assert load_warmup_manifest(work)["dtype"] == "bfloat16"

    # env UNSET: the manifest's dtype must drive both warmup and dispatch
    REGISTRY.clear()
    collection = ModelCollection.from_directory(work, project="cpproj")
    assert collection.serve_dtype == "bfloat16"
    stats = warmup_collection(collection)
    assert stats["errors"] == 0
    assert stats["dtype"] == "bfloat16"
    serve_keys = [
        key for key in REGISTRY._executables
        if str(key[0]).startswith("serve.")
    ]
    assert serve_keys, "warmup compiled no serving executables"
    for key in serve_keys:
        statics = dict(key[1])
        assert statics.get("dtype") == "bfloat16", key
    # and a real request hits a warmed executable, not a fresh compile
    reg = telemetry.REGISTRY.snapshot()
    before_miss = _counter(reg, "gordo_compile_cache_misses_total",
                           "programs")
    X = np.random.default_rng(3).standard_normal((256, 3)).astype(np.float32)
    collection.get("cp-machine-0").scorer.anomaly_arrays(X)
    after_miss = _counter(
        telemetry.REGISTRY.snapshot(),
        "gordo_compile_cache_misses_total", "programs",
    )
    assert after_miss == before_miss  # warmed, not compiled on request


def test_warmup_collection_precompiles_from_manifest(model_dir):
    collection = ModelCollection.from_directory(model_dir, project="cpproj")
    stats = warmup_collection(collection)
    assert stats["errors"] == 0
    assert stats["buckets"] == 1
    labels = {p["program"] for p in stats["programs"]}
    assert "serve.fleet/full" in labels
    assert "serve.fleet/subset" in labels
    assert "serve.score/anomaly" in labels
    # the streaming plane's incremental step warms alongside (rows=1 —
    # its dispatch shape is always one arriving row)
    assert "serve.stream_step" in labels
    # manifest row buckets drove the warm set
    rows = {p["rows"] for p in stats["programs"]}
    assert rows == {1, 256, 2048}


def test_serving_results_identical_warmup_on_vs_off(model_dir):
    """The acceptance parity pin: a warmed collection returns byte-for-
    byte what an unwarmed one does (same machines, same request)."""
    rng = np.random.default_rng(7)
    X = rng.standard_normal((300, 3)).astype(np.float32)

    warmed = ModelCollection.from_directory(model_dir, project="cpproj")
    assert warmup_collection(warmed)["errors"] == 0
    res_warm = warmed.fleet_scorer.score_all(
        {name: X for name in warmed.entries}
    )
    cold = ModelCollection.from_directory(model_dir, project="cpproj")
    res_cold = cold.fleet_scorer.score_all(
        {name: X for name in cold.entries}
    )
    assert set(res_warm) == set(res_cold)
    for name in res_warm:
        for key in res_warm[name]:
            np.testing.assert_array_equal(
                np.asarray(res_warm[name][key]),
                np.asarray(res_cold[name][key]),
                err_msg=f"{name}/{key} diverged between warmup on and off",
            )
    # per-machine route parity too
    e_warm = warmed.get(sorted(warmed.entries)[0])
    e_cold = cold.get(sorted(cold.entries)[0])
    a, b = e_warm.scorer.anomaly_arrays(X), e_cold.scorer.anomaly_arrays(X)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


# ---------------------------------------------------------------------------
# warming → ready readiness under concurrent requests
# ---------------------------------------------------------------------------

def test_healthz_warming_to_ready_under_concurrent_requests(
    model_dir, monkeypatch
):
    """/healthz says ``warming`` while the warmup thread runs, requests
    issued DURING warming still succeed, and the state flips to ``ready``
    (with the compile plane's warming flag cleared) when it finishes."""
    from gordo_tpu.serve import server as server_mod

    release = threading.Event()
    started = threading.Event()

    def slow_warmup(collection, row_sizes=None):
        started.set()
        assert compile_plane.warming()  # the flag is up while we compile
        release.wait(timeout=30)
        return {"buckets": 1, "fallbacks": 0, "errors": 0, "programs": []}

    monkeypatch.setattr(server_mod, "warmup_scorers", slow_warmup)

    async def runner():
        collection = ModelCollection.from_directory(
            model_dir, project="cpproj"
        )
        client = TestClient(TestServer(build_app(collection, warmup=True)))
        await client.start_server()
        try:
            assert started.wait(timeout=10)
            # concurrent traffic during warming: state reports warming,
            # scoring requests still serve (they compile lazily)
            X = np.zeros((300, 3), np.float32).tolist()
            health, ready, score = await asyncio.gather(
                client.get("/healthz"),
                client.get("/gordo/v0/cpproj/ready"),
                client.post(
                    "/gordo/v0/cpproj/cp-machine-0/anomaly/prediction",
                    json={"X": X},
                ),
            )
            assert (await health.json())["state"] == "warming"
            assert ready.status == 503
            assert score.status == 200
            release.set()
            await _wait(client.app[server_mod.WARMUP_TASK_KEY])
            health2 = await client.get("/healthz")
            doc = await health2.json()
            assert doc["state"] == "ready"
            assert doc["warmup_errors"] == 0
            assert (await client.get("/gordo/v0/cpproj/ready")).status == 200
            assert not compile_plane.warming()
        finally:
            release.set()
            await client.close()

    async def _wait(fut):
        while not fut.done():
            await asyncio.sleep(0.01)

    asyncio.run(runner())


def test_coalescer_queues_while_warming(monkeypatch):
    """During warmup the coalescer coalesces unconditionally (queue
    behind the shared compile) instead of bypass-dispatching a cold
    compile per executor thread."""
    from gordo_tpu.serve.coalesce import CoalescingScorer

    co = CoalescingScorer(lambda: None, knee_batch=4)
    try:
        co.inflight = 1  # below min_concurrency: would normally bypass
        compile_plane.set_warming(True)
        try:
            assert co.should_coalesce() is True
        finally:
            compile_plane.set_warming(False)
        assert co.should_coalesce() is False  # back to the adaptive bypass
    finally:
        co.close()


# ---------------------------------------------------------------------------
# CLI gate
# ---------------------------------------------------------------------------

def test_gordo_warmup_dir_cli(model_dir):
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import gordo

    res = CliRunner().invoke(gordo, ["warmup", "--dir", model_dir])
    assert res.exit_code == 0, res.output
    assert "serve.fleet/full" in res.output
    assert "error(s)" in res.output


def test_gordo_warmup_dir_cli_fails_on_compile_error(model_dir, monkeypatch):
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import gordo

    def broken(collection, row_sizes=None, manifest=None):
        return {"buckets": 0, "fallbacks": 0, "errors": 2, "programs": [],
                "compile_seconds": 0.0}

    monkeypatch.setattr("gordo_tpu.compile.warmup_collection", broken)
    res = CliRunner().invoke(gordo, ["warmup", "--dir", model_dir])
    assert res.exit_code == 1


def test_gordo_warmup_requires_exactly_one_target():
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import gordo

    assert CliRunner().invoke(gordo, ["warmup"]).exit_code != 0
    assert CliRunner().invoke(
        gordo, ["warmup", "--dir", "x", "--url", "http://y"]
    ).exit_code != 0


# ---------------------------------------------------------------------------
# degradations are counted, not just logged (ISSUE 21 §2)
# ---------------------------------------------------------------------------

def test_aot_compile_failure_is_counted_and_still_answers(monkeypatch):
    """AOT lower/compile refused → the program keeps answering through
    plain jit (production behaviour), and the process-wide count, the
    per-program counter and /healthz's ``aot_fallbacks`` say so."""
    import jax.numpy as jnp

    prog = compile_plane.Program("test.aot_refused", lambda x: x * 2.0)

    def refuse(*a, **k):
        raise RuntimeError("backend refuses AOT")

    monkeypatch.setattr(prog._jitted, "lower", refuse, raising=False)
    before = compile_plane.aot_fallbacks()
    counter = telemetry.REGISTRY.get("gordo_compile_aot_fallbacks_total")
    out = prog(jnp.ones((4,), jnp.float32))
    assert np.allclose(np.asarray(out), 2.0)
    assert compile_plane.aot_fallbacks() == before + 1
    assert counter.value("test.aot_refused") == 1.0
    # one loud failure, then jit-only: not re-counted per call
    prog(jnp.ones((4,), jnp.float32))
    assert compile_plane.aot_fallbacks() == before + 1


def test_failed_fleet_program_demotes_loudly(tmp_path, monkeypatch):
    """A fleet program that fails to dispatch still builds every machine
    (through the single-machine builder) — and the summary carries the
    count and the reason, so a 512-wide program silently becoming 512
    small ones cannot pass for a healthy build."""
    from gordo_tpu.builder import fleet_build as fb

    def refuse(self, *a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM\nmore detail")

    monkeypatch.setattr(fb.FleetDiffBuilder, "dispatch", refuse)
    cfg = NormalizedConfig(PROJECT, "demoteproj")
    result = build_project(cfg.machines, str(tmp_path / "out"))
    summary = result.summary()
    assert not result.failed
    assert summary["fleet_built"] == 0 and summary["single_built"] == 3
    assert summary["demoted"] == {
        "machines": 3,
        "reasons": ["dispatch: RuntimeError: RESOURCE_EXHAUSTED: out of HBM"],
    }
    assert summary["device"]["platform"] == "cpu"
    assert summary["device"]["used"] == 0  # no fleet program ever ran


def test_healthy_build_summary_names_its_device(model_dir):
    cfg = NormalizedConfig(PROJECT, "cpproj")
    result = build_project(cfg.machines, model_dir + "-again")
    summary = result.summary()
    assert summary["demoted"] == {"machines": 0, "reasons": []}
    assert summary["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 8, "used": 1,
    }


def test_model_parallel_that_cannot_shard_is_an_error(model_dir):
    """One device to shard over is a refusal, like FleetMesh.resolve's
    over-ask — never a quiet single-device server."""
    from gordo_tpu.serve.server import run_server

    with pytest.raises(ValueError, match="--model-parallel needs more than"):
        run_server(model_dir, model_parallel=True, mesh_devices="1")


def test_compile_listeners_account_every_jit():
    """jax's own compile events land on the plane's series even for
    programs that never pass through a Program (the fleet build path)."""
    import jax
    import jax.numpy as jnp

    compile_plane.install_compile_listeners()
    counter = telemetry.REGISTRY.get("gordo_compile_jax_seconds_total")
    before = counter.value("backend")
    jax.jit(lambda x: jnp.tanh(x) + 21.0)(jnp.ones((7,))).block_until_ready()
    assert counter.value("backend") > before


# ---------------------------------------------------------------------------
# persistent-cache reuse across a forked-process restart (slow lane)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys, time
import jax, jax.numpy as jnp
from gordo_tpu.utils.compile_cache import enable_persistent_compile_cache
from gordo_tpu import compile as compile_plane, telemetry

assert enable_persistent_compile_cache(), "cache must engage under force"

def f(x):
    return jnp.tanh(x @ x.T).sum()

prog = compile_plane.Program("test.persist", f)
t0 = time.perf_counter()
prog.warm(jax.ShapeDtypeStruct((64, 64), jnp.float32))
dt = time.perf_counter() - t0
hits = misses = 0
for line in telemetry.render().splitlines():
    if line.startswith('gordo_compile_cache_hits_total{cache="persistent"}'):
        hits = float(line.rsplit(" ", 1)[1])
    if line.startswith('gordo_compile_cache_misses_total{cache="persistent"}'):
        misses = float(line.rsplit(" ", 1)[1])
print(json.dumps({"compile_s": dt, "hits": hits, "misses": misses}))
"""


@pytest.mark.slow
def test_persistent_cache_reused_across_forked_restart(tmp_path):
    """Two fresh processes sharing JAX_COMPILATION_CACHE_DIR: the first
    populates the on-disk cache (a persistent miss), the restart loads
    the executable from disk (a persistent hit, attested by the
    compile-plane counters) — the forked-worker / server-restart reuse
    path of ISSUE 5, in miniature."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # force: CPU is excluded by default (AOT feature-mismatch hazard);
        # back-to-back children on one machine are the trusted case
        "GORDO_COMPILE_CACHE": "force",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
        "GORDO_COMPILE_CACHE_MIN_SECONDS": "0",
    })

    def run():
        res = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=180,
        )
        assert res.returncode == 0, res.stderr[-2000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    first = run()
    assert first["misses"] >= 1  # populated the disk cache
    restart = run()
    assert restart["hits"] >= 1, restart  # the restart loaded from disk
    assert os.listdir(str(tmp_path / "xla"))  # entries actually on disk
