"""Driver benchmark: full fleet build throughput on the available chip(s).

Measures (names track BASELINE.json measurement configs):

- config 4 headline: per-tag anomaly-detector builds/hour/chip — the
  COMPLETE build path (synthetic time-series assembly, scaler stats, CV
  folds, threshold derivation, final fit, artifact dump) via
  ``build_project``.
- config 2: the same build rate for ``lstm_hourglass`` machines (50 tags,
  windowed sequences) plus the LSTM serving rate.
- config 5 serving: end-to-end HTTP throughput under a replayed
  multi-machine sensor stream (real aiohttp server + TCP + codec), single
  and bulk routes, JSON and msgpack wire formats — reported separately, no
  ``max()`` hiding.  In-process scorer rates are kept alongside under
  ``*_inprocess`` names.
- FLOP accounting: analytic training FLOPs per build (see
  ``docs/perf.md``) → ``effective_tflops`` + ``mfu_estimate`` against the
  v5e bf16 peak, so the headline can't silently claim a busy chip.

Prints exactly ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``

``vs_baseline`` is measured models/hour/chip divided by the north-star
per-chip rate (10,000 models/h on 64 chips = 156.25 models/h/chip).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

#: north star: 10k models < 1h on v5e-64 → per-chip rate to match.
NORTH_STAR_MODELS_PER_HOUR_PER_CHIP = 10_000 / 64
NORTH_STAR_SAMPLES_PER_SEC_PER_CHIP = 100_000
#: peak bf16 matmul FLOP/s per chip, keyed by ``jax.Device.device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s).  The fp32 programs
#: here can at best reach a fraction of it — the point of the MFU field is
#: honesty, not flattery.  A device that is not in the table is an error,
#: not a default: a CPU rate is never divided by a TPU peak.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
}

N_MACHINES = int(os.environ.get("BENCH_MODELS", "512"))
N_TAGS = int(os.environ.get("BENCH_TAGS", "10"))
N_LSTM_MACHINES = int(os.environ.get("BENCH_LSTM_MODELS", "64"))
N_LSTM_TAGS = int(os.environ.get("BENCH_LSTM_TAGS", "50"))
LSTM_LOOKBACK = int(os.environ.get("BENCH_LSTM_LOOKBACK", "12"))

#: stages that measure through forked children pinned to JAX_PLATFORMS=cpu
#: (servers, cold-start and scale-out workers).  One process owns the chip:
#: with this parent on an accelerator those children would publish CPU
#: numbers under an accelerator headline — and unpinned they would fight
#: the parent for the chip.  main() refuses them until they are rewritten
#: in-process (ROADMAP Speed 1); ``serve.replay.replay_bench`` is the
#: in-process pattern.  The stage functions stay callable directly on CPU.
CHILD_PROCESS_STAGES = frozenset({
    "serving_sharded", "cold_start", "multi_device", "backfill",
    "scores_lifecycle", "serving_wire",
})


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_emit_lock = threading.Lock()
_emitted = False


def emit_once(out: dict) -> None:
    """Print the single JSON result line exactly once; only marks emitted
    after the print actually succeeded, so a serialization hiccup can't
    permanently swallow the output line."""
    try:
        line = json.dumps(dict(out))
    except Exception as exc:
        line = json.dumps(
            {"metric": "bench", "value": None, "error": f"emit: {exc}"}
        )
    emit_line(line)


def emit_line(line: str) -> None:
    """Print a pre-serialized result line through the emit-once gate."""
    global _emitted
    with _emit_lock:
        if _emitted:
            return
        print(line, flush=True)
        _emitted = True
    try:
        persist_round(json.loads(line))
    except Exception as exc:  # non-JSON line: nothing to persist
        log(f"persist_round skipped (unparseable line): {exc!r}")


_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

#: round number for BENCH_rNN.json persistence (``--round N`` /
#: ``BENCH_ROUND``); None = don't write a round artifact
_ROUND: "int | None" = None
_round_write_failed = False


def persist_round(doc: dict) -> None:
    """Write the emitted result doc to ``BENCH_rNN.json`` in the repo dir.

    Round-file convention (docs/perf.md "Bench round artifacts"): NN is
    the PR/round sequence number; the file carries the single JSON line
    bench.py emitted for that round, so later rounds can be diffed
    without re-running anything.  Written atomically (tmp + rename) —
    the r6 lesson: the round file was referenced from CHANGES.md but a
    plain interrupted write meant it never landed.  Failures are LOUD:
    logged, flagged in the doc, and the process exits nonzero
    (:func:`exit_code`) instead of silently dropping the artifact.
    """
    global _round_write_failed
    if _ROUND is None:
        return
    path = os.path.join(_REPO_DIR, f"BENCH_r{_ROUND:02d}.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.write("\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        log(f"round artifact written: {path}")
    except Exception as exc:
        _round_write_failed = True
        log(f"ERROR: round artifact write FAILED for {path}: {exc!r}")
        try:
            os.unlink(tmp)
        except OSError:
            pass


#: stages that raised during this run (name -> first line of the error)
_failed_stages: "dict[str, str]" = {}


def exit_code() -> int:
    """Non-zero when a stage raised or a requested round artifact failed
    to persist."""
    return 1 if (_failed_stages or _round_write_failed) else 0


LSTM_MODEL = {
    "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.pipeline.Pipeline": {
                "steps": [
                    "gordo_tpu.ops.scalers.MinMaxScaler",
                    {
                        "gordo_tpu.models.estimator.LSTMAutoEncoder": {
                            "kind": "lstm_hourglass",
                            "lookback_window": LSTM_LOOKBACK,
                            "epochs": 10,
                            "batch_size": 64,
                        }
                    },
                ]
            }
        }
    }
}


def make_machines(n: int, n_tags: int = N_TAGS, model: dict | None = None,
                  prefix: str = "bench-machine"):
    from gordo_tpu.workflow.config import Machine

    # 4 days @ 10-min resolution ≈ 576 rows/machine, sine-mixture tags.
    return [
        Machine.from_config(
            {
                "name": f"{prefix}-{i:04d}",
                "dataset": {
                    "type": "RandomDataset",
                    "tag_list": [f"tag-{i:04d}-{j}" for j in range(n_tags)],
                },
                **({"model": model} if model else {}),
            }
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# FLOP accounting (see docs/perf.md for the derivation and caveats)
# ---------------------------------------------------------------------------

def _kernel_params(model) -> int:
    """Weight-matrix parameters of a built detector's network (ndim>=2
    leaves: dense/recurrent kernels; biases/scales excluded)."""
    import jax

    est = model.base_estimator
    if hasattr(est, "steps"):  # Pipeline
        est = est.steps[-1][1]
    return sum(
        x.size for x in jax.tree.leaves(est.params_)
        if getattr(x, "ndim", 0) >= 2
    )


def _train_flops_per_model(
    kernel_params: int, n_rows: int, epochs: int = 10, n_splits: int = 3,
    seq_steps: int = 1,
) -> float:
    """6 * kernel_params * trained_samples: the standard fwd(2)+bwd(4)
    dense-matmul estimate.  CV trains expanding folds (n/(k+1) * (1+..+k)
    rows) then the final fit trains all n rows; recurrent nets multiply by
    the steps each window unrolls (``seq_steps``)."""
    cv_rows = n_rows / (n_splits + 1) * (n_splits * (n_splits + 1) / 2)
    trained = (cv_rows + n_rows) * epochs * seq_steps
    return 6.0 * kernel_params * trained


# ---------------------------------------------------------------------------
# build benches
# ---------------------------------------------------------------------------

def _timed_build_runs(machines, mesh, label: str):
    """Two identical project builds (run 0 compiles, run 1 is the
    steady-state measurement); returns (rates, first artifact's model)."""
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import build_project

    rates = []
    model = None
    for run in range(2):
        out_dir = tempfile.mkdtemp(prefix=f"gordo-bench-{label}-")
        t0 = time.perf_counter()
        result = build_project(
            machines, out_dir, mesh=mesh, max_bucket_size=len(machines)
        )
        dt = time.perf_counter() - t0
        n_ok = len(result.artifacts)
        if run == 1 and n_ok:
            model = serializer.load(
                result.artifacts[sorted(result.artifacts)[0]]
            )
        shutil.rmtree(out_dir, ignore_errors=True)
        if result.failed:
            log(f"WARNING ({label}): {len(result.failed)} builds failed: "
                f"{dict(list(result.failed.items())[:3])}")
        if n_ok == 0:
            raise RuntimeError(f"All {label} builds failed")
        rates.append(n_ok / dt * 3600.0)
        log(f"{label} build run {run}: {n_ok} machines in {dt:.2f}s "
            f"({rates[-1]:.0f} models/h)")
    return rates, model


def _flop_fields(out: dict, prefix: str, model, models_per_hour: float,
                 seq_steps: int = 1) -> None:
    """Per-chip FLOP-rate + MFU fields (rates arrive fleet-wide; MFU is
    against ONE chip's peak, so divide by n_chips first)."""
    kp = _kernel_params(model)
    flops = _train_flops_per_model(kp, n_rows=576, seq_steps=seq_steps)
    per_chip_rate = models_per_hour / 3600.0 / out.get("n_chips", 1)
    out[f"{prefix}_kernel_params_per_model"] = kp
    out[f"{prefix}_tflops_per_model"] = round(flops / 1e12, 9)
    out[f"{prefix}_effective_tflops_per_chip"] = round(
        flops * per_chip_rate / 1e12, 6
    )
    kind = out["device_kind"]
    if kind not in PEAK_FLOPS_BY_DEVICE_KIND:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
            "PEAK_FLOPS_BY_DEVICE_KIND with its source before quoting an MFU"
        )
    out[f"{prefix}_mfu_estimate"] = round(
        flops * per_chip_rate / PEAK_FLOPS_BY_DEVICE_KIND[kind], 8
    )


def bench_build(mesh, out: dict) -> float:
    """Steady-state project-build rate in models/hour (in-process jit cache
    warm: run once to compile, time the second identical-shape run)."""
    rates, model = _timed_build_runs(make_machines(N_MACHINES), mesh, "ff")
    if model is not None:
        _flop_fields(out, "build", model, rates[-1])
    return rates[-1]


def bench_build_throughput(mesh, out: dict) -> None:
    """r23 acceptance: the dispatch/collect split of the build plane.

    One warmup run lands the compiles, then 4 rounds with the BEST
    standing — timing noise on a shared container is one-sided
    contamination (a background burst can add 30% to a single run,
    nothing can make one faster than the true floor), so min() estimates
    the uncontaminated time.  Per-stage attribution comes from the
    pipeline stage histogram deltas around the best round — dispatch
    (host-side launch), device (dispatch→collect wall), fetch (blocking
    D2H), assemble (per-machine detector unpacking), write, load — plus
    the ``gordo_build_device_idle_seconds`` occupancy counter, so the
    remaining between-chunk gaps are measurable instead of inferred.
    """
    from gordo_tpu import telemetry
    from gordo_tpu.builder.fleet_build import build_project

    def stage_sums() -> dict:
        metric = telemetry.REGISTRY.snapshot()["metrics"].get(
            "gordo_build_pipeline_stage_seconds"
        ) or {}
        sums = {}
        for key, v in metric.get("series", {}).items():
            sums[json.loads(key)[0]] = float(v["sum"])
        return sums

    def timed(machines, bucket, label):
        out_dir = tempfile.mkdtemp(prefix=f"gordo-bench-bt-{label}-")
        before = stage_sums()
        t0 = time.perf_counter()
        result = build_project(
            machines, out_dir, mesh=mesh, max_bucket_size=bucket,
        )
        dt = time.perf_counter() - t0
        after = stage_sums()
        shutil.rmtree(out_dir, ignore_errors=True)
        if result.failed or len(result.artifacts) != len(machines):
            raise RuntimeError(
                f"build_throughput {label}@{len(machines)}: "
                f"{len(result.failed)} failed"
            )
        stages = {
            k: round(after.get(k, 0.0) - before.get(k, 0.0), 4)
            for k in sorted(set(after) | set(before))
        }
        return dt, stages, result.device_idle_seconds

    n_machines, bucket = 512, 64
    machines = make_machines(n_machines, prefix=f"bench-bt{n_machines}")
    timed(machines, bucket, "warmup")  # land the compiles
    times = []
    stage_attr = idle = None
    for rnd in range(4):
        dt, stages, idle_s = timed(machines, bucket, "async")
        if not times or dt < min(times):
            stage_attr = stages  # attribution of the BEST round
            idle = round(idle_s, 4)
        times.append(dt)
        log(f"build_throughput async@{n_machines} round {rnd}: "
            f"{dt:.2f}s ({n_machines / dt * 3600.0:.0f} models/h)")
    out[f"build_throughput_async_models_per_hour_{n_machines}"] = (
        round(n_machines / min(times) * 3600.0, 1)
    )
    out["build_throughput_stage_seconds_async"] = stage_attr
    out["build_throughput_device_idle_seconds_async"] = idle


def bench_lstm_build(mesh, out: dict) -> None:
    """BASELINE config 2: lstm_hourglass on 50-tag windowed sequences —
    the scenario where scan latency and MXU under-utilization bite."""
    from gordo_tpu.serve.scorer import CompiledScorer

    machines = make_machines(
        N_LSTM_MACHINES, n_tags=N_LSTM_TAGS, model=LSTM_MODEL,
        prefix="bench-lstm",
    )
    rates, model = _timed_build_runs(machines, mesh, "lstm")
    n_chips = out.get("n_chips", 1)
    out["lstm_models_per_hour_per_chip"] = round(rates[-1] / n_chips, 1)
    out["lstm_vs_baseline"] = round(
        rates[-1] / n_chips / NORTH_STAR_MODELS_PER_HOUR_PER_CHIP, 3
    )
    if model is not None:
        _flop_fields(out, "lstm", model, rates[-1], seq_steps=LSTM_LOOKBACK)

        # LSTM serving rate (in-process fused scorer)
        scorer = CompiledScorer(model)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((4096, N_LSTM_TAGS)).astype(np.float32)
        scorer.anomaly_arrays(X, None)  # compile
        n_iter, t0 = 10, time.perf_counter()
        for _ in range(n_iter):
            scorer.anomaly_arrays(X, None)
        lstm_serving = n_iter * X.size / (time.perf_counter() - t0)
        out["lstm_serving_samples_per_sec_inprocess"] = round(lstm_serving)
        log(f"lstm serving (in-process): {lstm_serving:,.0f} samples/s")


# ---------------------------------------------------------------------------
# serving benches
# ---------------------------------------------------------------------------

def _build_serving_model():
    """One built bench machine's (model, metadata) — the serving stages'
    shared prototype."""
    from gordo_tpu.builder.build_model import build_model

    machine = make_machines(1)[0]
    return build_model(
        machine.name, machine.model, machine.dataset, {}, machine.evaluation
    )


def _serving_collection(art_dir: str, model, metadata, n_machines: int = 64):
    """A 64-machine ModelCollection over one artifact dir: each entry loads
    its own params copy, exactly like a 64-machine project (the device
    can't tell values are equal; the stacked program shape is identical)."""
    from gordo_tpu.serve.server import ModelCollection, ModelEntry
    from gordo_tpu import serializer

    art = os.path.join(art_dir, "m-000")
    serializer.dump(model, art, metadata=metadata)
    entries = {
        f"m-{i:03d}": ModelEntry(f"m-{i:03d}", art)
        for i in range(n_machines)
    }
    return ModelCollection(entries, project="bench")


def bench_serving(out: dict) -> None:
    """Config 5.  In-process scorer rates AND end-to-end HTTP replay —
    single + bulk, JSON + msgpack — reported as separate fields."""
    from gordo_tpu.serve.fleet_scorer import FleetScorer
    from gordo_tpu.serve.scorer import CompiledScorer
    from gordo_tpu.serve.replay import replay_bench

    model, metadata = _build_serving_model()
    rng = np.random.default_rng(0)

    # -- in-process (codec-free ceiling) ------------------------------------
    scorer = CompiledScorer(model)
    X = rng.standard_normal((8192, N_TAGS)).astype(np.float32)
    scorer.anomaly_arrays(X, None)  # compile
    n_iter, t0 = 20, time.perf_counter()
    for _ in range(n_iter):
        scorer.anomaly_arrays(X, None)
    single = n_iter * X.size / (time.perf_counter() - t0)
    out["serving_samples_per_sec_inprocess"] = round(single)
    log(f"serving in-process single: {single:,.0f} samples/s")

    n_machines = 64
    fleet = FleetScorer.from_models(
        {f"m-{i:03d}": model for i in range(n_machines)}
    )
    X_by = {
        f"m-{i:03d}": rng.standard_normal((2048, N_TAGS)).astype(np.float32)
        for i in range(n_machines)
    }
    fleet.score_all(X_by)  # compile
    n_iter, t0 = 10, time.perf_counter()
    for _ in range(n_iter):
        fleet.score_all(X_by)
    stacked = n_iter * n_machines * 2048 * N_TAGS / (time.perf_counter() - t0)
    out["serving_samples_per_sec_inprocess_stacked"] = round(stacked)
    log(f"serving in-process stacked ({n_machines} machines): "
        f"{stacked:,.0f} samples/s")

    # -- HTTP replayed stream (the number that matters) ---------------------
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-serve-")
    try:
        collection = _serving_collection(
            art_dir, model, metadata, n_machines
        )

        http = {}
        for mode, wire, rounds, coalesce_ms, par in (
            ("bulk", "json", 5, 0.0, 8),
            ("bulk", "msgpack", 5, 0.0, 8),
            # coalesced-vs-not at three concurrencies (r4 verdict item 4):
            # the adaptive policy must make coalescing >= direct everywhere
            # (or stand down to it).  5 rounds per paired point: at 3 the
            # pair's delta was inside run-to-run noise (±3%) and flipped
            # sign between runs.
            ("single", "json", 3, 0.0, 1),
            ("single", "json", 3, 2.0, 1),
            ("single", "json", 5, 0.0, 8),
            ("single", "json", 5, 2.0, 8),
            ("single", "json", 5, 0.0, 64),
            ("single", "json", 5, 2.0, 64),
        ):
            # paired (direct-vs-coalesced) points run best-of-2: single
            # runs on a shared CPU drift ±10% between adjacent runs, which
            # is larger than the effect under test at low concurrency.
            # Applied symmetrically to both sides of every pair.
            n_attempts = 2 if mode == "single" else 1
            res = None
            for _ in range(n_attempts):
                attempt = replay_bench(
                    collection, mode=mode, wire=wire, n_rounds=rounds,
                    rows=2048, parallelism=par,
                    coalesce_window_ms=coalesce_ms,
                )
                if res is None or (
                    attempt["samples_per_sec"] > res["samples_per_sec"]
                ):
                    res = attempt
            key = f"serving_samples_per_sec_http_{mode}_{wire}"
            if coalesce_ms:
                key += "_coalesced"
            if par != 8:  # 8-way keeps the r3/r4-compatible unsuffixed key
                key += f"_p{par}"
            out[key] = round(res["samples_per_sec"])
            out[key.replace("samples_per_sec", "latency_p50_ms")] = round(
                res["latency_p50_ms"], 2
            )
            if res["latency_n"] >= 20:
                # fewer samples (bulk: one request/round) would record a
                # near-max masquerading as a tail percentile
                out[key.replace("samples_per_sec", "latency_p99_ms")] = round(
                    res["latency_p99_ms"], 2
                )
            http[(mode, wire, bool(coalesce_ms), par)] = res["samples_per_sec"]
            co = res.get("coalescer") or {}
            if co:
                # attest how the adaptive policy behaved in the measured
                # window: "knee_no_gain + 0 dispatches" IS the evidence
                # that the combined path routed direct where batching
                # can't pay (acceptance: never worse than direct)
                out[key + "_coalescer"] = {
                    k: co.get(k)
                    for k in (
                        "dispatches", "requests", "bypassed_requests",
                        "mean_batch", "batch_cap", "knee_estimated",
                        "knee_no_gain", "queue_full_bypassed", "standdowns",
                    )
                }
            co_note = (
                f", batch {co['mean_batch']} cap {co['batch_cap']} "
                f"standdowns {co['standdowns']}"
                if co.get("dispatches") else ""
            )
            log(f"serving HTTP {mode}/{wire} x{par}"
                f"{' +coalesce' if coalesce_ms else ''}: "
                f"{res['samples_per_sec']:,.0f} samples/s "
                f"({res['response_mb_per_sec']:.1f} MB/s responses, "
                f"p50 {res['latency_p50_ms']:.0f}ms / "
                f"p99 {res['latency_p99_ms']:.0f}ms{co_note})")
        # headline serving number = HTTP bulk over the production wire
        out["serving_samples_per_sec"] = round(
            http[("bulk", "msgpack", False, 8)]
        )
        out["serving_devices"] = 1
        out["serving_vs_target"] = round(
            http[("bulk", "msgpack", False, 8)]
            / NORTH_STAR_SAMPLES_PER_SEC_PER_CHIP,
            3,
        )
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_serving_openloop(out: dict) -> None:
    """Open-loop (fixed-arrival-rate) latency points — the percentiles an
    SLO would actually use, vs the closed-loop saturation artifacts the
    ``serving`` stage reports.  Protocol per route: measure saturation
    closed-loop, then p50/p99 at 0.5× and 0.8× of it
    (``serve.replay.openloop_bench``)."""
    from gordo_tpu.serve.replay import openloop_bench

    model, metadata = _build_serving_model()
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-openloop-")
    try:
        collection = _serving_collection(art_dir, model, metadata, 64)
        for mode, wire, coalesce_ms, par in (
            # the production bulk wire (acceptance: p99_at_* for msgpack
            # bulk), then the coalescer's route direct vs coalesced
            ("bulk", "msgpack", 0.0, 8),
            ("single", "json", 0.0, 32),
            ("single", "json", 2.0, 32),
        ):
            res = openloop_bench(
                collection, mode=mode, wire=wire, rows=2048,
                parallelism=par, sat_rounds=2, duration_s=4.0,
                coalesce_window_ms=coalesce_ms,
            )
            base = f"serving_openloop_{mode}_{wire}"
            if coalesce_ms:
                base += "_coalesced"
            out[base + "_saturation_rps"] = round(
                res["saturation_requests_per_sec"], 2
            )
            for frac, p in res["points"].items():
                out[f"{base}_p50_at_{frac}_ms"] = round(
                    p["latency_p50_ms"], 2
                )
                out[f"{base}_p99_at_{frac}_ms"] = round(
                    p["latency_p99_ms"], 2
                )
                out[f"{base}_latency_n_at_{frac}"] = p["latency_n"]
            log(
                f"openloop {mode}/{wire}"
                f"{' +coalesce' if coalesce_ms else ''}: sat "
                f"{res['saturation_requests_per_sec']:.1f} req/s; "
                + "; ".join(
                    f"{frac}: p50 {p['latency_p50_ms']:.0f}ms / "
                    f"p99 {p['latency_p99_ms']:.0f}ms (n={p['latency_n']})"
                    for frac, p in res["points"].items()
                )
            )
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_serving_precision(out: dict) -> None:
    """ISSUE 7 acceptance: the fused single-dispatch request path vs the
    r11 host-side path, and the serving-precision (dtype) sweep.

    Protocol (docs/perf.md "Serving precision"):

    - in-process fp32-vs-bf16 parity re-attestation (max-normalized
      per-series error; bounds match tests/test_serving_precision.py)
      and the single-dispatch attestation: N requests must move the
      dispatch/transfer counters by exactly N;
    - per dtype (fp32, bf16): p50/p99 + throughput over the
      single-machine JSON route at 1/8/64-way closed loop (fresh
      collection per dtype — buckets restack at the storage dtype);
    - fused vs host (GORDO_SERVE_FUSED=off — the r11 request path with
      concatenate/tile padding and the host confidence divide) at
      64-way fp32, interleaved best-of-2 per side.  Gate: fused p50
      strictly below host p50 in the same run.

    CPU XLA emulates bf16, so bf16 *throughput parity* is the expected
    CPU result (the bf16 win is a TPU lever); the CPU win under test
    here is the fused path vs r11's host-side work.
    """
    from gordo_tpu import telemetry
    from gordo_tpu.serve.replay import replay_bench
    from gordo_tpu.serve.scorer import CompiledScorer

    model, metadata = _build_serving_model()
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-prec-")
    knobs = ("GORDO_SERVE_DTYPE", "GORDO_SERVE_FUSED", "GORDO_SERVE_INT8")
    saved = {k: os.environ.get(k) for k in knobs}

    def setenv(key: str, value: "str | None") -> None:
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value

    def counter(name: str) -> float:
        metric = telemetry.REGISTRY.snapshot()["metrics"].get(name) or {}
        return float(sum(metric.get("series", {}).values()))

    try:
        rng = np.random.default_rng(0)
        X = rng.standard_normal((2048, N_TAGS)).astype(np.float32)

        # -- parity re-attestation (in-process, per-series bounds) ----------
        ref_scorer = CompiledScorer(model, dtype="float32")
        ref = ref_scorer.anomaly_arrays(X)
        bf = CompiledScorer(model, dtype="bfloat16").anomaly_arrays(X)
        bounds = {
            "model-output": 0.03,
            "total-anomaly-score": 0.10,
            "anomaly-confidence": 0.10,
        }
        errs, parity_ok = {}, True
        for key, tol in bounds.items():
            r = np.asarray(ref[key], np.float32)
            q = np.asarray(bf[key], np.float32)
            scale = max(float(np.max(np.abs(r))), 1e-6)
            err = float(np.max(np.abs(r - q))) / scale
            errs[key] = round(err, 6)
            parity_ok = parity_ok and err <= tol
        out["serving_precision_bf16_max_norm_err"] = errs
        out["serving_precision_bf16_parity_ok"] = bool(parity_ok)
        log(f"serving_precision bf16 parity: {errs} -> "
            f"{'OK' if parity_ok else 'FAIL'}")

        # -- single-dispatch attestation ------------------------------------
        n_att = 20
        d0 = counter("gordo_serve_dispatches_total")
        t0 = counter("gordo_serve_input_transfers_total")
        for _ in range(n_att):
            ref_scorer.anomaly_arrays(X)
        dd = counter("gordo_serve_dispatches_total") - d0
        td = counter("gordo_serve_input_transfers_total") - t0
        out["serving_precision_requests_attested"] = n_att
        out["serving_precision_dispatches_measured"] = dd
        out["serving_precision_one_dispatch_per_request"] = (
            dd == n_att and td == n_att
        )
        log(f"serving_precision dispatch attestation: {dd:.0f} dispatches / "
            f"{td:.0f} transfers for {n_att} requests")

        # -- per-dtype HTTP sweep at 1/8/64-way -----------------------------
        for dtype_name, env_value in (("float32", None), ("bfloat16", "bf16")):
            setenv("GORDO_SERVE_DTYPE", env_value)
            collection = _serving_collection(art_dir, model, metadata, 64)
            for par, rounds in ((1, 3), (8, 4), (64, 4)):
                res = replay_bench(
                    collection, mode="single", wire="json",
                    n_rounds=rounds, rows=2048, parallelism=par,
                )
                key = f"serving_precision_{dtype_name}"
                out[f"{key}_samples_per_sec_p{par}"] = round(
                    res["samples_per_sec"]
                )
                out[f"{key}_p50_ms_p{par}"] = round(res["latency_p50_ms"], 2)
                if res["latency_n"] >= 20:
                    out[f"{key}_p99_ms_p{par}"] = round(
                        res["latency_p99_ms"], 2
                    )
                log(f"serving_precision {dtype_name} x{par}: "
                    f"{res['samples_per_sec']:,.0f} samples/s, "
                    f"p50 {res['latency_p50_ms']:.1f}ms / "
                    f"p99 {res['latency_p99_ms']:.1f}ms")
        setenv("GORDO_SERVE_DTYPE", None)

        # -- fused vs r11 host path, 64-way fp32, interleaved best-of-2 -----
        collection = _serving_collection(art_dir, model, metadata, 64)
        best: dict = {"host": None, "fused": None}
        for _ in range(2):
            for label, fused_env in (("host", "off"), ("fused", None)):
                setenv("GORDO_SERVE_FUSED", fused_env)
                res = replay_bench(
                    collection, mode="single", wire="json",
                    n_rounds=4, rows=2048, parallelism=64,
                )
                point = {
                    "p50": res["latency_p50_ms"],
                    "p99": res["latency_p99_ms"],
                    "sps": res["samples_per_sec"],
                }
                if best[label] is None or point["p50"] < best[label]["p50"]:
                    best[label] = point
                log(f"serving_precision {label} x64: "
                    f"p50 {point['p50']:.1f}ms, {point['sps']:,.0f} samples/s")
        setenv("GORDO_SERVE_FUSED", None)
        out["serving_precision_host_p50_ms_64"] = round(
            best["host"]["p50"], 2
        )
        out["serving_precision_fused_p50_ms_64"] = round(
            best["fused"]["p50"], 2
        )
        out["serving_precision_host_p99_ms_64"] = round(
            best["host"]["p99"], 2
        )
        out["serving_precision_fused_p99_ms_64"] = round(
            best["fused"]["p99"], 2
        )
        out["serving_precision_fused_samples_per_sec_64"] = round(
            best["fused"]["sps"]
        )
        out["serving_precision_host_samples_per_sec_64"] = round(
            best["host"]["sps"]
        )
        # the acceptance gate: the fused single-dispatch path beats the
        # r11 host-side path on CPU p50 at 64-way, same run
        out["serving_precision_fused_beats_host_p50_64"] = (
            best["fused"]["p50"] < best["host"]["p50"]
        )
        log(f"serving_precision fused vs host p50 @64: "
            f"{best['fused']['p50']:.1f}ms vs {best['host']['p50']:.1f}ms "
            f"({'PASS' if best['fused']['p50'] < best['host']['p50'] else 'FAIL'})")
    finally:
        for key, value in saved.items():
            setenv(key, value)
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_telemetry_overhead(out: dict) -> None:
    """Acceptance gate for the telemetry plane: the instrumented msgpack
    bulk path (request middleware + histograms + spans live) must cost
    <= 2% throughput vs the ``GORDO_TELEMETRY=off`` kill switch.

    Protocol (r9 fix): BENCH_r08 recorded a −16.83% "overhead" — the
    instrumented side measured FASTER than the kill switch, i.e. pure
    noise — because each side reported a best-of-3 with no warmup and
    the two sides ran as sequential blocks, so minutes of machine drift
    (plus lucky cold-cache draws) decided the sign.  Now: one unrecorded
    WARMUP round per side (aiohttp connection pool, codec and jit caches
    hot), then 3 recorded samples per side taken INTERLEAVED
    (on, off, on, off, ...) so drift lands on both sides equally, and
    the gate compares per-side MEDIANS — best-of rewards outliers, the
    median ignores them.  The per-side sample lists land in the doc so
    the spread is attestable next to the verdict.
    """
    from gordo_tpu import telemetry
    from gordo_tpu.serve.replay import replay_bench

    model, metadata = _build_serving_model()
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-telemetry-")
    try:
        collection = _serving_collection(art_dir, model, metadata, 64)

        def sample(n_rounds: int = 5) -> dict:
            return replay_bench(
                collection, mode="bulk", wire="msgpack", n_rounds=n_rounds,
                rows=2048, parallelism=8,
            )

        results = {True: [], False: []}
        for i in range(3):
            for enabled in (True, False):
                telemetry.set_enabled(enabled)
                try:
                    if i == 0:
                        sample(n_rounds=2)  # per-side warmup, discarded
                    results[enabled].append(sample())
                finally:
                    telemetry.set_enabled(True)

        def median(rs: "list[dict]") -> "tuple[dict, list[float]]":
            rs = sorted(rs, key=lambda r: r["samples_per_sec"])
            return rs[len(rs) // 2], [r["samples_per_sec"] for r in rs]

        on, on_samples = median(results[True])
        off, off_samples = median(results[False])
        overhead_pct = 100.0 * (
            1.0 - on["samples_per_sec"] / off["samples_per_sec"]
        )
        out["telemetry_on_samples_per_sec"] = round(on["samples_per_sec"])
        out["telemetry_off_samples_per_sec"] = round(off["samples_per_sec"])
        out["telemetry_on_samples"] = [round(v) for v in on_samples]
        out["telemetry_off_samples"] = [round(v) for v in off_samples]
        # negative = instrumented median still faster: residual noise
        # floor, now bounded by the median instead of amplified by max()
        out["telemetry_overhead_pct"] = round(overhead_pct, 2)
        out["telemetry_overhead_ok"] = overhead_pct <= 2.0
        # the in-run scrape attests /metrics served valid text under load
        out["telemetry_scrape"] = on.get("metrics_scrape")
        log(
            f"telemetry overhead (msgpack bulk, interleaved median of 3): "
            f"on {on['samples_per_sec']:,.0f} vs off "
            f"{off['samples_per_sec']:,.0f} samples/s -> "
            f"{overhead_pct:+.2f}% (gate: <= 2%)"
        )
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_health_overhead(out: dict) -> None:
    """ISSUE 9 acceptance: the fleet-health plane's per-response score
    sketching must stay within the existing <= 2% telemetry budget on
    the 64-way bulk serving path, and a 2-shard fleet's merged health
    doc must be byte-equivalent to the single-process one for the same
    request stream.

    Protocol: one unrecorded warmup round per side, then 5 ADJACENT
    on/off pairs with the gate on the MEDIAN of pairwise overheads —
    a tightening of telemetry_overhead's r9 interleaving: on this
    shared-box class of machine the per-sample spread is 20-30%, so
    per-side medians taken minutes apart still soak up drift; adjacent
    pairs run seconds apart and their ratio cancels it.  The recording
    side also attests the sketches actually accumulated (a no-op path
    passing the gate would prove nothing).

    Merge parity: the same deterministic per-machine request stream is
    scored once through one full-fleet collection and once through two
    machine-affinity shard collections (the serve.shard partition);
    the shards' health docs merge through telemetry.merge_health_docs —
    the SAME function watchman's /fleet-health endpoint applies to the
    per-replica docs it fetches — and the merged doc must equal the
    single-process doc byte-for-byte after stripping timestamps
    (json.dumps(normalize_health_doc(...), sort_keys=True)).
    """
    from gordo_tpu import telemetry
    from gordo_tpu.serve.replay import replay_bench
    from gordo_tpu.serve.server import ModelCollection
    from gordo_tpu.serve.shard import shard_map

    model, metadata = _build_serving_model()
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-health-")
    try:
        collection = _serving_collection(art_dir, model, metadata, 64)
        names = sorted(collection.entries)
        baselines = {n: collection.entries[n].metadata for n in names}

        def sample(n_rounds: int = 5) -> dict:
            return replay_bench(
                collection, mode="bulk", wire="msgpack", n_rounds=n_rounds,
                rows=2048, parallelism=8,
            )

        telemetry.FLEET_HEALTH.clear()
        telemetry.FLEET_HEALTH.load_baselines(baselines)
        on_samples: "list[float]" = []
        off_samples: "list[float]" = []
        pair_pcts: "list[float]" = []
        for i in range(5):
            for enabled in (True, False):
                telemetry.set_enabled(enabled)
                try:
                    if i == 0:
                        sample(n_rounds=2)  # per-side warmup, discarded
                    rate = sample()["samples_per_sec"]
                finally:
                    telemetry.set_enabled(True)
                (on_samples if enabled else off_samples).append(rate)
            pair_pcts.append(
                100.0 * (1.0 - on_samples[-1] / off_samples[-1])
            )
        overhead_pct = sorted(pair_pcts)[len(pair_pcts) // 2]
        doc = telemetry.FLEET_HEALTH.doc(machines=names)
        recorded = sum(
            1 for e in doc["machines"].values() if e["live"]
        )
        out["health_on_samples"] = [round(v) for v in on_samples]
        out["health_off_samples"] = [round(v) for v in off_samples]
        out["health_pair_overhead_pcts"] = [
            round(p, 2) for p in pair_pcts
        ]
        out["health_overhead_pct"] = round(overhead_pct, 2)
        out["health_overhead_ok"] = overhead_pct <= 2.0
        # recording attestation: every served machine's sketch is live
        # and the drift signal computed against the build baseline
        out["health_machines_recorded"] = recorded
        out["health_top_drift_len"] = len(doc["top-drift"])
        log(
            f"fleet-health overhead (msgpack bulk, median of 5 adjacent "
            f"on/off pairs): {overhead_pct:+.2f}% "
            f"(pairs {[round(p, 2) for p in pair_pcts]}, gate: <= 2%); "
            f"{recorded}/64 machines sketched"
        )

        # -- 2-shard merged doc == single-process doc -----------------------
        rng = np.random.default_rng(14)
        streams = {
            n: [
                rng.standard_normal((1024, N_TAGS)).astype(np.float32)
                for _ in range(3)
            ]
            for n in names
        }

        telemetry.FLEET_HEALTH.clear()
        telemetry.FLEET_HEALTH.load_baselines(baselines)
        full_scorer = collection.fleet_scorer
        for rnd in range(3):
            full_scorer.score_all({n: streams[n][rnd] for n in names})
        doc_full = telemetry.normalize_health_doc(
            telemetry.FLEET_HEALTH.doc(machines=names, top=8)
        )

        telemetry.FLEET_HEALTH.clear()
        owners = shard_map(names, 2)
        shard_docs = []
        for shard_idx in range(2):
            owned = [n for n in names if owners[n] == shard_idx]
            shard_col = ModelCollection(
                {n: collection.entries[n] for n in owned}, project="bench"
            )
            for rnd in range(3):
                shard_col.fleet_scorer.score_all(
                    {n: streams[n][rnd] for n in owned}
                )
            shard_docs.append(
                telemetry.FLEET_HEALTH.doc(machines=owned, top=8)
            )
        merged = telemetry.normalize_health_doc(
            telemetry.merge_health_docs(shard_docs, top=8)
        )
        full_bytes = json.dumps(doc_full, sort_keys=True)
        merged_bytes = json.dumps(merged, sort_keys=True)
        out["health_merge_parity_ok"] = full_bytes == merged_bytes
        out["health_merge_doc_bytes"] = len(full_bytes)
        log(
            "fleet-health 2-shard merged doc parity: "
            + ("byte-equivalent" if full_bytes == merged_bytes
               else "MISMATCH")
            + f" ({len(full_bytes)} bytes, modulo timestamps)"
        )
        telemetry.FLEET_HEALTH.clear()
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_artifact_io(out: dict) -> None:
    """ISSUE 6 acceptance: artifact format v2 (memory-mapped bucket
    packs) vs v1 (per-machine dirs) — build artifact-write throughput
    and server time-to-ready, measured in the same run.

    Protocol (docs/perf.md "Artifact I/O"): train ONE machine, then
    replicate its trained detector across N names so the measurement
    isolates artifact I/O from training.  Writes: v1 dumps N per-machine
    dirs through the serializer; v2 writes ``ceil(N/512)`` packs through
    ``artifacts.write_pack``.  Time-to-ready: ``ModelCollection.
    from_directory`` + fleet-scorer construction + a block on the
    stacked device params — everything between "process has artifacts"
    and "bulk scoring is resident", without HTTP noise.  At 512 the
    ready points run best-of-2 interleaved (v1, v2, v1, v2 — shared-CPU
    drift lands on both sides); the 10k points run once each, budget
    permitting.  Gate: v2 time-to-ready at 512 strictly below v1's in
    this run.  The v2 load's whole-pack device transfers are attested
    from the telemetry counter (exactly one per pack).
    """
    import jax

    from gordo_tpu import artifacts, serializer
    from gordo_tpu.serve.server import ModelCollection

    model, metadata = _build_serving_model()
    chunk = 512

    def dir_bytes(d: str) -> int:
        total = 0
        for root, _, files in os.walk(d):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(root, f))
                except OSError:
                    pass
        return total

    def write_v1(d: str, names: "list[str]") -> float:
        t0 = time.perf_counter()
        for name in names:
            md = dict(metadata)
            md["name"] = name
            serializer.dump(model, os.path.join(d, name), metadata=md)
        return time.perf_counter() - t0

    def write_v2(d: str, names: "list[str]") -> float:
        t0 = time.perf_counter()
        for start in range(0, len(names), chunk):
            part = names[start: start + chunk]
            metas = []
            for name in part:
                md = dict(metadata)
                md["name"] = name
                metas.append(md)
            artifacts.write_pack(d, part, [model] * len(part), metas)
        return time.perf_counter() - t0

    def time_to_ready(d: str) -> float:
        t0 = time.perf_counter()
        coll = ModelCollection.from_directory(d, project="bench")
        fleet = coll.fleet_scorer
        for bucket in fleet.buckets:
            jax.block_until_ready(jax.tree.leaves(bucket.params))
        return time.perf_counter() - t0

    n_large = int(os.environ.get("BENCH_ARTIFACT_MACHINES", "10000"))
    for n in (512, n_large):
        names = [f"am-{i:05d}" for i in range(n)]
        d1 = tempfile.mkdtemp(prefix=f"gordo-bench-art-v1-{n}-")
        d2 = tempfile.mkdtemp(prefix=f"gordo-bench-art-v2-{n}-")
        try:
            t_v1 = write_v1(d1, names)
            t_v2 = write_v2(d2, names)
            b1, b2 = dir_bytes(d1), dir_bytes(d2)
            n_packs = -(-n // chunk)
            out[f"artifact_io_write_v1_s_{n}"] = round(t_v1, 3)
            out[f"artifact_io_write_v2_s_{n}"] = round(t_v2, 3)
            out[f"artifact_io_write_v1_artifacts_per_sec_{n}"] = round(
                n / t_v1, 1
            )
            out[f"artifact_io_write_v2_artifacts_per_sec_{n}"] = round(
                n / t_v2, 1
            )
            out[f"artifact_io_write_v2_mb_per_sec_{n}"] = round(
                b2 / t_v2 / 1e6, 1
            )
            out[f"artifact_io_bytes_v1_{n}"] = b1
            out[f"artifact_io_bytes_v2_{n}"] = b2
            out[f"artifact_io_packs_{n}"] = n_packs
            log(f"artifact_io write @{n}: v1 {t_v1:.2f}s ({b1 / 1e6:.1f} MB)"
                f" vs v2 {t_v2:.2f}s ({b2 / 1e6:.1f} MB, {n_packs} packs)")

            attempts = 2 if n == 512 else 1
            ready = {"v1": [], "v2": []}
            for i in range(attempts):
                ready["v1"].append(time_to_ready(d1))
                if n == 512 and i == 0:
                    d0 = artifacts.device_put_count()
                ready["v2"].append(time_to_ready(d2))
                if n == 512 and i == 0:
                    dputs = artifacts.device_put_count() - d0
                    out["artifact_io_device_puts_512"] = dputs
                    out["artifact_io_one_device_put_per_pack"] = (
                        dputs == n_packs
                    )
            r1, r2 = min(ready["v1"]), min(ready["v2"])
            out[f"artifact_io_ready_v1_s_{n}"] = round(r1, 3)
            out[f"artifact_io_ready_v2_s_{n}"] = round(r2, 3)
            out[f"artifact_io_ready_speedup_{n}"] = round(r1 / r2, 3)
            log(f"artifact_io time-to-ready @{n}: v1 {r1:.2f}s vs "
                f"v2 {r2:.2f}s ({r1 / r2:.2f}x)")
            if n == 512:
                # the acceptance gate, same-run comparison
                out["artifact_io_ready_ok"] = r2 < r1
                # context vs BENCH_r10's warmed-restart 2.19s (different
                # workload — 8-machine forked full restart — recorded
                # for trend reading, not a gate)
                out["artifact_io_ready_v2_beats_r10_restart"] = r2 < 2.19
        finally:
            shutil.rmtree(d1, ignore_errors=True)
            shutil.rmtree(d2, ignore_errors=True)


def bench_hot_reload(out: dict) -> None:
    """ISSUE 11 acceptance: versioned artifact generations + delta hot
    reload — the serving process picks up a ``delta_write`` of k changed
    machines out of BENCH_ARTIFACT_MACHINES (default 10k) in
    O(changed-machines), never restarting and never recompiling.

    Protocol (docs/perf.md "Hot reload"): train ONE machine, replicate
    it across N names into v2 packs (512/chunk), stamp generation 1,
    and keep one long-lived ModelCollection serving it.  Each delta
    cycle ``delta_write``s a contiguous builder-chunk-shaped range of k
    machines (k=32 → a 1-pack slice, k=512 → a whole pack), then times
    ``maybe_delta_reload`` + a block on the stacked device params — the
    moment scoring sees the new generation.  Full-restart baseline is
    ``ModelCollection.from_directory`` + fleet-scorer + block over the
    same dir, interleaved best-of-2 with the delta cycles so shared-CPU
    drift lands on both sides.  Gates: delta@32 ≤ 0.1× full restart;
    zero ``gordo_compile_cache_misses_total`` growth across every
    reload (stable bucket shapes compile nothing); scoring p99 measured
    concurrently DURING reload cycles within 1.25× steady state; and
    post-flip scoring byte-identical to a cold load of the final
    generation.  Device transfers per delta are attested from the
    telemetry counter (exactly one per touched pack).
    """
    import pickle
    import threading

    import jax

    from gordo_tpu import artifacts, telemetry
    from gordo_tpu.serve.server import ModelCollection

    model, metadata = _build_serving_model()
    chunk = 512
    n = int(os.environ.get("BENCH_ARTIFACT_MACHINES", "10000"))
    names = [f"hr-{i:05d}" for i in range(n)]
    d = tempfile.mkdtemp(prefix="gordo-bench-hotreload-")

    def counter(name: str) -> float:
        metric = telemetry.REGISTRY.snapshot()["metrics"].get(name) or {}
        return float(sum(metric.get("series", {}).values()))

    try:
        t0 = time.perf_counter()
        for start in range(0, n, chunk):
            part = names[start: start + chunk]
            metas = []
            for nm in part:
                md = dict(metadata)
                md["name"] = nm
                metas.append(md)
            artifacts.write_pack(d, part, [model] * len(part), metas)
        gen = artifacts.stamp_generation(d)
        out["hot_reload_write_s"] = round(time.perf_counter() - t0, 3)
        out["hot_reload_machines"] = n
        log(f"hot_reload: wrote {n} machines as v2 gen {gen} in "
            f"{out['hot_reload_write_s']}s")

        def time_to_ready() -> float:
            t0 = time.perf_counter()
            coll = ModelCollection.from_directory(d, project="bench")
            fleet = coll.fleet_scorer
            for bucket in fleet.buckets:
                jax.block_until_ready(jax.tree.leaves(bucket.params))
            return time.perf_counter() - t0

        # the long-lived serving collection every delta cycle reloads
        serving = ModelCollection.from_directory(d, project="bench")
        for bucket in serving.fleet_scorer.buckets:
            jax.block_until_ready(jax.tree.leaves(bucket.params))

        # scoring subset spanning changed and unchanged machines; warm
        # the program so the compile-miss window below is pure reload
        rng = np.random.default_rng(0)
        X = rng.standard_normal((512, N_TAGS)).astype(np.float32)
        sub_names = sorted({names[i] for i in (
            0, min(33, n - 1), min(chunk * 3, n - 1), n // 2, n - 1,
        )})
        sub = {nm: X for nm in sub_names}
        serving.fleet_scorer.score_all(sub)
        # the p99 probe request: a whole-fleet bulk sweep — this tier's
        # canonical workload — warmed here so the compile-miss window
        # below spans only reloads
        bulk = {nm: X for nm in names}
        serving.fleet_scorer.score_all(bulk)

        variant = pickle.loads(pickle.dumps(model))
        tick = [1000.0]

        def write_delta(k: int, lo: int) -> "list[str]":
            """Builder-side half: delta_write names[lo:lo+k] as a new
            generation.  On a real fleet this runs on the builder, not
            the serving replica — it never counts as reload time."""
            tick[0] += 1.0
            if hasattr(variant, "aggregate_threshold_"):
                variant.aggregate_threshold_ = tick[0]
            changed = names[lo: lo + k]
            artifacts.delta_write(d, {nm: variant for nm in changed})
            return changed

        def reload_timed(changed: "list[str]") -> "tuple[float, float]":
            """Serving-side half: the reload-to-ready window (wall
            start/end) for the generation just published."""
            t0 = time.perf_counter()
            changes = serving.maybe_delta_reload()
            fleet = serving.fleet_scorer
            for bucket in fleet.buckets:
                jax.block_until_ready(jax.tree.leaves(bucket.params))
            t1 = time.perf_counter()
            if sorted(changes["reloaded"]) != sorted(changed):
                raise RuntimeError(
                    f"reload touched {len(changes['reloaded'])} machines, "
                    f"expected {len(changed)}"
                )
            return t0, t1

        def delta_cycle(k: int, lo: int) -> float:
            t0, t1 = reload_timed(write_delta(k, lo))
            return t1 - t0

        misses0 = counter("gordo_compile_cache_misses_total")

        # interleaved best-of-2: restart, delta@32, restart, delta@32 —
        # then delta@512 twice (a whole pack each, different pack per
        # cycle so neither side rides the other's page cache)
        k_small = min(32, n)
        k_big = min(chunk, n)
        lo_a = chunk * 3 if n >= chunk * 4 else 0
        lo_b = chunk * 4 if n >= chunk * 5 else lo_a
        full_1 = time_to_ready()
        dputs0 = artifacts.device_put_count()
        delta32_1 = delta_cycle(k_small, 0)
        dputs_32 = artifacts.device_put_count() - dputs0
        full_2 = time_to_ready()
        delta32_2 = delta_cycle(k_small, 0)
        delta512_1 = delta_cycle(k_big, lo_a)
        delta512_2 = delta_cycle(k_big, lo_b)

        # p99 while reloads are actually in flight.  The probe request
        # is the whole-fleet sweep from a worker thread — the steady
        # baseline uses the SAME thread structure with the main thread
        # idle, and only samples whose wall interval overlaps a
        # reload-to-ready window count as "during reload".  delta_write
        # runs on the builder on a real fleet, so each cycle lets the
        # request that overlapped the write drain before the reload
        # starts — reload windows measure pure serving-side sharing.
        samples: "list[tuple[float, float]]" = []
        stop = threading.Event()

        def score_loop() -> None:
            while not stop.is_set():
                t0 = time.perf_counter()
                serving.fleet_scorer.score_all(bulk)
                samples.append((t0, time.perf_counter()))

        th = threading.Thread(target=score_loop, daemon=True)
        th.start()
        t_end = time.perf_counter() + 45.0
        while len(samples) < 13 and time.perf_counter() < t_end:
            time.sleep(0.05)
        # first sample is the conventional warm-in discard
        lat_steady = (
            [t1 - t0 for t0, t1 in samples[1:]]
            or [t1 - t0 for t0, t1 in samples]
        )

        mark = max(0, len(samples) - 1)
        windows: "list[tuple[float, float]]" = []
        lo_load = 64 if n >= 96 else 0
        t_end = time.perf_counter() + 120.0
        while len(windows) < 12 and time.perf_counter() < t_end:
            changed = write_delta(k_small, lo_load)
            settle = len(samples) + 1
            while len(samples) < settle and time.perf_counter() < t_end:
                time.sleep(0.01)
            windows.append(reload_timed(changed))
        stop.set()
        th.join(timeout=60)
        reload_cycles = len(windows)
        lat_reload = [
            t1 - t0 for t0, t1 in samples[mark:]
            if any(t0 < w1 and w0 < t1 for w0, w1 in windows)
        ] or lat_steady
        serving.fleet_scorer.score_all(sub)  # post-flip dispatch counted
        misses_delta = (
            counter("gordo_compile_cache_misses_total") - misses0
        )

        full = min(full_1, full_2)
        d32 = min(delta32_1, delta32_2)
        d512 = min(delta512_1, delta512_2)
        p99_s = float(np.percentile(lat_steady, 99)) * 1e3
        p99_r = float(np.percentile(lat_reload, 99)) * 1e3

        out["hot_reload_full_restart_s"] = round(full, 3)
        out["hot_reload_delta_s_32"] = round(d32, 3)
        out["hot_reload_delta_s_512"] = round(d512, 3)
        out["hot_reload_ratio_32"] = round(d32 / full, 4)
        out["hot_reload_ratio_512"] = round(d512 / full, 4)
        out["hot_reload_ratio_32_ok"] = d32 / full <= 0.1
        out["hot_reload_device_puts_32"] = dputs_32
        out["hot_reload_one_put_per_touched_pack"] = dputs_32 == 1.0
        out["hot_reload_compile_misses_delta"] = misses_delta
        out["hot_reload_zero_compile_ok"] = misses_delta == 0.0
        out["hot_reload_cycles_under_load"] = reload_cycles
        out["hot_reload_p99_samples_steady"] = len(lat_steady)
        out["hot_reload_p99_samples_reload"] = len(lat_reload)
        out["hot_reload_p50_steady_ms"] = round(
            float(np.percentile(lat_steady, 50)) * 1e3, 2
        )
        out["hot_reload_p50_reload_ms"] = round(
            float(np.percentile(lat_reload, 50)) * 1e3, 2
        )
        out["hot_reload_p99_steady_ms"] = round(p99_s, 2)
        out["hot_reload_p99_reload_ms"] = round(p99_r, 2)
        out["hot_reload_p99_ratio"] = round(p99_r / p99_s, 3)
        out["hot_reload_p99_ok"] = p99_r <= 1.25 * p99_s
        out["hot_reload_generation"] = serving.generation
        log(f"hot_reload: restart {full:.2f}s vs delta@32 {d32:.3f}s "
            f"({d32 / full:.3f}x) / delta@512 {d512:.3f}s; "
            f"compile misses +{misses_delta:.0f}; p99 steady {p99_s:.1f}ms "
            f"vs during-reload {p99_r:.1f}ms")

        # byte-identity: the delta-reloaded scorer must match a cold
        # load of the final generation exactly
        cold = ModelCollection.from_directory(d, project="bench")
        hot_o = serving.fleet_scorer.score_all(sub)
        cold_o = cold.fleet_scorer.score_all(sub)
        identical = all(
            np.asarray(hot_o[nm][k]).tobytes()
            == np.asarray(cold_o[nm][k]).tobytes()
            for nm in hot_o for k in hot_o[nm]
        )
        out["hot_reload_byte_identical_to_cold_load"] = identical
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_serving_sharded(out: dict) -> None:
    """ISSUE 8 acceptance: the horizontal serving tier — N forked scoring
    replicas (REAL server processes, the multihost_dryrun pattern), each
    loading only its shard of a shared v2 pack dir, driven closed-loop at
    64-way concurrency with client-side machine-affinity routing.

    Protocol (docs/perf.md "Sharded serving"):

    - one trained machine replicated across 64 names, packed v2 in
      8-machine chunks so shard boundaries align with pack boundaries at
      N=2 and N=4;
    - baseline: ONE unsharded server process; sharded: N=2 and N=4
      replica processes (``gordo run-server --shard i/N`` equivalents),
      requests routed to owners via ``serve.shard.ShardRouter``;
    - aggregate throughput + p50/p99 per topology after a full warmup
      round (per-request latencies from submission, 64 in flight);
    - byte parity: the 2-replica scatter-gather of one bulk round must
      equal the single process's response arrays EXACTLY;
    - per-replica time-to-ready at 10k machines: a fresh process loading
      shard 0/4 vs a fresh process loading everything (the 1/N gate —
      each replica touches only its own packs' skeletons/transfers).

    Honesty note: this container exposes ONE CPU core, so N replica
    processes timeshare it — aggregate throughput CANNOT show the real
    N-way win here (the processes are compute-serialized).  The fields gate
    what 1 core can prove (routing correctness, parity, 1/N ready); the
    throughput ratios are recorded with ``cpu_cores`` alongside.
    """
    import asyncio
    import socket
    import urllib.request

    import aiohttp

    from gordo_tpu import artifacts
    from gordo_tpu.serve import codec
    from gordo_tpu.serve.shard import ShardRouter, shard_slices

    n_machines = int(os.environ.get("BENCH_SHARDED_MACHINES", "64"))
    rows = int(os.environ.get("BENCH_SHARDED_ROWS", "512"))
    rounds = int(os.environ.get("BENCH_SHARDED_ROUNDS", "6"))
    concurrency = 64
    out["cpu_cores"] = os.cpu_count()
    if os.cpu_count() == 1:
        out["sharded_single_core_serialized"] = (
            "1 visible core: replica processes timeshare it, so the "
            "aggregate-throughput axis cannot exceed ~1x here; the "
            "multi-core/TPU win is not measured"
        )

    model, metadata = _build_serving_model()
    names = [f"sm-{i:03d}" for i in range(n_machines)]
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-sharded-")
    procs: "list[subprocess.Popen]" = []
    logs: "list[str]" = []

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(port: int, shard: "str | None") -> subprocess.Popen:
        env = dict(os.environ)
        env.pop("GORDO_SERVE_SHARD", None)
        env["JAX_PLATFORMS"] = "cpu"
        args = [
            sys.executable, "-m", "gordo_tpu.cli.cli", "run-server",
            "--model-dir", art_dir, "--project", "bench",
            "--host", "127.0.0.1", "--port", str(port),
            "--rescan-interval", "0",
        ]
        if shard:
            args += ["--shard", shard]
        log_path = os.path.join(art_dir, f"server-{port}.log")
        logs.append(log_path)
        proc = subprocess.Popen(
            args, env=env,
            stdout=open(log_path, "w"), stderr=subprocess.STDOUT,
        )
        procs.append(proc)
        return proc

    def wait_ready(port: int, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        url = f"http://127.0.0.1:{port}/healthz"
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return
            except Exception:
                time.sleep(0.25)
        raise RuntimeError(f"replica on :{port} never became ready")

    def stop(to_stop: "list[subprocess.Popen]") -> None:
        for proc in to_stop:
            proc.terminate()
        for proc in to_stop:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    headers = {
        "Content-Type": codec.MSGPACK_CONTENT_TYPE,
        "Accept": codec.MSGPACK_CONTENT_TYPE,
    }

    async def drive(urls_by_machine: "dict[str, str]") -> dict:
        """Closed-loop single-machine anomaly rounds, 64 in flight across
        the whole tier, each request routed to its owner replica."""
        rng = np.random.default_rng(0)
        bodies = {
            name: codec.packb(
                {"X": rng.standard_normal((rows, N_TAGS)).astype(np.float32)}
            )
            for name in names
        }
        latencies: "list[float]" = []
        timeout = aiohttp.ClientTimeout(total=300)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            sem = asyncio.Semaphore(concurrency)

            async def post(name: str, measured: bool) -> None:
                url = (
                    f"{urls_by_machine[name]}/gordo/v0/bench/{name}"
                    "/anomaly/prediction"
                )
                async with sem:
                    t0 = time.perf_counter()
                    async with session.post(
                        url, data=bodies[name], headers=headers
                    ) as resp:
                        raw = await resp.read()
                    if resp.status != 200:
                        raise RuntimeError(
                            f"{name} -> {resp.status}: {raw[:160]!r}"
                        )
                if measured:
                    latencies.append(time.perf_counter() - t0)

            # warmup round: per-process compiles land outside the timing
            await asyncio.gather(*(post(n, False) for n in names))
            t0 = time.perf_counter()
            await asyncio.gather(*(
                post(n, True) for _ in range(rounds) for n in names
            ))
            dt = time.perf_counter() - t0
        n_req = rounds * len(names)
        p50, p99 = np.percentile(latencies, [50, 99])
        return {
            "samples_per_sec": n_req * rows * N_TAGS / dt,
            "requests_per_sec": n_req / dt,
            "p50_ms": float(p50 * 1e3),
            "p99_ms": float(p99 * 1e3),
        }

    async def bulk_scatter(
        urls: "list[str]", X_by: "dict[str, np.ndarray]"
    ) -> dict:
        """One bulk round, scatter-gathered across ``urls`` with the
        shared shard function, reassembled in machine order."""
        router = ShardRouter(names, urls)
        plan = router.split(X_by)
        timeout = aiohttp.ClientTimeout(total=300)
        async with aiohttp.ClientSession(timeout=timeout) as session:

            async def post(base: str, members: "list[str]") -> dict:
                async with session.post(
                    f"{base}/gordo/v0/bench/_bulk/anomaly/prediction",
                    data=codec.packb({"X": {m: X_by[m] for m in members}}),
                    headers=headers,
                ) as resp:
                    raw = await resp.read()
                if resp.status != 200:
                    raise RuntimeError(f"bulk {base} -> {resp.status}")
                return codec.unpackb(raw)["data"]

            parts = await asyncio.gather(
                *(post(b, ms) for b, ms in plan.items())
            )
        gathered: dict = {}
        for part in parts:
            gathered.update(part)
        return {m: gathered[m] for m in X_by}

    try:
        # ---- shared v2 artifact dir: 8-machine packs (shard-aligned) ----
        chunk = max(1, n_machines // 8)
        for start in range(0, n_machines, chunk):
            part = names[start: start + chunk]
            metas = []
            for name in part:
                md = dict(metadata)
                md["name"] = name
                metas.append(md)
            artifacts.write_pack(art_dir, part, [model] * len(part), metas)
        log(f"sharded: {n_machines} machines in "
            f"{-(-n_machines // chunk)} packs under {art_dir}")

        # ---- baseline: one unsharded process ----
        base_port = free_port()
        base_proc = spawn(base_port, None)
        wait_ready(base_port)
        base_url = f"http://127.0.0.1:{base_port}"
        baseline = asyncio.run(drive({n: base_url for n in names}))
        out["sharded_baseline_samples_per_sec"] = round(
            baseline["samples_per_sec"]
        )
        out["sharded_baseline_p50_ms"] = round(baseline["p50_ms"], 2)
        out["sharded_baseline_p99_ms"] = round(baseline["p99_ms"], 2)
        log(f"sharded baseline (1 proc): "
            f"{baseline['samples_per_sec']:,.0f} samples/s, "
            f"p50 {baseline['p50_ms']:.0f}ms p99 {baseline['p99_ms']:.0f}ms")

        rng = np.random.default_rng(11)
        X_parity = {
            n: rng.standard_normal((rows, N_TAGS)).astype(np.float32)
            for n in names
        }
        single_bulk = asyncio.run(bulk_scatter([base_url], X_parity))

        for n_replicas in (2, 4):
            ports = [free_port() for _ in range(n_replicas)]
            replica_procs = [
                spawn(port, f"{i}/{n_replicas}")
                for i, port in enumerate(ports)
            ]
            for port in ports:
                wait_ready(port)
            urls = [f"http://127.0.0.1:{p}" for p in ports]
            slices = shard_slices(names, n_replicas)
            url_of = {
                name: urls[i]
                for i, shard in enumerate(slices) for name in shard
            }
            res = asyncio.run(drive(url_of))
            key = f"sharded_{n_replicas}rep"
            out[f"{key}_samples_per_sec"] = round(res["samples_per_sec"])
            out[f"{key}_p50_ms"] = round(res["p50_ms"], 2)
            out[f"{key}_p99_ms"] = round(res["p99_ms"], 2)
            speedup = res["samples_per_sec"] / baseline["samples_per_sec"]
            out[f"sharded_speedup_{n_replicas}"] = round(speedup, 3)
            log(f"sharded {n_replicas} replicas: "
                f"{res['samples_per_sec']:,.0f} samples/s "
                f"({speedup:.2f}x baseline), p50 {res['p50_ms']:.0f}ms "
                f"p99 {res['p99_ms']:.0f}ms")

            if n_replicas == 2:
                sharded_bulk = asyncio.run(bulk_scatter(urls, X_parity))
                parity = list(sharded_bulk) == list(single_bulk) and all(
                    (
                        np.array_equal(sharded_bulk[m][k], v)
                        and getattr(sharded_bulk[m][k], "dtype", None)
                        == getattr(v, "dtype", None)
                    )
                    if isinstance(v, np.ndarray)
                    else sharded_bulk[m][k] == v
                    for m in single_bulk
                    for k, v in single_bulk[m].items()
                )
                out["sharded_parity_ok"] = bool(parity)
                out["sharded_parity_machines"] = len(single_bulk)
                log(f"sharded 2-replica scatter-gather byte parity: "
                    f"{'OK' if parity else 'FAILED'} "
                    f"({len(single_bulk)} machines)")
            stop(replica_procs)
        # the 2x gate the multi-core deployment meets; recorded honestly
        # either way (see cpu_cores / sharded_single_core_serialized)
        out["sharded_2x_ge_1p6_ok"] = out["sharded_speedup_2"] >= 1.6
        stop([base_proc])

        # ---- per-replica time-to-ready at 10k machines ----
        n_large = int(os.environ.get("BENCH_SHARDED_READY_MACHINES", "10000"))
        ready_shards = 4
        big_dir = tempfile.mkdtemp(prefix="gordo-bench-sharded-10k-")
        try:
            big_names = [f"bm-{i:05d}" for i in range(n_large)]
            t0 = time.perf_counter()
            for start in range(0, n_large, 512):
                part = big_names[start: start + 512]
                metas = []
                for name in part:
                    md = dict(metadata)
                    md["name"] = name
                    metas.append(md)
                artifacts.write_pack(
                    big_dir, part, [model] * len(part), metas
                )
            log(f"sharded: {n_large}-machine v2 dir written in "
                f"{time.perf_counter() - t0:.1f}s")

            ready_script = (
                "import json, sys, time\n"
                "import jax\n"
                "from gordo_tpu.serve.server import ModelCollection\n"
                "from gordo_tpu.serve.shard import ShardSpec\n"
                "d, spec = sys.argv[1], sys.argv[2]\n"
                "shard = None if spec == '-' else ShardSpec.parse(spec)\n"
                "t0 = time.perf_counter()\n"
                "coll = ModelCollection.from_directory("
                "d, project='bench', shard=shard)\n"
                "fleet = coll.fleet_scorer\n"
                "for b in fleet.buckets:\n"
                "    jax.block_until_ready(jax.tree.leaves(b.params))\n"
                "print(json.dumps({'ready_s': time.perf_counter() - t0,"
                " 'machines': len(coll.entries)}))\n"
            )

            def ready_child(spec: str) -> dict:
                env = dict(os.environ)
                env.pop("GORDO_SERVE_SHARD", None)
                env["JAX_PLATFORMS"] = "cpu"
                res = subprocess.run(
                    [sys.executable, "-c", ready_script, big_dir, spec],
                    env=env, stdout=subprocess.PIPE, text=True,
                    timeout=600,
                )
                if res.returncode != 0:
                    raise RuntimeError(
                        f"ready child {spec} rc={res.returncode}"
                    )
                return json.loads(res.stdout.strip().splitlines()[-1])

            # min-of-2 per point (page-cache / shared-CPU noise lands on
            # both sides); shard 1 runs once to show a mid-fleet shard
            # (TWO pack-boundary slices) costs the same shape
            full_s = min(ready_child("-")["ready_s"] for _ in range(2))
            shard0_s = min(
                ready_child(f"0/{ready_shards}")["ready_s"]
                for _ in range(2)
            )
            shard1_s = ready_child(f"1/{ready_shards}")["ready_s"]
            fraction = shard0_s / full_s
            out[f"sharded_ready_full_{n_large}_s"] = round(full_s, 3)
            out[f"sharded_ready_shard_{n_large}_s"] = round(shard0_s, 3)
            out[f"sharded_ready_shard1_{n_large}_s"] = round(shard1_s, 3)
            out["sharded_ready_shards"] = ready_shards
            out["sharded_ready_fraction"] = round(fraction, 3)
            # strict same-run gate: shard <= full/N.  The fixed cost both
            # loads share (store open + discover over the FULL index) is
            # a few % of full, so this sits within noise of exactly 1/N.
            out["sharded_ready_1_over_n_ok"] = (
                fraction <= 1.0 / ready_shards
            )
            # the ISSUE reference point: 1/N of the single-process v2
            # number from BENCH_r11 (37.9s v1 -> 6.0s v2 at 10k, CPU)
            out["sharded_ready_vs_r11_6s_ok"] = (
                n_large != 10000 or shard0_s <= 6.0 / ready_shards
            )
            log(f"sharded time-to-ready @{n_large}: full {full_s:.2f}s vs "
                f"shard 0/{ready_shards} {shard0_s:.2f}s / shard 1 "
                f"{shard1_s:.2f}s ({fraction:.3f} of full; gate <= "
                f"{1.0 / ready_shards:.2f}; r11 ref 6.0s/N)")
        finally:
            shutil.rmtree(big_dir, ignore_errors=True)
    except Exception:
        for log_path in logs:
            try:
                with open(log_path) as fh:
                    tail = fh.read()[-2000:]
                if tail:
                    log(f"--- {log_path} tail ---\n{tail}")
            except OSError:
                pass
        raise
    finally:
        stop(procs)
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_cold_start(out: dict) -> None:
    """ISSUE 5 acceptance: cold-start elimination, measured end to end.

    Protocol (docs/perf.md "Cold start"): build a small project once
    (artifacts + warmup manifest on disk), then fork FRESH processes —
    the quantity under test only exists in a process with empty compile
    caches — via ``python -m gordo_tpu.compile.coldstart``:

    - ``cold`` × K: no warmup; the first request eats the compile.
    - ``warm`` × K: manifest-driven AOT warmup first; the first request
      pays dispatch only.  p99 over the K per-process first requests
      (each process contributes exactly one first request).
    - cached restart: two ``warm`` runs sharing a persistent compile
      cache (``GORDO_COMPILE_CACHE=force`` + a scratch
      ``JAX_COMPILATION_CACHE_DIR`` — force because this container's CPU
      backend is excluded by default; back-to-back runs on one machine
      are the trusted single-machine case the override exists for).
      Run 1 populates, run 2 must go ready measurably faster, with the
      ``gordo_compile_cache_hits_total{cache="persistent"}`` counters
      from run 2's exposition attested into the result doc.

    Gates: warmed first-request p99 at least 5x below unwarmed, and
    cached-restart time-to-ready below the uncached one.
    """
    from gordo_tpu.builder.fleet_build import build_project

    trials = int(os.environ.get("BENCH_COLD_TRIALS", "5"))
    rows = 256
    art_dir = tempfile.mkdtemp(prefix="gordo-bench-cold-art-")
    cache_dir = tempfile.mkdtemp(prefix="gordo-bench-cold-cache-")

    def child(mode: str, env_extra: dict) -> dict:
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(env_extra)
        res = subprocess.run(
            [sys.executable, "-m", "gordo_tpu.compile.coldstart",
             "--artifacts", art_dir, "--mode", mode, "--rows", str(rows)],
            env=env, stdout=subprocess.PIPE, text=True, timeout=300,
        )
        line = (res.stdout or "").strip().splitlines()
        doc = json.loads(line[-1]) if line else {}
        if res.returncode != 0 or "error" in doc:
            raise RuntimeError(
                f"cold-start child {mode} rc={res.returncode}: "
                f"{doc.get('error', 'no output')}"
            )
        return doc

    try:
        machines = make_machines(8, n_tags=4, prefix="bench-cold")
        result = build_project(machines, art_dir)
        if result.failed:
            raise RuntimeError(f"cold-start build failed: {result.failed}")

        no_disk = {"GORDO_COMPILE_CACHE": "0"}
        cold_runs = [child("cold", no_disk) for _ in range(trials)]
        warm_runs = [child("warm", no_disk) for _ in range(trials)]
        cold_p99 = float(np.percentile(
            [r["first_request_s"] for r in cold_runs], 99
        ))
        warm_p99 = float(np.percentile(
            [r["first_request_s"] for r in warm_runs], 99
        ))
        out["cold_start_trials"] = trials
        out["cold_start_unwarmed_first_request_p99_ms"] = round(
            cold_p99 * 1e3, 2
        )
        out["cold_start_warmed_first_request_p99_ms"] = round(
            warm_p99 * 1e3, 2
        )
        out["cold_start_first_request_speedup"] = round(
            cold_p99 / max(warm_p99, 1e-9), 2
        )
        out["cold_start_warmed_5x_ok"] = cold_p99 >= 5.0 * warm_p99
        log(f"cold_start first request: unwarmed p99 {cold_p99 * 1e3:.0f}ms "
            f"vs warmed p99 {warm_p99 * 1e3:.0f}ms "
            f"({cold_p99 / max(warm_p99, 1e-9):.1f}x)")

        # cached restart: populate the persistent cache, then restart.
        # min-compile-time 0: the bench's deliberately small programs
        # must exercise the disk round-trip the fleet's multi-second
        # programs get by default.
        disk = {"GORDO_COMPILE_CACHE": "force",
                "JAX_COMPILATION_CACHE_DIR": cache_dir,
                "GORDO_COMPILE_CACHE_MIN_SECONDS": "0"}
        populate = child("warm", disk)
        restart = child("warm", disk)
        out["cold_start_time_to_ready_uncached_s"] = populate[
            "time_to_ready_s"
        ]
        out["cold_start_time_to_ready_cached_s"] = restart["time_to_ready_s"]
        out["cold_start_cached_restart_ok"] = (
            restart["time_to_ready_s"] < populate["time_to_ready_s"]
        )
        hits = [
            line for line in restart.get("compile_metrics", ())
            if 'cache="persistent"' in line and "hits" in line
        ]
        out["cold_start_cache_hit_metrics"] = hits
        out["cold_start_metrics_scrape"] = restart.get("compile_metrics")
        log(f"cold_start time-to-ready: uncached "
            f"{populate['time_to_ready_s']:.2f}s vs cached restart "
            f"{restart['time_to_ready_s']:.2f}s; persistent hits: {hits}")
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)
        shutil.rmtree(cache_dir, ignore_errors=True)


def _sha256_tree(*parts) -> str:
    """Stable fp-byte digest over arrays / pytrees of arrays — the
    byte-parity witness the multi_device children compare against the
    single-device pinned run."""
    import hashlib

    import jax

    h = hashlib.sha256()
    for part in parts:
        for leaf in jax.tree_util.tree_leaves(part):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def _attest_placement(tree) -> dict:
    """Per-device placement attestation: where the first device array in
    ``tree`` actually lives (``addressable_shards``), not where the mesh
    said it should."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and leaf.ndim >= 1:
            shards = leaf.addressable_shards
            return {
                "n_shards": len(shards),
                "device_ids": sorted(s.device.id for s in shards),
                "shard_shape": list(shards[0].data.shape),
            }
    return {"n_shards": 0, "device_ids": [], "shard_shape": []}


def scaleout_child_main(argv: "list[str]") -> None:
    """Forked measurement half of :func:`bench_multi_device`: this
    process was spawned with ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` already in its environment (device topology is
    fixed at backend init, so the quantity under test only exists in a
    fresh process — the cold_start pattern).

    r22: the real placement plane end to end, in process.  Resolves a
    :class:`~gordo_tpu.mesh.FleetMesh` over every forced device, runs a
    sharded fleet FIT and a sharded fleet SCORING round, and prints one
    JSON line carrying (a) steady-state throughput for both, (b) sha256
    fp32 digests of the fit result and the score outputs — the parent
    compares them across device counts for byte parity against the
    single-device run, (c) ``addressable_shards`` attestation that
    params and stacked scoring buffers really landed one block per
    device, and (d) the compile-registry executable count per phase —
    exactly ONE sharded executable per bucket, stable across rounds."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, required=True)
    p.add_argument("--machines", type=int, default=32)
    p.add_argument("--rows", type=int, default=1024)
    p.add_argument("--rounds", type=int, default=8)
    a = p.parse_args(argv)
    try:
        import jax

        from gordo_tpu.compile import REGISTRY
        from gordo_tpu.mesh import FleetMesh
        from gordo_tpu.parallel.fleet import fleet_fit
        from gordo_tpu.serve.fleet_scorer import FleetScorer
        from gordo_tpu.train.fit import TrainConfig

        devices = jax.devices()
        if len(devices) != a.devices:
            raise RuntimeError(
                f"forced {a.devices} host devices, backend exposes "
                f"{len(devices)}"
            )
        fm = FleetMesh.resolve()  # all forced devices on the fleet axis
        doc: dict = {
            "devices": fm.n_devices,
            "model_shards": fm.n_model_shards,
            "machines": a.machines,
            "rows": a.rows,
            "rounds": a.rounds,
        }

        # -- sharded fleet fit -------------------------------------------
        from gordo_tpu.registry import lookup_factory

        n_feat = 4
        module = lookup_factory("AutoEncoder", "feedforward_hourglass")(
            n_features=n_feat, n_features_out=n_feat
        )
        rng = np.random.default_rng(7)
        Xf = rng.standard_normal(
            (a.machines, 256, n_feat)
        ).astype(np.float32)
        wf = np.ones((a.machines, 256), np.float32)
        cfg = TrainConfig(epochs=2, batch_size=128)
        seeds = np.arange(a.machines, dtype=np.uint32)
        exe0 = REGISTRY.n_executables()
        t0 = time.perf_counter()
        fit_res = fleet_fit(
            module, Xf, Xf, wf, cfg, seeds=seeds, mesh=fm.mesh
        )
        fit_res.collect()
        doc["fit_cold_seconds"] = round(time.perf_counter() - t0, 4)
        doc["fit_executables"] = REGISTRY.n_executables() - exe0
        t0 = time.perf_counter()
        warm = fleet_fit(
            module, Xf, Xf, wf, cfg, seeds=seeds, mesh=fm.mesh
        )
        warm.collect()
        doc["fit_seconds"] = round(time.perf_counter() - t0, 4)
        doc["fit_digest"] = _sha256_tree(
            fit_res.history, fit_res.unstack_params()
        )
        doc["fit_placement"] = _attest_placement(fit_res.params)

        # -- sharded fleet scoring ---------------------------------------
        model, _metadata = _build_serving_model()
        names = [f"md-{i:03d}" for i in range(a.machines)]
        scorer = FleetScorer.from_models(
            {n: model for n in names}, mesh=fm.mesh
        )
        rng = np.random.default_rng(11)
        X_by = {
            n: rng.standard_normal((a.rows, N_TAGS)).astype(np.float32)
            for n in names
        }
        exe0 = REGISTRY.n_executables()
        first = scorer.score_all(X_by)  # compile + first transfers
        exe_after_compile = REGISTRY.n_executables() - exe0
        scorer.score_all(X_by)  # steady state
        t0 = time.perf_counter()
        for _ in range(a.rounds):
            out_scores = scorer.score_all(X_by)
        dt = time.perf_counter() - t0
        samples = a.rounds * a.machines * a.rows * N_TAGS
        doc["score_digest"] = _sha256_tree(
            [out_scores[n] for n in names]
        )
        doc["n_buckets"] = len(scorer.buckets)
        doc["score_executables"] = exe_after_compile
        # one sharded executable per bucket, and NO recompiles once warm
        doc["one_executable_per_bucket_ok"] = (
            exe_after_compile == len(scorer.buckets)
            and REGISTRY.n_executables() - exe0 == exe_after_compile
        )
        doc["score_placement"] = _attest_placement(
            vars(scorer.buckets[0])
        )
        del first
        doc.update({
            "n_stacked": scorer.n_stacked,
            "seconds": round(dt, 4),
            "samples_per_sec": round(samples / dt) if dt > 0 else None,
        })
        print(json.dumps(doc), flush=True)
    except Exception as exc:  # one diagnostic line, never a dead rc
        print(
            json.dumps({"error": f"{type(exc).__name__}: {exc}"}),
            flush=True,
        )
        raise SystemExit(1)
    raise SystemExit(0)


def bench_multi_device(out: dict) -> None:
    """ISSUE 18 tentpole: the placement plane end to end over REAL XLA
    device counts — forked children swept over
    ``--xla_force_host_platform_device_count`` in {1,2,4,8}
    (:func:`scaleout_child_main`), each running an in-process SHARDED
    fleet fit + fleet scoring through :class:`gordo_tpu.mesh.FleetMesh`.

    Beyond the throughput curve (and the r13 replica-scaling gate,
    >=1.6x aggregate at 2), the parent now verifies the correctness
    claims: every sharded child's fit and score sha256 digests must be
    BYTE-IDENTICAL to the 1-device child's (fp32; per-device blocks >= 2
    models — see tests/test_mesh.py for the block-1 ULP caveat), each
    child attests per-device placement via ``addressable_shards``, and
    each confirms exactly one sharded executable per bucket with no
    steady-state recompiles.

    Honesty note stands when the host exposes fewer cores than devices:
    forced host-platform devices timeshare the physical cores, so a flat
    curve there bounds sharding/scheduling overhead rather than
    disproving the multi-chip win.
    """
    counts = [
        int(x) for x in
        os.environ.get("BENCH_MULTI_DEVICE_COUNTS", "1,2,4,8").split(",")
    ]
    machines = int(os.environ.get("BENCH_MULTI_DEVICE_MACHINES", "32"))
    rows = int(os.environ.get("BENCH_MULTI_DEVICE_ROWS", "1024"))
    rounds = int(os.environ.get("BENCH_MULTI_DEVICE_ROUNDS", "8"))
    cores = os.cpu_count()
    out["cpu_cores"] = cores

    def child(n_dev: int) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_dev}")
        env["XLA_FLAGS"] = " ".join(flags)
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--scaleout-child", "--devices", str(n_dev),
             "--machines", str(machines), "--rows", str(rows),
             "--rounds", str(rounds)],
            env=env, stdout=subprocess.PIPE, text=True, timeout=420,
        )
        lines = (res.stdout or "").strip().splitlines()
        doc = json.loads(lines[-1]) if lines else {}
        if res.returncode != 0 or "error" in doc:
            raise RuntimeError(
                f"scaleout child @{n_dev} rc={res.returncode}: "
                f"{doc.get('error', 'no output')}"
            )
        return doc

    curve: dict = {}
    fit_curve: dict = {}
    docs: dict = {}
    for n_dev in counts:
        doc = child(n_dev)
        docs[str(n_dev)] = doc
        curve[str(n_dev)] = doc["samples_per_sec"]
        fit_curve[str(n_dev)] = doc.get("fit_seconds")
        log(f"multi_device @{n_dev}: {doc['samples_per_sec']:,} samples/s "
            f"({doc['n_stacked']} stacked, {doc['seconds']}s score, "
            f"{doc.get('fit_seconds')}s fit, "
            f"shards={doc.get('model_shards')})")
    out["multi_device_counts"] = counts
    out["multi_device_machines"] = machines
    out["multi_device_samples_per_sec"] = curve
    out["multi_device_fit_seconds"] = fit_curve

    # byte parity: every sharded child's fit/score digests must equal the
    # single-device child's, bit for bit (fp32)
    base_doc = docs.get("1")
    if base_doc:
        parity = {
            k: (d.get("fit_digest") == base_doc.get("fit_digest")
                and d.get("score_digest") == base_doc.get("score_digest"))
            for k, d in docs.items() if k != "1"
        }
        out["multi_device_byte_parity"] = parity
        out["multi_device_byte_parity_ok"] = all(parity.values())
        log(f"multi_device byte parity vs 1 device: {parity} -> "
            f"{'PASS' if all(parity.values()) else 'FAIL'}")
    # placement attestation + one-executable-per-bucket, per child
    out["multi_device_placement"] = {
        k: {
            "fit": d.get("fit_placement"),
            "score": d.get("score_placement"),
            "one_executable_per_bucket_ok": d.get(
                "one_executable_per_bucket_ok"
            ),
        }
        for k, d in docs.items()
    }
    placement_ok = all(
        d.get("fit_placement", {}).get("n_shards") == int(k)
        and d.get("score_placement", {}).get("n_shards") == int(k)
        and d.get("one_executable_per_bucket_ok")
        for k, d in docs.items()
        if int(k) > 1
    )
    out["multi_device_placement_ok"] = placement_ok
    log(f"multi_device placement attestation (addressable_shards == "
        f"device count, 1 executable/bucket): "
        f"{'PASS' if placement_ok else 'FAIL'}")
    base = curve.get("1")
    if base:
        speedups = {k: round(v / base, 3) for k, v in curve.items() if v}
        out["multi_device_speedup_vs_1"] = speedups
        at2 = speedups.get("2")
        if at2 is not None:
            out["multi_device_speedup_at_2"] = at2
            out["multi_device_ge_1_6x_at_2_ok"] = at2 >= 1.6
            log(f"multi_device gate: {at2:.2f}x @2 devices >= 1.6x -> "
                f"{'PASS' if at2 >= 1.6 else 'FAIL'}")
    if cores is not None and cores < max(counts):
        out["multi_device_core_note"] = (
            f"{cores} visible core(s) for up to {max(counts)} forced "
            "host devices: device programs timeshare the cores, so a "
            "flat curve bounds sharding overhead rather than disproving "
            "the multi-chip win"
        )


def _refresh_parity(out: dict, size: int, warm_dir: str, cold_dir: str,
                    subset, Xp, series: str, median_tol: float,
                    max_tol: float) -> bool:
    """Per-machine warm-vs-cold score parity for one refresh subset:
    max-normalized ``series`` error on the bf16 suite's standard-normal
    input, sampled across the subset.  Machines whose metadata attests a
    cold fallback are counted, not compared — the builder's parity gate
    already demoted them to full rebuilds."""
    from gordo_tpu import artifacts, telemetry
    from gordo_tpu.serve.server import ModelCollection

    sample = subset[::max(1, size // 16)][:16]
    store = artifacts.open_store(warm_dir)
    cold_coll = ModelCollection.from_directory(
        cold_dir, project="bench-refresh-cold"
    )
    warm_coll = ModelCollection.from_directory(
        warm_dir, project="bench-refresh-warm"
    )
    errs: "list[float]" = []
    attested = 0
    failed: "list[str]" = []
    with telemetry.FLEET_HEALTH.suspended():
        for m in sample:
            meta = store.load_metadata(m.name)
            warm_meta = meta.get("model", {}).get("warm_start", {})
            if warm_meta.get("warm") is False:
                attested += 1
                continue
            r = np.asarray(
                cold_coll.get(m.name).scorer.anomaly_arrays(Xp)[series],
                np.float32,
            )
            q = np.asarray(
                warm_coll.get(m.name).scorer.anomaly_arrays(Xp)[series],
                np.float32,
            )
            err = float(np.max(np.abs(r - q))) / max(
                float(np.max(np.abs(r))), 1e-6
            )
            errs.append(err)
            if err > max_tol:
                failed.append(m.name)
    med = float(np.median(errs)) if errs else 0.0
    worst = float(np.max(errs)) if errs else 0.0
    parity_ok = med <= median_tol and not failed
    out[f"refresh_parity_sampled_{size}"] = len(sample)
    out[f"refresh_parity_attested_fallbacks_{size}"] = attested
    out[f"refresh_parity_median_{size}"] = round(med, 4)
    out[f"refresh_parity_max_{size}"] = round(worst, 4)
    out[f"refresh_parity_failed_{size}"] = failed
    out[f"refresh_parity_ok_{size}"] = parity_ok
    log(f"refresh subset {size} parity: {series} median {med:.4f} "
        f"max {worst:.4f} over {len(errs)} machines "
        f"({attested} attested fallback(s), {len(failed)} out of bounds)")
    return parity_ok


def bench_refresh(out: dict) -> None:
    """ISSUE 13 acceptance: drift-driven incremental refresh — warm-start
    subset rebuilds make retraining O(drifted), not O(fleet).

    Protocol (docs/perf.md "Refresh"): build a BENCH_REFRESH_FLEET-machine
    project cold into one v2 store, then for each subset size in
    BENCH_REFRESH_SUBSETS (default 32 and 512) run interleaved best-of-N
    rebuilds of that subset: COLD into a fresh scratch store (full data
    assembly + full-epoch training, what a non-incremental pipeline pays
    for the same machines) vs WARM into the live store
    (``build_project(subset, warm_start=True)``: previous-generation
    params seed a reduced-epoch fit, published via delta writes).  The
    measured operating point is one warm epoch over a 24-epoch base
    (``GORDO_REFRESH_EPOCH_FRACTION=0.04``) — builds here are fully
    deterministic (cold-vs-cold score diff is exactly 0), so parity
    measures nothing but the warm refit's movement.  Gates per subset:
    warm wall-clock ≤ 0.5× cold, and ≪ the full-fleet build; per-machine
    score parity between the first warm rebuild's artifacts and a cold
    reference within the bf16-suite bounds (total-anomaly-score
    max-normalized on the suite's standard-normal input: median ≤ 3%,
    per-machine max ≤ 10%) — machines whose metadata attests a cold
    fallback are counted, not compared.  Finally one end-to-end
    drift→flip→reloaded cycle against a live serving collection: a real
    drifting score-sketch rollup lands, ``refresh_once`` selects and
    warm-rebuilds exactly that machine, and the latency until
    ``maybe_delta_reload`` has the new generation's params on device is
    reported as ``refresh_drift_to_live_s``.
    """
    import jax

    from gordo_tpu import artifacts, telemetry
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.refresh.loop import RefreshConfig, refresh_once
    from gordo_tpu.serve.server import ModelCollection
    from gordo_tpu.telemetry import fleet_health as fh

    fleet_n = int(os.environ.get("BENCH_REFRESH_FLEET", "576"))
    subsets = [
        int(s) for s in
        os.environ.get("BENCH_REFRESH_SUBSETS", "32,512").split(",")
        if s.strip()
    ]
    subsets = [s for s in subsets if s <= fleet_n]
    reps = int(os.environ.get("BENCH_REFRESH_REPS", "2"))
    bucket = 64
    model = {
        "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.pipeline.Pipeline": {
                    "steps": [
                        "gordo_tpu.ops.scalers.MinMaxScaler",
                        {"gordo_tpu.models.estimator.AutoEncoder": {
                            # converged base models: warm refits (6
                            # epochs = ceil(24 * 0.25)) start near the
                            # optimum, so the parity comparison below
                            # measures publish fidelity, not leftover
                            # training noise
                            "kind": "feedforward_hourglass",
                            "epochs": 24,
                            "batch_size": 64,
                        }},
                    ],
                },
            },
        },
    }
    machines = make_machines(fleet_n, n_tags=4, model=model,
                             prefix="bench-rf")
    reg = telemetry.FLEET_HEALTH

    def counter(name: str) -> float:
        metric = telemetry.REGISTRY.snapshot()["metrics"].get(name) or {}
        return float(sum(metric.get("series", {}).values()))

    def build(mods, dest, **kw):
        t0 = time.perf_counter()
        result = build_project(
            mods, dest, max_bucket_size=bucket, artifact_format="v2", **kw
        )
        dt = time.perf_counter() - t0
        if result.failed:
            raise RuntimeError(
                f"refresh bench build failed: {dict(list(result.failed.items())[:3])}"
            )
        return result, dt

    d = tempfile.mkdtemp(prefix="gordo-bench-refresh-")
    scratch: "list[str]" = []
    saved_frac = os.environ.get("GORDO_REFRESH_EPOCH_FRACTION")
    # the measured operating point: ceil(24 * 0.04) = 1 warm epoch
    os.environ["GORDO_REFRESH_EPOCH_FRACTION"] = os.environ.get(
        "BENCH_REFRESH_EPOCH_FRACTION", "0.04"
    )
    try:
        _, full_s = build(machines, d)
        out["refresh_fleet_machines"] = fleet_n
        out["refresh_full_fleet_s"] = round(full_s, 2)
        out["refresh_full_fleet_models_per_hour"] = round(
            fleet_n / full_s * 3600.0, 1
        )
        log(f"refresh: full fleet {fleet_n} machines cold in {full_s:.1f}s "
            f"({fleet_n / full_s * 3600.0:.0f} models/h)")

        # parity input mirrors the bf16 suite (bench_serving_precision /
        # tests/test_serving_precision.py): standard-normal rows,
        # max-normalized error on the serving-facing anomaly score.
        # Builds here are deterministic (two cold builds score
        # identically), so the cold reference is exact and every diff is
        # the warm refit's movement.  Bounds: median ≤ 3%, per-machine
        # max ≤ 10%.
        parity_series = "total-anomaly-score"
        parity_median_tol, parity_max_tol = 0.03, 0.10
        Xp = np.random.default_rng(0).standard_normal((1024, 4)).astype(
            np.float32
        )
        all_ok = True
        for size in subsets:
            subset = machines[:size]
            # parity first: one cold reference build (which also
            # jit-warms the cold program), then the FIRST warm rebuild
            # over the pristine store — exactly one warm epoch of
            # movement, the steady-state refresh operating point
            cold_dir = tempfile.mkdtemp(
                prefix=f"gordo-bench-refresh-cold{size}-"
            )
            scratch.append(cold_dir)
            _, _ = build(subset, cold_dir)
            warm_result, _ = build(subset, d, warm_start=True)
            parity_ok = _refresh_parity(
                out, size, d, cold_dir, subset, Xp, parity_series,
                parity_median_tol, parity_max_tol,
            )
            # timing: interleaved best-of-N at steady state (both
            # programs are jit-warm from the parity builds above)
            cold_s: "list[float]" = []
            warm_s: "list[float]" = []
            for rep in range(reps):
                rep_dir = tempfile.mkdtemp(
                    prefix=f"gordo-bench-refresh-cold{size}-"
                )
                scratch.append(rep_dir)
                _, dt = build(subset, rep_dir)
                cold_s.append(dt)
                shutil.rmtree(rep_dir, ignore_errors=True)
                scratch.remove(rep_dir)
                warm_result, dt = build(subset, d, warm_start=True)
                warm_s.append(dt)
            cold_best, warm_best = min(cold_s), min(warm_s)
            ratio = warm_best / max(cold_best, 1e-9)
            out[f"refresh_cold_subset_s_{size}"] = round(cold_best, 2)
            out[f"refresh_warm_subset_s_{size}"] = round(warm_best, 2)
            out[f"refresh_cold_models_per_hour_{size}"] = round(
                size / cold_best * 3600.0, 1
            )
            out[f"refresh_warm_models_per_hour_{size}"] = round(
                size / warm_best * 3600.0, 1
            )
            out[f"refresh_warm_over_cold_{size}"] = round(ratio, 3)
            out[f"refresh_warm_halved_ok_{size}"] = warm_best <= 0.5 * cold_best
            out[f"refresh_warm_vs_full_fleet_{size}"] = round(
                warm_best / max(full_s, 1e-9), 3
            )
            out[f"refresh_warm_fallbacks_{size}"] = len(
                warm_result.warm_fallbacks
            )
            log(f"refresh subset {size}: cold {cold_best:.1f}s vs warm "
                f"{warm_best:.1f}s ({ratio:.2f}x, "
                f"{len(warm_result.warm_fallbacks)} fallback(s))")

            all_ok = all_ok and parity_ok and warm_best <= 0.5 * cold_best
            shutil.rmtree(cold_dir, ignore_errors=True)
            scratch.remove(cold_dir)

        # end-to-end: drifting rollup lands → refresh_once warm-rebuilds
        # exactly that machine → the live collection delta-reloads it.
        target = machines[0].name
        names = [m.name for m in machines]
        reg.clear(names)
        coll = ModelCollection.from_directory(d, project="bench-refresh")
        with reg.suspended():
            fleet = coll.fleet_scorer
            for b in fleet.buckets:
                jax.block_until_ready(jax.tree.leaves(b.params))
        gen_before = artifacts.read_generation(d)
        rngh = np.random.default_rng(7)
        fh.write_rollup(d, {
            "gordo-fleet-health": 1,
            "machines": {target: {
                "baseline": fh.sketch_from_scores(
                    rngh.lognormal(0.0, 1.0, 4000), ts=0.0
                ).to_doc(),
                "live": fh.sketch_from_scores(
                    rngh.lognormal(3.0, 1.0, 2000), ts=0.0
                ).to_doc(),
            }},
        })
        rcfg = RefreshConfig(
            machines=machines, output_dir=d, project="bench-refresh",
            hysteresis=1, cooldown_seconds=0,
            build_kwargs={"max_bucket_size": bucket,
                          "artifact_format": "v2"},
        )
        d0 = artifacts.device_put_count()
        t0 = time.perf_counter()
        with reg.suspended():
            summary = refresh_once(rcfg)
            changes = coll.maybe_delta_reload()
            for b in coll.fleet_scorer.buckets:
                jax.block_until_ready(jax.tree.leaves(b.params))
        e2e = time.perf_counter() - t0
        flip_ok = (
            summary.get("outcome") == "rebuilt"
            and summary.get("rebuilt") == [target]
            and summary.get("generation") == gen_before + 1
            and coll.generation == gen_before + 1
            and changes.get("reloaded") == [target]
        )
        out["refresh_drift_to_live_s"] = round(e2e, 2)
        out["refresh_e2e_outcome"] = summary.get("outcome")
        out["refresh_e2e_rebuilt"] = summary.get("rebuilt")
        out["refresh_e2e_reloaded"] = changes.get("reloaded")
        out["refresh_e2e_device_puts"] = artifacts.device_put_count() - d0
        out["refresh_e2e_flip_ok"] = flip_ok
        out["refresh_cycles_total"] = counter("gordo_refresh_cycles_total")
        out["refresh_machines_total"] = counter("gordo_refresh_machines_total")
        out["refresh_ok"] = all_ok and flip_ok
        log(f"refresh e2e: drift→flip→reloaded in {e2e:.2f}s "
            f"(outcome {summary.get('outcome')}, reloaded "
            f"{changes.get('reloaded')}, flip_ok {flip_ok})")
    finally:
        if saved_frac is None:
            os.environ.pop("GORDO_REFRESH_EPOCH_FRACTION", None)
        else:
            os.environ["GORDO_REFRESH_EPOCH_FRACTION"] = saved_frac
        shutil.rmtree(d, ignore_errors=True)
        for s in scratch:
            shutil.rmtree(s, ignore_errors=True)


# ---------------------------------------------------------------------------
# backfill bench
# ---------------------------------------------------------------------------

def _backfill_fleet_dir(model, metadata, names: "list[str]") -> str:
    """A v2 pack dir replicating one built machine across ``names`` in
    512-machine packs (the artifact-plane layout the 10k time-to-ready
    bench uses)."""
    from gordo_tpu import artifacts

    art_dir = tempfile.mkdtemp(prefix="gordo-bench-backfill-")
    for start in range(0, len(names), 512):
        part = names[start: start + 512]
        metas = []
        for name in part:
            md = dict(metadata)
            md["name"] = name
            metas.append(md)
        artifacts.write_pack(art_dir, part, [model] * len(part), metas)
    return art_dir


def bench_backfill(out: dict) -> None:
    """ISSUE 14 acceptance: the backfill plane's archive path vs the only
    alternative the reference had — replaying history through the HTTP
    serving tier.

    Protocol (docs/perf.md "Backfill"):

    - one trained machine replicated across N names (512 and 10k), v2
      packs, identical tag lists — so the provider cost collapses to one
      fetch on BOTH paths and the comparison is codec/transport, not
      data generation;
    - archive path: a warmup ``run_backfill`` over one preceding chunk
      (stacked-program compiles land in the in-process jit registry),
      then a measured run over the full range.  The reported rate is the
      summary's END-TO-END number — artifact loads, provider fetch,
      chunk slicing, dispatch, assemble, mmap write and fsync all
      inside the clock;
    - HTTP comparators against a REAL ``run-server`` subprocess over
      the same artifact dir, same windows, production bulk msgpack
      wire, bodies sized by the client's own ``bulk_rows_budget`` (the
      payload contract any replay client must respect).  Two numbers,
      reported separately:

      * ``http_wire``: raw bulk posts with responses decoded and
        DISCARDED, a few in flight so the server never starves — the
        transport-only saturation floor no real replay can beat;
      * ``http_replay``: the actual ``Client`` (``use_bulk=True``)
        replaying the range and materializing per-machine score frames
        — the pre-backfill way to score history over HTTP (forwarding/
        persistence left OFF, which favors HTTP: the archive's clock
        includes writing scores to disk).

      Server startup, model loading, and warmup rounds are excluded
      from the HTTP clocks (the archive number includes its own);
    - attestation: device transfers per chunk from the run summary
      (one stacked host->device staging per bucket program per chunk;
      the replicated fleet is structurally ONE bucket, so the gate is
      exactly 1.0);
    - gate: archive-path samples/s >= 3x the ``Client`` HTTP replay at
      512 machines on CPU.  The wire floor is recorded alongside so
      the transport-vs-materialization split stays visible.

    Honesty note: with one visible core the replay's client-side codec
    timeshares with the server (in production the client is another
    host), but the dominant replay costs — server unpackb/packb, the
    budget-bounded body sizes, per-request round trips — are inherent
    to the HTTP plane; ``cpu_cores`` is recorded alongside.
    """
    import asyncio
    import socket
    import urllib.request

    import aiohttp
    import pandas as pd

    from gordo_tpu.batch import BackfillConfig, chunk_windows, run_backfill
    from gordo_tpu.client.io import bulk_rows_budget
    from gordo_tpu.dataset import dataset_from_metadata
    from gordo_tpu.serve import codec

    n_small = int(os.environ.get("BENCH_BACKFILL_MACHINES", "512"))
    small_rows = int(os.environ.get("BENCH_BACKFILL_CHUNK_ROWS", "2048"))
    small_chunks = int(os.environ.get("BENCH_BACKFILL_CHUNKS", "8"))
    n_large = int(os.environ.get("BENCH_BACKFILL_LARGE_MACHINES", "10000"))
    large_rows = int(os.environ.get("BENCH_BACKFILL_LARGE_CHUNK_ROWS", "256"))
    large_chunks = int(os.environ.get("BENCH_BACKFILL_LARGE_CHUNKS", "2"))
    concurrency = int(os.environ.get("BENCH_BACKFILL_HTTP_CONCURRENCY", "3"))
    out["cpu_cores"] = os.cpu_count()

    model, metadata = _build_serving_model()
    resolution = (metadata.get("dataset") or {}).get("resolution", "10min")
    step = pd.tseries.frequencies.to_offset(resolution)

    procs: "list[subprocess.Popen]" = []
    logs: "list[str]" = []

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(port: int, art_dir: str) -> subprocess.Popen:
        env = dict(os.environ)
        env.pop("GORDO_SERVE_SHARD", None)
        env["JAX_PLATFORMS"] = "cpu"
        log_path = os.path.join(art_dir, f"server-{port}.log")
        logs.append(log_path)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "gordo_tpu.cli.cli", "run-server",
                "--model-dir", art_dir, "--project", "bench",
                "--host", "127.0.0.1", "--port", str(port),
                "--rescan-interval", "0",
            ],
            env=env,
            stdout=open(log_path, "w"), stderr=subprocess.STDOUT,
        )
        procs.append(proc)
        return proc

    def wait_ready(port: int, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        url = f"http://127.0.0.1:{port}/healthz"
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return
            except Exception:
                time.sleep(0.25)
        raise RuntimeError(f"backfill server on :{port} never became ready")

    def stop(to_stop: "list[subprocess.Popen]") -> None:
        for proc in to_stop:
            proc.terminate()
        for proc in to_stop:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    headers = {
        "Content-Type": codec.MSGPACK_CONTENT_TYPE,
        "Accept": codec.MSGPACK_CONTENT_TYPE,
    }

    def archive_run(art_dir: str, start, end, rows: int) -> dict:
        """Warmup run over the chunk preceding ``start`` (same stacked
        geometry -> compiles land), then the measured end-to-end run."""
        warm_dir = tempfile.mkdtemp(prefix="gordo-bench-bf-warm-")
        meas_dir = tempfile.mkdtemp(prefix="gordo-bench-bf-arch-")
        try:
            run_backfill(BackfillConfig(
                model_dir=art_dir, start=str(start - step * rows),
                end=str(start), archive_dir=warm_dir, project="bench",
                chunk_rows=rows,
            ))
            return run_backfill(BackfillConfig(
                model_dir=art_dir, start=str(start), end=str(end),
                archive_dir=meas_dir, project="bench", chunk_rows=rows,
            ))
        finally:
            shutil.rmtree(warm_dir, ignore_errors=True)
            shutil.rmtree(meas_dir, ignore_errors=True)

    def http_wire_floor(
        port: int, names: "list[str]", start, end, rows: int
    ) -> dict:
        """The same windows through a real server's bulk msgpack route,
        bodies sized by the client's samples budget, responses decoded
        and DISCARDED — the transport-only floor no real replay client
        can beat (a replay has to materialize and keep its scores)."""
        dataset = dataset_from_metadata(
            metadata["dataset"], str(start), str(end)
        )
        X, _ = dataset.get_data()
        budget_rows = bulk_rows_budget(len(names) * X.shape[1], rows)
        slabs: "list[np.ndarray]" = []
        for t0, t1 in chunk_windows(start, end, resolution, rows):
            lo, hi = X.index.searchsorted(t0), X.index.searchsorted(t1)
            arr = X.iloc[lo:hi].to_numpy(np.float32)
            for r0 in range(0, len(arr), budget_rows):
                if len(arr[r0: r0 + budget_rows]):
                    slabs.append(arr[r0: r0 + budget_rows])

        url = (
            f"http://127.0.0.1:{port}"
            "/gordo/v0/bench/_bulk/anomaly/prediction"
        )

        async def drive() -> "tuple[int, float]":
            samples = 0
            timeout = aiohttp.ClientTimeout(total=900)
            sem = asyncio.Semaphore(concurrency)
            async with aiohttp.ClientSession(timeout=timeout) as session:

                async def post(slab: np.ndarray, measured: bool) -> None:
                    nonlocal samples
                    # packb under the semaphore: at most ``concurrency``
                    # bodies alive, encode overlapped with server work
                    async with sem:
                        body = codec.packb({"X": {n: slab for n in names}})
                        async with session.post(
                            url, data=body, headers=headers
                        ) as resp:
                            raw = await resp.read()
                    if resp.status != 200:
                        raise RuntimeError(
                            f"bulk replay -> {resp.status}: {raw[:160]!r}"
                        )
                    data = codec.unpackb(raw)["data"]
                    if measured:
                        for res in data.values():
                            samples += int(
                                np.asarray(res["tag-anomaly-scores"]).size
                            )

                # warmup: head + tail slab shapes land the server compiles
                await asyncio.gather(
                    post(slabs[0], False), post(slabs[-1], False)
                )
                t0 = time.perf_counter()
                await asyncio.gather(*(post(s, True) for s in slabs))
                return samples, time.perf_counter() - t0

        samples, dt = asyncio.run(drive())
        return {
            "samples": samples,
            "seconds": dt,
            "samples_per_sec": samples / dt if dt > 0 else 0.0,
            "rows_per_request": budget_rows,
            "n_requests": len(slabs),
        }

    def client_replay(port: int, start, end, rows: int) -> dict:
        """THE pre-backfill alternative: the real ``Client`` replaying the
        range over the bulk msgpack wire and materializing per-machine
        score frames — what scoring history over HTTP actually costs.
        Prediction forwarding/persistence is left OFF (favors HTTP: the
        archive path's clock includes writing its scores to disk)."""
        from gordo_tpu.client import Client

        client = Client(
            "bench", port=port, use_bulk=True, batch_size=rows,
        )
        t0 = time.perf_counter()
        results = client.predict(str(start), str(end))
        dt = time.perf_counter() - t0
        samples = 0
        for res in results:
            if not res.ok:
                raise RuntimeError(
                    f"client replay failed for {res.name}: "
                    f"{res.error_messages}"
                )
            frame = res.predictions
            n_tag_cols = sum(
                1 for c in frame.columns if c[0] == "tag-anomaly-scores"
            )
            samples += len(frame) * n_tag_cols
        return {
            "samples": samples,
            "seconds": dt,
            "samples_per_sec": samples / dt if dt > 0 else 0.0,
            "machines": len(results),
        }

    def scenario(
        n: int, rows: int, chunks: int, ready_timeout_s: float,
        with_client_replay: bool,
    ) -> "float | None":
        names = [f"bf-{i:05d}" for i in range(n)]
        art_dir = _backfill_fleet_dir(model, metadata, names)
        server = None
        try:
            start = pd.Timestamp("2024-01-01T00:00:00Z")
            end = start + step * (rows * chunks)
            summary = archive_run(art_dir, start, end, rows)
            key = f"backfill_{n}"
            archive_sps = summary["samples-per-second"]
            out[f"{key}_samples_per_sec"] = round(archive_sps)
            out[f"{key}_samples"] = summary["samples"]
            out[f"{key}_seconds"] = summary["seconds"]
            out[f"{key}_chunks"] = summary["chunks-ok"]
            out[f"{key}_chunk_rows"] = rows
            per_chunk = (
                summary["device-transfers"] / max(1, summary["chunks-ok"])
            )
            out[f"{key}_device_transfers_per_chunk"] = round(per_chunk, 3)
            out[f"{key}_one_transfer_per_chunk_ok"] = per_chunk == 1.0
            log(f"backfill archive @{n}: {archive_sps:,.0f} samples/s "
                f"({summary['samples']:,} samples / {summary['seconds']}s, "
                f"{per_chunk:.1f} transfers/chunk)")

            port = free_port()
            server = spawn(port, art_dir)
            wait_ready(port, ready_timeout_s)

            wire = http_wire_floor(port, names, start, end, rows)
            out[f"{key}_http_wire_samples_per_sec"] = round(
                wire["samples_per_sec"]
            )
            out[f"{key}_http_rows_per_request"] = wire["rows_per_request"]
            out[f"{key}_http_requests"] = wire["n_requests"]
            out[f"{key}_vs_http_wire_speedup"] = round(
                archive_sps / wire["samples_per_sec"], 3
            )
            log(f"backfill http wire floor @{n}: "
                f"{wire['samples_per_sec']:,.0f} samples/s "
                f"({wire['n_requests']} requests of "
                f"{wire['rows_per_request']} rows) -> archive "
                f"{archive_sps / wire['samples_per_sec']:.2f}x")

            if not with_client_replay:
                return None
            replay = client_replay(port, start, end, rows)
            out[f"{key}_http_replay_samples_per_sec"] = round(
                replay["samples_per_sec"]
            )
            out[f"{key}_http_replay_samples"] = replay["samples"]
            out[f"{key}_http_replay_seconds"] = round(replay["seconds"], 3)
            speedup = archive_sps / replay["samples_per_sec"]
            out[f"{key}_vs_http_replay_speedup"] = round(speedup, 3)
            log(f"backfill client replay @{n}: "
                f"{replay['samples_per_sec']:,.0f} samples/s "
                f"({replay['samples']:,} samples / {replay['seconds']:.1f}s)"
                f" -> archive {speedup:.2f}x")
            return speedup
        finally:
            if server is not None:
                stop([server])
            shutil.rmtree(art_dir, ignore_errors=True)

    try:
        speedup = scenario(
            n_small, small_rows, small_chunks, 180.0,
            with_client_replay=True,
        )
        # the acceptance gate: archive path >= 3x replaying the same
        # range through the HTTP tier at 512 machines on CPU
        out["backfill_ge_3x_http_ok"] = speedup >= 3.0
        log(f"backfill gate @{n_small}: {speedup:.2f}x >= 3x -> "
            f"{'PASS' if speedup >= 3.0 else 'FAIL'}")
        if n_large:
            # client-side frame materialization at 10k machines x tiny
            # budget bodies takes tens of minutes — the wire floor is
            # the recorded comparator at fleet scale
            scenario(
                n_large, large_rows, large_chunks, 420.0,
                with_client_replay=False,
            )
    except Exception:
        for log_path in logs:
            try:
                with open(log_path) as fh:
                    tail = fh.read()[-2000:]
                if tail:
                    log(f"--- {log_path} tail ---\n{tail}")
            except OSError:
                pass
        raise
    finally:
        stop(procs)


def bench_scores_lifecycle(out: dict) -> None:
    """ISSUE 16 acceptance: the score-archive lifecycle at fleet-year
    scale — compaction throughput vs raw mmap scan speed, aggregate
    byte-identity across compaction, and the ``/scores/aggregate``
    pushdown vs client-side fetch-and-aggregate over ``score_history``.

    Protocol (docs/perf.md "Archive lifecycle"):

    - a synthetic 512-machine archive: 8 chunks x 2048 rows at 30min
      resolution (~341 days — a fleet-year of scored history; ~75M
      scored samples, ~370 MB of GSA1 columns) written through the REAL
      ``write_chunk`` path (fsync'd segments + completion records);
    - raw scan: every byte of every data segment summed through the
      same ``np.memmap`` reads the query plane uses (best of 2, warm
      page cache — the comparator compaction has to keep up with);
    - compaction: ``compact_scores`` at a 90d partition (3 periods of
      2-3 chunks each; the trailing single-chunk period stays as a
      chunk file — eligibility needs >= 2 segments).  Throughput =
      bytes moved (input scanned + output fsync'd) / wall clock, gated
      >= 0.5x the scan rate; the write-only rate and the medium's
      measured durable-write ceiling are recorded alongside (the fsync
      before each index flip pins the write side to the disk, so the
      honest comparison needs both numbers);
    - aggregates (count/mean/max/p50/p90/p99/exceed over 7d periods)
      run before and after compaction and must be BYTE-identical;
    - pushdown: a real ``run-server`` subprocess over a 1-model v2 pack
      dir holding the archive; ``client.score_summary`` end-to-end
      (HTTP + server-side mmap scan + GSB1 columnar wire + decode) vs
      the pre-r20 client-side path — ``client.score_history`` (LOCAL
      mmap reads, zero wire cost: a handicap the gate absorbs)
      materializing 512 frames + pandas groupby computing the SAME
      stats.  Gate: pushdown >= 10x faster end-to-end.
    """
    import socket
    import urllib.request

    import pandas as pd

    from gordo_tpu.batch import (
        AGGREGATE_STATS,
        ScoreArchive,
        compact_scores,
        gc_scores,
        plan_compaction,
    )
    from gordo_tpu.client import Client

    n_machines = int(os.environ.get("BENCH_SCORES_MACHINES", "512"))
    chunk_rows = int(os.environ.get("BENCH_SCORES_CHUNK_ROWS", "2048"))
    n_chunks = int(os.environ.get("BENCH_SCORES_CHUNKS", "8"))
    n_tags = int(os.environ.get("BENCH_SCORES_TAGS", "8"))
    agg_period = "7d"
    threshold = 1.0
    out["cpu_cores"] = os.cpu_count()

    model, metadata = _build_serving_model()
    art_dir = _backfill_fleet_dir(model, metadata, ["scores-m-000"])
    # the stage measures SOFTWARE throughput (compactor and scan on the
    # same medium); a device-independent medium keeps the ratio from
    # collapsing into this container's fsync bandwidth, which is probed
    # and recorded separately against the real disk below.
    shm = os.environ.get("BENCH_SCORES_DIR", "/dev/shm")
    if os.path.isdir(shm) and os.access(shm, os.W_OK):
        shm_dir = tempfile.mkdtemp(prefix="gordo-bench-scores-", dir=shm)
        for entry in os.listdir(art_dir):
            shutil.move(os.path.join(art_dir, entry),
                        os.path.join(shm_dir, entry))
        os.rmdir(art_dir)
        art_dir = shm_dir
        out["scores_archive_medium"] = "tmpfs"
    else:
        out["scores_archive_medium"] = "disk"
    procs: "list[subprocess.Popen]" = []
    logs: "list[str]" = []

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(port: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.pop("GORDO_SERVE_SHARD", None)
        env["JAX_PLATFORMS"] = "cpu"
        log_path = os.path.join(art_dir, f"server-{port}.log")
        logs.append(log_path)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "gordo_tpu.cli.cli", "run-server",
                "--model-dir", art_dir, "--project", "bench",
                "--host", "127.0.0.1", "--port", str(port),
                "--rescan-interval", "0",
            ],
            env=env,
            stdout=open(log_path, "w"), stderr=subprocess.STDOUT,
        )
        procs.append(proc)
        return proc

    def wait_ready(port: int, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        url = f"http://127.0.0.1:{port}/healthz"
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return
            except Exception:
                time.sleep(0.25)
        raise RuntimeError(f"scores server on :{port} never became ready")

    def stop(to_stop: "list[subprocess.Popen]") -> None:
        for proc in to_stop:
            proc.terminate()
        for proc in to_stop:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    try:
        # -- build the fleet-year archive through the real write path ---
        step = pd.Timedelta("30min")
        step_ns = int(step.value)
        start = pd.Timestamp("2024-01-01T00:00:00Z")
        names = [f"scm-{i:04d}" for i in range(n_machines)]
        arch = ScoreArchive.create(
            art_dir, project="bench", start=str(start),
            end=str(start + step * (chunk_rows * n_chunks)),
            resolution="30min", chunk_rows=chunk_rows,
            n_chunks=n_chunks, dtype="float32", machines=names,
        )
        tags = [f"t{j}" for j in range(n_tags)]
        rng = np.random.default_rng(3)
        t0_ns = int(start.value)
        span_ns = chunk_rows * step_ns
        t_build = time.perf_counter()
        for c in range(n_chunks):
            idx = (
                t0_ns + c * span_ns
                + step_ns * np.arange(chunk_rows, dtype=np.int64)
            )
            tot = rng.random((n_machines, chunk_rows), dtype=np.float32) * 3
            tag = rng.random(
                (n_machines, chunk_rows, n_tags), dtype=np.float32
            )
            arch.write_chunk(c, {
                name: {
                    "index-ns": idx,
                    "total-anomaly-score": tot[i],
                    "tag-anomaly-scores": tag[i],
                    "tags": tags,
                }
                for i, name in enumerate(names)
            })
        build_s = time.perf_counter() - t_build
        rows_total = n_machines * chunk_rows * n_chunks
        out["scores_machines"] = n_machines
        out["scores_rows"] = rows_total
        out["scores_samples"] = rows_total * (n_tags + 1)
        out["scores_archive_build_s"] = round(build_s, 2)

        # -- raw mmap scan floor (best of 2, warm cache) ----------------
        def mmap_scan() -> "tuple[int, float]":
            t0 = time.perf_counter()
            nbytes = 0
            sink = 0
            for path in arch._data_segments():
                buf = np.memmap(path, dtype=np.uint8, mode="r")
                sink += int(np.add.reduce(buf, dtype=np.int64))
                nbytes += buf.size
            return nbytes, time.perf_counter() - t0

        scan_bytes, scan_1 = mmap_scan()
        _, scan_2 = mmap_scan()
        scan_s = min(scan_1, scan_2)
        scan_bps = scan_bytes / scan_s if scan_s > 0 else 0.0
        out["scores_archive_mb"] = round(scan_bytes / 1e6, 1)
        out["scores_scan_mb_per_s"] = round(scan_bps / 1e6, 1)
        log(f"scores scan: {scan_bytes / 1e6:,.0f} MB in {scan_s:.2f}s "
            f"({scan_bps / 1e6:,.0f} MB/s)")

        # -- aggregate before compaction (also the local-latency point) -
        t0 = time.perf_counter()
        agg_pre = arch.aggregate(
            stats=list(AGGREGATE_STATS), period=agg_period,
            threshold=threshold,
        )
        out["scores_aggregate_local_s"] = round(time.perf_counter() - t0, 3)

        # -- durable-write ceiling of the real disk -------------------
        # a production compactor must fsync every period file before
        # the index flip, so on spinning/virtio media its write side is
        # device-bound.  Probe the container's disk with a dd-style
        # write+fsync so the report carries that ceiling next to the
        # software throughput measured above it.
        probe_path = os.path.join(
            tempfile.gettempdir(), "gordo_bench_disk_probe.tmp"
        )
        probe_mb = 128
        block = np.random.default_rng(0).integers(
            0, 256, probe_mb * 1_000_000, dtype=np.uint8
        ).tobytes()
        t0 = time.perf_counter()
        with open(probe_path, "wb") as fh:
            fh.write(block)
            fh.flush()
            os.fsync(fh.fileno())
        disk_bps = len(block) / (time.perf_counter() - t0)
        os.unlink(probe_path)
        del block
        out["scores_disk_write_mb_per_s"] = round(disk_bps / 1e6, 1)

        # -- compaction vs the scan floor -------------------------------
        # throughput counts the bytes the compactor MOVES per wall
        # second: every input byte scanned off the chunk segments plus
        # every output byte written durably — the two directions of
        # compaction I/O, both recorded separately below.
        eligible = plan_compaction(art_dir, period="90d")["eligible"]
        read_bytes = sum(
            os.path.getsize(os.path.join(arch.directory, fname))
            for info in eligible.values()
            for _c, _s, fname in info["segments"]
        )
        t0 = time.perf_counter()
        summary = compact_scores(art_dir, period="90d")
        compact_s = time.perf_counter() - t0
        write_bps = (
            summary["bytes-written"] / compact_s if compact_s > 0 else 0.0
        )
        io_bps = (
            (read_bytes + summary["bytes-written"]) / compact_s
            if compact_s > 0 else 0.0
        )
        ratio = io_bps / scan_bps if scan_bps > 0 else 0.0
        out["scores_compact_periods"] = summary["periods-compacted"]
        out["scores_compact_segments_merged"] = summary["segments-merged"]
        out["scores_compact_mb_read"] = round(read_bytes / 1e6, 1)
        out["scores_compact_mb_written"] = round(
            summary["bytes-written"] / 1e6, 1
        )
        out["scores_compact_s"] = round(compact_s, 2)
        out["scores_compact_write_mb_per_s"] = round(write_bps / 1e6, 1)
        out["scores_compact_vs_disk_ratio"] = round(
            write_bps / disk_bps, 3
        ) if disk_bps > 0 else None
        out["scores_compact_mb_per_s"] = round(io_bps / 1e6, 1)
        out["scores_compact_vs_scan_ratio"] = round(ratio, 3)
        out["scores_compact_ge_half_scan_ok"] = ratio >= 0.5
        log(f"scores compact: {summary['periods-compacted']} periods "
            f"({len(eligible)} planned), "
            f"{summary['bytes-written'] / 1e6:,.0f} MB written + "
            f"{read_bytes / 1e6:,.0f} MB scanned in {compact_s:.2f}s "
            f"({io_bps / 1e6:,.0f} MB/s moved, {ratio:.2f}x scan; "
            f"write side {write_bps / 1e6:,.0f} MB/s vs disk "
            f"{disk_bps / 1e6:,.0f} MB/s) -> "
            f"{'PASS' if ratio >= 0.5 else 'FAIL'}")

        # -- byte-identity across compaction ----------------------------
        agg_post = arch.aggregate(
            stats=list(AGGREGATE_STATS), period=agg_period,
            threshold=threshold,
        )
        identical = agg_pre["periods"] == agg_post["periods"] and all(
            agg_pre["stats"][k].tobytes() == agg_post["stats"][k].tobytes()
            for k in agg_pre["stats"]
        )
        out["scores_aggregate_bytes_identical_ok"] = identical
        log(f"scores aggregate byte-identity across compaction: "
            f"{'PASS' if identical else 'FAIL'}")

        # -- pushdown vs client-side fetch-and-aggregate ----------------
        port = free_port()
        spawn(port)
        wait_ready(port, 240.0)
        client = Client("bench", port=port)
        client.score_summary(machines=names[:1], period=agg_period)  # warm
        t0 = time.perf_counter()
        doc = client.score_summary(
            stats=list(AGGREGATE_STATS), period=agg_period,
            threshold=threshold,
        )
        push_s = time.perf_counter() - t0
        resp_bytes = sum(
            np.asarray(a).nbytes
            for stats_map in doc["data"].values()
            for a in stats_map.values()
        )
        midx = {n: i for i, n in enumerate(agg_post["machines"])}
        parity = all(
            np.array_equal(
                np.asarray(doc["data"][n][k]), agg_post["stats"][k][midx[n]]
            )
            for n in doc["data"] for k in AGGREGATE_STATS
        )
        out["scores_pushdown_parity_ok"] = parity

        t0 = time.perf_counter()
        frames = client.score_history(archive_dir=art_dir)
        fetched = 0
        for frame in frames.values():
            fetched += int(frame.size)
            s = frame["total-anomaly-score"]
            grouped = s.groupby(pd.Grouper(freq="7D"))
            grouped.agg(["count", "mean", "max"])
            grouped.quantile([0.5, 0.9, 0.99])
            s.gt(threshold).groupby(pd.Grouper(freq="7D")).sum()
        fetch_s = time.perf_counter() - t0
        speedup = fetch_s / push_s if push_s > 0 else 0.0
        out["scores_pushdown_s"] = round(push_s, 3)
        out["scores_pushdown_response_kb"] = round(resp_bytes / 1e3, 1)
        out["scores_pushdown_periods"] = len(doc["periods"])
        out["scores_fetch_aggregate_s"] = round(fetch_s, 2)
        out["scores_fetch_aggregate_cells"] = fetched
        out["scores_pushdown_speedup"] = round(speedup, 2)
        out["scores_pushdown_ge_10x_ok"] = speedup >= 10.0
        log(f"scores pushdown: {push_s:.3f}s "
            f"({resp_bytes / 1e3:,.0f} KB over the wire) vs "
            f"fetch-and-aggregate {fetch_s:.2f}s "
            f"({fetched:,} frame cells) -> {speedup:.1f}x >= 10x "
            f"{'PASS' if speedup >= 10.0 else 'FAIL'}")

        # -- retention (destructive: runs last) -------------------------
        now_s = (start + step * (chunk_rows * n_chunks)).timestamp()
        t0 = time.perf_counter()
        g = gc_scores(art_dir, keep_days=180, now=now_s)
        out["scores_gc_s"] = round(time.perf_counter() - t0, 3)
        out["scores_gc_segments_deleted"] = g["segments-deleted"]
        out["scores_gc_mb_reclaimed"] = round(g["bytes-reclaimed"] / 1e6, 1)
        log(f"scores gc --keep 180: {g['segments-deleted']} segment(s), "
            f"{g['bytes-reclaimed'] / 1e6:,.0f} MB reclaimed")
    except Exception:
        for log_path in logs:
            try:
                with open(log_path) as fh:
                    tail = fh.read()[-2000:]
                if tail:
                    log(f"--- {log_path} tail ---\n{tail}")
            except OSError:
                pass
        raise
    finally:
        stop(procs)
        shutil.rmtree(art_dir, ignore_errors=True)


def bench_streaming(out: dict) -> None:
    """ISSUE 17 acceptance: the streaming plane vs 1-row bulk polling,
    end-to-end through a real server, plus detection-to-push latency
    over a live SSE subscriber.

    Protocol (docs/perf.md "Streaming plane"):

    - arrival schedule: a GSA1 archive window written through the real
      ``write_chunk`` path and replayed in ``index-ns`` order — the
      stream is driven by the same clock a backfilled fleet replays;
    - in-process step rate (diagnostic): ``StreamHub.ingest_rows`` once
      per arrival — the fixed-shape incremental step over the
      device-resident ring, dispatched through the compile plane's
      ``bind`` fast path (per-arrival cost is O(window), independent of
      history length) — against the bulk device path re-scoring the
      trailing lookback padded to its 256-row compile bucket;
    - the GATE is end-to-end: a 1-row poller pays one full HTTP bulk
      request per sample (that is the ONLY way the request path yields
      one new verdict), while the streaming plane ingests arrivals in
      transport batches and delivers per-row verdicts through the
      event ring (drained here via the documented long-poll fallback,
      whose batched frames are also how a thin consumer would read).
      Gate: streaming >= 5x polling samples/s/core, both sides
      single-threaded against the same single-core server;
    - detection-to-push p99: a live SSE subscriber over the wire;
      per-event latency = frame receipt minus the verdict's ``time``
      field (stamped by the hub at detection).
    """
    import asyncio
    import threading as _threading
    import urllib.request

    import pandas as pd
    from aiohttp import web

    from gordo_tpu.batch import ScoreArchive
    from gordo_tpu.client import Client
    from gordo_tpu.serve import ModelCollection, build_app
    from gordo_tpu.serve.scorer import CompiledScorer
    from gordo_tpu.serve.stream import StreamHub

    n_replay = int(os.environ.get("BENCH_STREAM_ROWS", "2048"))
    n_poll = int(os.environ.get("BENCH_STREAM_POLLS", "96"))
    n_e2e = int(os.environ.get("BENCH_STREAM_E2E_ROWS", "1024"))
    n_push = int(os.environ.get("BENCH_STREAM_PUSH_EVENTS", "384"))
    ingest_batch = int(os.environ.get("BENCH_STREAM_BATCH", "32"))
    out["cpu_cores"] = os.cpu_count()

    model, metadata = _build_serving_model()
    scorer = CompiledScorer(model)
    name = "stream-m-000"

    # -- arrival schedule: one GSA1 chunk replayed in index order -----------
    arch_dir = tempfile.mkdtemp(prefix="gordo-bench-stream-")
    try:
        step = pd.Timedelta("30min")
        start = pd.Timestamp("2024-01-01T00:00:00Z")
        arch = ScoreArchive.create(
            arch_dir, project="bench", start=str(start),
            end=str(start + step * n_replay), resolution="30min",
            chunk_rows=n_replay, n_chunks=1, dtype="float32",
            machines=[name],
        )
        rng = np.random.default_rng(17)
        idx = (
            int(start.value)
            + int(step.value) * np.arange(n_replay, dtype=np.int64)
        )
        arch.write_chunk(0, {name: {
            "index-ns": idx,
            "total-anomaly-score":
                rng.random(n_replay, dtype=np.float32),
            "tag-anomaly-scores":
                rng.random((n_replay, N_TAGS), dtype=np.float32),
            "tags": [f"tag-{j}" for j in range(N_TAGS)],
        }})
        hist = arch.read_machine(name)
        order = np.argsort(hist["index-ns"], kind="stable")
        X = rng.standard_normal((n_replay, N_TAGS)).astype(np.float32)
        X = X[order]

        # -- in-process device-path diagnostic ------------------------------
        hub = StreamHub()
        warm = 8
        for i in range(warm):  # includes the stream-step compile
            hub.ingest_rows(name, scorer, X[i])
        t0 = time.perf_counter()
        for i in range(warm, n_replay):
            hub.ingest_rows(name, scorer, X[i])
        step_rate = (n_replay - warm) / (time.perf_counter() - t0)
        h = hub.streams[name].state_rows

        scorer.anomaly_arrays(X[:h], None)  # compile the polled bucket
        t0 = time.perf_counter()
        for i in range(h, h + n_poll):
            scorer.anomaly_arrays(X[i - h: i], None)
        device_poll_rate = n_poll / (time.perf_counter() - t0)
        out["stream_step_samples_per_s"] = round(step_rate, 1)
        out["stream_device_polling_samples_per_s"] = round(
            device_poll_rate, 1
        )
        out["stream_state_rows"] = h
        log(
            f"streaming step (in-process): {step_rate:,.0f}/s vs "
            f"{device_poll_rate:,.0f}/s bulk device path"
        )

        # -- end-to-end: real server, 1-row polling vs ingest+drain ---------
        art_dir = _backfill_fleet_dir(model, metadata, [name])
        try:

            async def runner():
                coll = ModelCollection.from_directory(
                    art_dir, project="bench"
                )
                app_runner = web.AppRunner(build_app(coll))
                await app_runner.setup()
                site = web.TCPSite(app_runner, "127.0.0.1", 0)
                await site.start()
                base = f"http://127.0.0.1:{app_runner.addresses[0][1]}"

                def post(url, doc):
                    req = urllib.request.Request(
                        url, data=json.dumps(doc).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=60) as resp:
                        return json.load(resp)

                def drive():
                    # 1-row bulk polling: one request per sample is the
                    # request path's only route to one new verdict
                    url = (
                        f"{base}/gordo/v0/bench/{name}/anomaly/prediction"
                    )
                    post(url, {"X": X[:1].tolist()})  # warm
                    t0 = time.perf_counter()
                    for i in range(n_poll):
                        post(url, {"X": X[i: i + 1].tolist()})
                    poll_rate = n_poll / (time.perf_counter() - t0)

                    # streaming: transport-batched ingest + the consumer
                    # draining the event ring via long-poll frames
                    feeder = Client("bench", base_url=base)
                    feeder.stream_ingest({name: X[:warm].tolist()})
                    stream_url = f"{base}/gordo/v0/bench/stream"
                    got, cursor = 0, 0

                    def feed():
                        j = warm
                        while j < warm + n_e2e:
                            feeder.stream_ingest({name: X[
                                j % (n_replay - ingest_batch):
                                j % (n_replay - ingest_batch)
                                + ingest_batch
                            ].tolist()})
                            j += ingest_batch

                    th = _threading.Thread(target=feed, daemon=True)
                    t0 = time.perf_counter()
                    th.start()
                    while got < n_e2e:
                        status = urllib.request.urlopen(
                            f"{stream_url}?mode=poll&after={cursor}"
                            "&timeout=10", timeout=60,
                        )
                        doc = json.load(status)
                        got += sum(
                            1 for ev in doc["events"]
                            if ev["type"] == "verdict"
                        )
                        cursor = doc["last-event-id"]
                    stream_rate = got / (time.perf_counter() - t0)
                    th.join(timeout=30)

                    # detection-to-push p99 over a live SSE subscriber
                    lats: "list[float]" = []
                    consumer = Client("bench", base_url=base)
                    stop = _threading.Event()

                    def feed_paced():
                        j = 0
                        while not stop.is_set():
                            feeder.stream_ingest(
                                {name: [X[j % n_replay].tolist()]}
                            )
                            j += 1
                            time.sleep(0.003)

                    th2 = _threading.Thread(target=feed_paced, daemon=True)
                    th2.start()
                    try:
                        for ev in consumer.stream(
                            machines=[name], max_events=n_push
                        ):
                            if ev["type"] != "verdict":
                                continue
                            lats.append(
                                time.time() - ev["data"]["time"]
                            )
                    finally:
                        stop.set()
                        th2.join(timeout=10)
                    return poll_rate, stream_rate, lats

                try:
                    return await asyncio.get_running_loop().run_in_executor(
                        None, drive
                    )
                finally:
                    await app_runner.cleanup()

            poll_rate, stream_rate, lats = asyncio.run(runner())
            ratio = stream_rate / poll_rate
            out["stream_samples_per_s_per_core"] = round(stream_rate, 1)
            out["stream_polling_samples_per_s_per_core"] = round(
                poll_rate, 1
            )
            out["stream_vs_polling"] = round(ratio, 2)
            log(
                f"streaming e2e: {stream_rate:,.0f} samples/s/core vs "
                f"{poll_rate:,.0f} polling ({ratio:.1f}x; gate >= 5x)"
            )
            if ratio < 5.0:
                out["stream_gate_miss"] = (
                    f"streaming {ratio:.2f}x polling, gate >= 5x"
                )

            lats_ms = np.asarray(lats) * 1e3
            out["stream_push_p50_ms"] = round(
                float(np.percentile(lats_ms, 50)), 2
            )
            out["stream_push_p99_ms"] = round(
                float(np.percentile(lats_ms, 99)), 2
            )
            out["stream_push_events"] = len(lats)
            log(
                f"streaming push latency over SSE: p50 "
                f"{out['stream_push_p50_ms']}ms p99 "
                f"{out['stream_push_p99_ms']}ms ({len(lats)} events)"
            )
        finally:
            shutil.rmtree(art_dir, ignore_errors=True)
    finally:
        shutil.rmtree(arch_dir, ignore_errors=True)


def bench_serving_wire(out: dict) -> None:
    """ISSUE 15 acceptance: the GSB1 columnar bulk wire vs the r18
    msgpack bulk wire, end-to-end through the real ``Client`` against a
    REAL ``run-server`` subprocess.

    Protocol (docs/perf.md "Bulk wire"):

    - one trained machine replicated across N names (512), v2 packs,
      identical tag lists — the same fleet the backfill bench uses, so
      the comparison is wire codec + client materialization, not model
      or provider variance;
    - both legs are the actual ``Client`` (``use_bulk=True``) replaying
      a range and COUNTING every per-tag score sample it received:

      * ``columnar``: the r19 default — ``Accept`` negotiates GSB1,
        the client decodes zero-copy ``np.frombuffer`` views and the
        samples are counted off the LAZY column access (no DataFrame
        is ever built — the per-machine frame materialization the r18
        profile showed at 35x the raw wire floor is simply not paid);
      * ``msgpack``: ``use_columnar=False``, the r18 wire — per-machine
        DataFrames materialized via ``res.predictions``, exactly how
        BENCH_r18's ``backfill_512_http_replay_samples_per_sec``
        (264,367/s) was measured.

      The msgpack leg replays FEWER chunks (rates are normalized to
      samples/s) so the slow leg fits the stage budget;
    - legs are interleaved (C M C M ...) and each wire reports its
      best-of-``BENCH_WIRE_REPEATS`` — interleaving keeps slow drift
      (page cache, CPU thermal) from biasing one wire;
    - an un-timed warmup leg per wire lands the server's stacked-
      program compiles and both codec paths before any clock starts;
    - attestation: ``serving_wire_value_identity_ok`` — one slab posted
      twice to the same server, once per ``Accept``; every float array
      in the decoded responses must match BITWISE (fp32), scalars
      exactly.  The columnar wire is a relayout, not a requantization;
    - gate: columnar client e2e samples/s >= 3x the r18 msgpack
      baseline at 512 machines on CPU.
    """
    import urllib.request

    import pandas as pd

    from gordo_tpu.client import Client
    from gordo_tpu.serve import codec

    n_machines = int(os.environ.get("BENCH_WIRE_MACHINES", "512"))
    rows = int(os.environ.get("BENCH_WIRE_ROWS", "2048"))
    col_chunks = int(os.environ.get("BENCH_WIRE_CHUNKS", "8"))
    mp_chunks = int(os.environ.get("BENCH_WIRE_MSGPACK_CHUNKS", "2"))
    repeats = int(os.environ.get("BENCH_WIRE_REPEATS", "2"))
    out["cpu_cores"] = os.cpu_count()

    model, metadata = _build_serving_model()
    resolution = (metadata.get("dataset") or {}).get("resolution", "10min")
    step = pd.tseries.frequencies.to_offset(resolution)
    names = [f"wire-{i:05d}" for i in range(n_machines)]
    art_dir = _backfill_fleet_dir(model, metadata, names)

    procs: "list[subprocess.Popen]" = []
    logs: "list[str]" = []

    def free_port() -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(port: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.pop("GORDO_SERVE_SHARD", None)
        env["JAX_PLATFORMS"] = "cpu"
        log_path = os.path.join(art_dir, f"server-{port}.log")
        logs.append(log_path)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "gordo_tpu.cli.cli", "run-server",
                "--model-dir", art_dir, "--project", "bench",
                "--host", "127.0.0.1", "--port", str(port),
                "--rescan-interval", "0",
            ],
            env=env,
            stdout=open(log_path, "w"), stderr=subprocess.STDOUT,
        )
        procs.append(proc)
        return proc

    def wait_ready(port: int, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        url = f"http://127.0.0.1:{port}/healthz"
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return
            except Exception:
                time.sleep(0.25)
        raise RuntimeError(f"wire server on :{port} never became ready")

    def stop(to_stop: "list[subprocess.Popen]") -> None:
        for proc in to_stop:
            proc.terminate()
        for proc in to_stop:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    start = pd.Timestamp("2024-01-01T00:00:00Z")

    def leg(port: int, columnar: bool, chunks: int, timed: bool) -> dict:
        """One full client replay over ``chunks`` windows; per-tag score
        samples counted off the wire-appropriate access path.  The clock
        covers replay AND consumption: the lazy client defers frame work
        to first access, so stopping at ``predict()`` would undercharge
        the msgpack leg exactly the cost this stage exists to measure."""
        client = Client(
            "bench", port=port, use_bulk=True, batch_size=rows,
            use_columnar=columnar,
        )
        end = start + step * (rows * chunks)
        t0 = time.perf_counter()
        results = client.predict(str(start), str(end))
        samples = 0
        for res in results:
            if not res.ok:
                raise RuntimeError(
                    f"wire replay failed for {res.name}: "
                    f"{res.error_messages}"
                )
            if columnar:
                # lazy column access — no DataFrame on this path, which
                # IS the measured difference
                samples += int(
                    np.asarray(res.raw.column("tag-anomaly-scores")).size
                )
            else:
                frame = res.predictions
                n_tag_cols = sum(
                    1 for c in frame.columns
                    if c[0] == "tag-anomaly-scores"
                )
                samples += len(frame) * n_tag_cols
        dt = time.perf_counter() - t0
        if timed:
            log(f"serving_wire {'columnar' if columnar else 'msgpack'} "
                f"leg: {samples / dt:,.0f} samples/s "
                f"({samples:,} samples / {dt:.1f}s, {chunks} chunks)")
        return {
            "samples": samples,
            "seconds": dt,
            "samples_per_sec": samples / dt if dt > 0 else 0.0,
        }

    def value_identity(port: int) -> bool:
        """One slab, posted twice; the two wires must decode to the same
        fp32 BITS for every array and the same python floats."""
        n_tags = len((metadata.get("dataset") or {}).get("tag_list") or [])
        rng = np.random.default_rng(19)
        slab = rng.standard_normal(
            (min(rows, 512), max(1, n_tags))
        ).astype(np.float32)
        subset = names[: min(8, len(names))]
        body = codec.packb({"X": {n: slab for n in subset}})
        url = (
            f"http://127.0.0.1:{port}"
            "/gordo/v0/bench/_bulk/anomaly/prediction"
        )

        def post(accept: str) -> bytes:
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={
                    "Content-Type": codec.MSGPACK_CONTENT_TYPE,
                    "Accept": accept,
                },
            )
            with urllib.request.urlopen(req, timeout=300) as resp:
                if resp.status != 200:
                    raise RuntimeError(f"wire identity -> {resp.status}")
                return resp.read()

        mp_data = codec.unpackb(post(codec.MSGPACK_CONTENT_TYPE))["data"]
        col_data = codec.decode_columnar(post(
            f"{codec.COLUMNAR_CONTENT_TYPE}, {codec.MSGPACK_CONTENT_TYPE}"
        ))["data"]
        if sorted(mp_data) != sorted(col_data):
            return False
        for name, ref in mp_data.items():
            got = col_data[name]
            if sorted(got) != sorted(ref):
                return False
            for key, val in ref.items():
                if isinstance(val, np.ndarray):
                    if got[key].dtype != val.dtype:
                        return False
                    if got[key].tobytes() != val.tobytes():
                        return False
                elif got[key] != val:
                    return False
        return True

    server = None
    try:
        port = free_port()
        server = spawn(port)
        wait_ready(port, 240.0)

        # identity first: it doubles as a codec-path warmup on both wires
        out["serving_wire_value_identity_ok"] = value_identity(port)

        # un-timed warmup legs land stacked-program compiles + budget-
        # shaped bodies for both wires
        leg(port, columnar=True, chunks=1, timed=False)
        leg(port, columnar=False, chunks=1, timed=False)

        col_best: "dict | None" = None
        mp_best: "dict | None" = None
        for _ in range(max(1, repeats)):
            c = leg(port, columnar=True, chunks=col_chunks, timed=True)
            m = leg(port, columnar=False, chunks=mp_chunks, timed=True)
            if col_best is None or c["samples_per_sec"] > col_best["samples_per_sec"]:
                col_best = c
            if mp_best is None or m["samples_per_sec"] > mp_best["samples_per_sec"]:
                mp_best = m

        col_sps = col_best["samples_per_sec"]
        mp_sps = mp_best["samples_per_sec"]
        out["serving_wire_machines"] = n_machines
        out["serving_wire_chunk_rows"] = rows
        out["serving_wire_columnar_chunks"] = col_chunks
        out["serving_wire_msgpack_chunks"] = mp_chunks
        out["serving_wire_columnar_samples_per_sec"] = round(col_sps)
        out["serving_wire_columnar_samples"] = col_best["samples"]
        out["serving_wire_columnar_seconds"] = round(col_best["seconds"], 3)
        out["serving_wire_msgpack_samples_per_sec"] = round(mp_sps)
        out["serving_wire_msgpack_samples"] = mp_best["samples"]
        out["serving_wire_msgpack_seconds"] = round(mp_best["seconds"], 3)
        out["serving_wire_speedup_vs_msgpack"] = (
            col_sps / mp_sps if mp_sps > 0 else 0.0
        )
        out["serving_wire_r18_baseline_samples_per_sec"] = (
            R18_BULK_REPLAY_SAMPLES_PER_SEC
        )
        out["serving_wire_vs_r18_baseline"] = round(
            col_sps / R18_BULK_REPLAY_SAMPLES_PER_SEC, 3
        )
        out["serving_wire_ge_3x_r18_ok"] = (
            col_sps >= 3.0 * R18_BULK_REPLAY_SAMPLES_PER_SEC
        )
        log(f"serving_wire gate: columnar {col_sps:,.0f}/s vs r18 "
            f"msgpack baseline {R18_BULK_REPLAY_SAMPLES_PER_SEC:,}/s -> "
            f"{col_sps / R18_BULK_REPLAY_SAMPLES_PER_SEC:.2f}x "
            f"(>= 3x: "
            f"{'PASS' if out['serving_wire_ge_3x_r18_ok'] else 'FAIL'}); "
            f"in-run msgpack {mp_sps:,.0f}/s -> "
            f"{out['serving_wire_speedup_vs_msgpack']:.2f}x")
        out["serving_wire_speedup_vs_msgpack"] = round(
            out["serving_wire_speedup_vs_msgpack"], 3
        )
    except Exception:
        for log_path in logs:
            try:
                with open(log_path) as fh:
                    tail = fh.read()[-2000:]
                if tail:
                    log(f"--- {log_path} tail ---\n{tail}")
            except OSError:
                pass
        raise
    finally:
        if server is not None:
            stop([server])
        shutil.rmtree(art_dir, ignore_errors=True)


#: BENCH_r18.json backfill_512_http_replay_samples_per_sec — the msgpack
#: bulk client-replay rate the r19 columnar wire is gated against
R18_BULK_REPLAY_SAMPLES_PER_SEC = 264367


#: stage registry order == run order == metric priority
STAGES = ("build", "build_throughput", "artifact_io", "hot_reload",
          "serving", "serving_precision", "serving_sharded",
          "serving_wire", "serving_openloop", "telemetry_overhead",
          "health_overhead", "cold_start", "multi_device", "refresh",
          "backfill", "scores_lifecycle", "streaming", "lstm")


def parse_cli(argv: "list[str]") -> "tuple[list[str], int | None]":
    """Parse ``(stages, round)`` from the CLI.

    ``--stage NAME`` (repeatable) selects a subset of STAGES to run, in
    canonical order; no ``--stage`` runs everything this process can run
    (see ``CHILD_PROCESS_STAGES``).  ``--round NN`` (or the BENCH_ROUND
    env var) additionally persists the emitted result line to
    ``BENCH_rNN.json`` (atomic write; see :func:`persist_round`).
    Side-effect-free so tests can exercise it without a jax import."""
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--stage", action="append", choices=STAGES, default=None,
        help="Run only the named stage(s); repeatable. Default: all.",
    )
    p.add_argument(
        "--round", type=int, default=None,
        help="Round number NN: persist the emitted result line to "
             "BENCH_rNN.json (atomic tmp+rename; the run exits nonzero "
             "if the write fails). Defaults to $BENCH_ROUND when set.",
    )
    args = p.parse_args(argv)
    selected = args.stage or list(STAGES)
    rnd = args.round
    if rnd is None and os.environ.get("BENCH_ROUND"):
        rnd = int(os.environ["BENCH_ROUND"])
    return [s for s in STAGES if s in selected], rnd


def parse_stages(argv: "list[str]") -> "list[str]":
    """Back-compat wrapper: just the stage list from :func:`parse_cli`."""
    return parse_cli(argv)[0]


def main(argv: "list[str] | None" = None) -> int:
    """Run the selected stages on the attached accelerator and print ONE
    JSON line naming the device it ran on.  Returns the exit code.

    There is no CPU fallback: a run that finds no accelerator fails, prints
    no result and starts nothing else.  A stage that raises is logged with
    its traceback, recorded under ``stages_failed`` and makes the exit code
    non-zero; later stages still run, so one failure does not cost the
    other numbers.
    """
    global _ROUND
    stages, _ROUND = parse_cli(sys.argv[1:] if argv is None else argv)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        log(f"bench: jax found no accelerator ({devices}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). A CPU run is not a "
            "measurement of this system: nothing was run.")
        return 1
    explicit = stages != list(STAGES)
    blocked = [s for s in stages if s in CHILD_PROCESS_STAGES]
    if blocked and explicit:
        log(f"bench: stage(s) {blocked} measure through forked children "
            f"pinned to CPU and cannot run while this process holds the "
            f"{platform} (see CHILD_PROCESS_STAGES).")
        return 2
    stages = [s for s in stages if s not in CHILD_PROCESS_STAGES]

    from gordo_tpu.parallel.mesh import fleet_mesh

    n_chips = len(devices)
    out: dict = {
        "metric": "per-tag anomaly-detector builds/hour/chip (full build path)",
        "value": None,
        "unit": "models/hour/chip",
        "vs_baseline": None,
        "n_machines": N_MACHINES,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_chips": n_chips,
    }
    if explicit:
        out["stages_selected"] = stages
    else:
        out["stages_not_runnable"] = sorted(CHILD_PROCESS_STAGES)
    mesh = fleet_mesh(devices) if n_chips > 1 else None

    def build_stage():
        models_per_hour = bench_build(mesh, out)
        per_chip = models_per_hour / n_chips
        out["value"] = round(per_chip, 1)
        out["vs_baseline"] = round(
            per_chip / NORTH_STAR_MODELS_PER_HOUR_PER_CHIP, 3
        )

    stage_fns = {
        "build": build_stage,
        "build_throughput": lambda: bench_build_throughput(mesh, out),
        "artifact_io": lambda: bench_artifact_io(out),
        "hot_reload": lambda: bench_hot_reload(out),
        "serving": lambda: bench_serving(out),
        "serving_precision": lambda: bench_serving_precision(out),
        "serving_openloop": lambda: bench_serving_openloop(out),
        "telemetry_overhead": lambda: bench_telemetry_overhead(out),
        "health_overhead": lambda: bench_health_overhead(out),
        "refresh": lambda: bench_refresh(out),
        "streaming": lambda: bench_streaming(out),
        "lstm": lambda: bench_lstm_build(mesh, out),
    }
    for name in stages:
        try:
            stage_fns[name]()
        except Exception as exc:
            import traceback

            log(f"stage {name} FAILED:\n{traceback.format_exc()}")
            _failed_stages[name] = (str(exc).splitlines() or [repr(exc)])[0]
        else:
            out.setdefault("stages_done", []).append(name)
    if _failed_stages:
        out["stages_failed"] = dict(_failed_stages)
    emit_once(out)
    return exit_code()


if __name__ == "__main__":
    # forked measurement child for bench_multi_device — dispatched before
    # main() so the parent's argparse (whose choices are STAGES) never
    # sees the child flags
    if "--scaleout-child" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--scaleout-child"]
        scaleout_child_main(argv)
    sys.exit(main())
