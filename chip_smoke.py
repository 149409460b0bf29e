"""Chip smoke: build-project -> run-server on the attached accelerator.

The quickest proof that the system still starts on the chip.  It drives
the main path once through the entry points a user calls — ``python -m
gordo_tpu.cli.cli build-project`` and ``... run-server`` — at the full
width of the two model families the repo supports (BASELINE.json shapes):

- phase ``dense``: the default detector (MinMaxScaler +
  feedforward_hourglass under DiffBasedAnomalyDetector), 10 tags, the
  RandomDataset default window (4 days at 10 min), one full default
  bucket of 512 machines;
- phase ``lstm``: ``lstm_hourglass``, 50 tags, lookback 12, 16 machines.

Each phase builds the project, serves the built directory with
``--warmup``, and answers a single JSON request, a stacked ``_bulk``
request and a ``stream/ingest`` on it.  The dense phase is then built a
second time, process-cold into a fresh directory, to attest that the
persistent compile cache hits on this backend.  Everything is asserted,
nothing is merely logged: any failed check raises and the run exits
non-zero without printing a result.

One process holds the chip at a time.  This parent never imports jax: it
runs one child after another (device probe, build, server, ...) and reads
what each child reports about its own device.  On a host with several
chips the same script uses all of them (the build shards its ``models``
axis over every device; the server gets ``--model-parallel``) and
additionally asserts that every device received a shard.

``main()`` refuses to run unless jax finds an accelerator.  The phases
are importable functions that take sizes and the expected platform as
arguments, so ``tests/test_chip_smoke.py`` and a CPU rehearsal go through
the same code at 2 machines x 3 tags.

Output: progress on stderr, child logs under ``chiprun_out/chip_smoke/``,
and — only on success — two JSON lines on stdout.  First the evidence
(versions, per-phase seconds with compile seconds apart, cache hits and
writes, request counts; also kept as ``chiprun_out/chip_smoke/result.json``)
— bring-up evidence, not benchmark numbers.  Then, LAST, the result with
exactly these keys, the device as the probe child's jax reported it:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.metadata
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CLI = [sys.executable, "-m", "gordo_tpu.cli.cli"]
PROJECT = "smoke"

#: the contract: exit 0 within 1200 s, compilation included
DEADLINE_SECONDS = 1150.0

LSTM_LOOKBACK = 12

#: log lines that mean a fallback hid something (ISSUE 21 §2); a child log
#: containing any of them fails the run
FALLBACK_MARKERS = (
    "falling back to singles",      # fleet program failed -> per-machine
    "building single",              # machine never reached a fleet bucket
    "AOT compile unavailable",      # AOT lower/compile refused -> jit
    "falling back to jit",          # AOT executable failed -> jit
    "Warmup failed",                # a serving program did not pre-compile
    "Warmup: fleet scorer construction failed",
    "warmup manifest write failed",
    "generation stamp failed",
)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(condition: Any, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Workload sizes.  Widths, lookback and the training window are the
    BASELINE.json shapes; only machine counts may be trimmed."""

    dense_machines: int = 512
    dense_tags: int = 10
    lstm_machines: int = 16
    lstm_tags: int = 50
    single_rows: int = 256
    bulk_machines: int = 64
    bulk_rows: int = 2048
    stream_rows: int = 5


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def _kill_group(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (it leads its own session,
    so its process group is exactly its descendants)."""
    for sig, grace in ((signal.SIGTERM, 20.0), (signal.SIGKILL, None)):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass  # the whole group is already gone
        try:
            proc.wait(timeout=grace)
            return
        except subprocess.TimeoutExpired:
            continue


def run_child(
    argv: Sequence[str], log_path: str, env: Dict[str, str], timeout: float
) -> str:
    """Run one child to its end from the repo root; return its stdout.
    stderr goes to ``log_path``.  A non-zero exit or a timeout fails."""
    check(timeout > 0, f"no time left to start {' '.join(argv[:6])}")
    with open(log_path, "wb") as err:
        proc = subprocess.Popen(
            list(argv), cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=err, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"child exceeded {timeout:.0f}s: {' '.join(argv[:6])} "
                f"(log: {log_path})"
            )
        finally:
            _kill_group(proc)
    text = out.decode("utf-8", "replace")
    if proc.returncode != 0:
        raise SmokeFailure(
            f"child exited {proc.returncode}: {' '.join(argv[:6])}\n"
            f"--- stdout tail ---\n{text[-2000:]}\n"
            f"--- log tail ({log_path}) ---\n{_tail(log_path)}"
        )
    return text


def _tail(path: str, n_bytes: int = 4000) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            fh.seek(max(0, fh.tell() - n_bytes))
            return fh.read().decode("utf-8", "replace")
    except OSError as exc:
        return f"<unreadable: {exc}>"


def scan_for_fallbacks(log_path: str) -> None:
    with open(log_path, "r", errors="replace") as fh:
        text = fh.read()
    hits = [m for m in FALLBACK_MARKERS if m in text]
    check(not hits, f"fallback message(s) {hits} in {log_path}")


_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'device_kind': d[0].device_kind, 'count': len(d)}))"
)


def probe_device(workdir: str, env: Dict[str, str], timeout: float) -> Dict:
    """What jax finds, asked of a child that exits before the next starts
    (``jax.devices()`` in this process would claim the chip)."""
    out = run_child(
        [sys.executable, "-c", _PROBE],
        os.path.join(workdir, "probe.log"), env, timeout,
    )
    return json.loads(out.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# project config + what a build left on disk
# ---------------------------------------------------------------------------

LSTM_MODEL: Dict[str, Any] = {
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


def write_project(
    path: str, prefix: str, n_machines: int, n_tags: int,
    model: Optional[Dict[str, Any]] = None,
) -> List[str]:
    """Generate the project config at ``path`` (JSON is YAML; the CLI wants
    a ``.yaml`` name) with ``RandomDataset`` machines — data comes from a
    seed, nothing from the network.  No ``model`` means the project
    default: the dense hourglass detector.  Returns the machine names."""
    names = [f"{prefix}-{i:04d}" for i in range(n_machines)]
    doc: Dict[str, Any] = {
        "machines": [
            {
                "name": name,
                "dataset": {
                    "type": "RandomDataset",
                    "tag_list": [f"{name}-tag-{j}" for j in range(n_tags)],
                },
            }
            for name in names
        ]
    }
    if model is not None:
        doc["globals"] = {"model": model}
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return names


def read_machine_metadata(out_dir: str) -> Dict[str, Dict[str, Any]]:
    """Per-machine build metadata from the v2 pack sidecars (plain JSON —
    no model is loaded, so no backend is touched)."""
    machines: Dict[str, Dict[str, Any]] = {}
    packs = os.path.join(out_dir, ".gordo-packs")
    for path in glob.glob(os.path.join(packs, "*.meta.json")):
        with open(path) as fh:
            machines.update(json.load(fh)["machines"])
    return machines


def read_build_counters(out_dir: str) -> Dict[str, Dict[str, float]]:
    """``{metric: {first label value: value}}`` for the labelled counters
    of a single-host build's telemetry snapshot
    (``<out>/.gordo-telemetry/shard-000-of-001.json``)."""
    path = os.path.join(out_dir, ".gordo-telemetry", "shard-000-of-001.json")
    with open(path) as fh:
        metrics = json.load(fh)["metrics"]
    return {
        name: {
            json.loads(labels)[0]: float(value)
            for labels, value in metric["series"].items()
        }
        for name, metric in metrics.items()
        if metric["kind"] == "counter" and metric["labels"]
    }


def cache_dir() -> str:
    """Where the children keep their compile cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the program's fixed default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )


# ---------------------------------------------------------------------------
# phase: build
# ---------------------------------------------------------------------------

def _finite_positive(values: Any) -> bool:
    flat = np.asarray(values, dtype=np.float64).ravel()
    return bool(flat.size and np.all(np.isfinite(flat)) and np.all(flat > 0))


def build_phase(
    label: str,
    workdir: str,
    config: str,
    names: List[str],
    out_dir: str,
    expect_platform: str,
    env: Dict[str, str],
    timeout: float,
    min_persistent_hits: int = 0,
) -> Tuple[Dict[str, Any], Dict[str, Dict[str, Any]]]:
    """One ``build-project`` child of the project at ``config`` (whose
    machines are ``names``) into ``out_dir``; assert the summary, the
    device it names, and every machine's thresholds and loss.  Returns the
    phase report and the per-machine build metadata."""
    n_machines = len(names)
    log_path = os.path.join(workdir, f"{label}-build.log")
    t0 = time.monotonic()
    out = run_child(
        CLI + ["build-project", "--machine-config", config,
               "--project-name", PROJECT, "--output-dir", out_dir],
        log_path, env, timeout,
    )
    wall = time.monotonic() - t0
    summary = json.loads(out.strip().splitlines()[-1])
    device = summary["device"]
    log(f"{label}: built in {wall:.1f}s on {device}")

    check(device["platform"] == expect_platform,
          f"{label}: built on {device}, expected platform {expect_platform}")
    check(device["device_kind"], f"{label}: no device_kind in {device}")
    check(device["used"] == device["count"],
          f"{label}: result arrays were on {device['used']} of "
          f"{device['count']} devices")
    check(summary["n_machines"] == n_machines,
          f"{label}: n_machines {summary['n_machines']} != {n_machines}")
    check(summary["fleet_built"] == n_machines,
          f"{label}: fleet_built {summary['fleet_built']} != {n_machines}")
    check(summary["single_built"] == 0 and summary["cached"] == 0,
          f"{label}: single_built/cached not 0: {summary}")
    check(summary["failed"] == {}, f"{label}: failed {summary['failed']}")
    check(summary["demoted"]["machines"] == 0,
          f"{label}: bucket demotions {summary['demoted']}")
    check(summary["aot_fallbacks"] == 0,
          f"{label}: {summary['aot_fallbacks']} AOT->jit fallbacks")
    scan_for_fallbacks(log_path)

    machines = read_machine_metadata(out_dir)
    check(sorted(machines) == sorted(names),
          f"{label}: pack metadata names {len(machines)} machines, "
          f"built {n_machines}")
    for name, meta in machines.items():
        cv = meta["model"]["cross_validation"]
        check(_finite_positive(cv["feature_thresholds"])
              and _finite_positive(cv["aggregate_threshold"]),
              f"{label}/{name}: thresholds not finite and > 0: {cv}")
        loss = meta["model"]["base_estimator"]["history"]["loss"]
        check(all(math.isfinite(v) for v in loss) and loss[-1] < loss[0],
              f"{label}/{name}: training loss did not fall: {loss}")

    counters = read_build_counters(out_dir)
    hits = counters["gordo_compile_cache_hits_total"].get("persistent", 0)
    writes = counters["gordo_compile_cache_misses_total"].get("persistent", 0)
    check(hits >= min_persistent_hits,
          f"{label}: {hits:.0f} persistent compile-cache hits, need "
          f">= {min_persistent_hits} (cache dir {cache_dir()})")
    if device["count"] > 1:
        transfers = counters["gordo_mesh_device_transfers_total"]
        fed = sorted(k for k, v in transfers.items() if v > 0)
        check(len(fed) == device["count"],
              f"{label}: placement fed devices {fed} of {device['count']}")
    report = {
        "device": device,
        "wall_seconds": round(wall, 2),
        "build_seconds": round(summary["build_seconds"], 2),
        "compile_seconds": {
            stage: round(seconds, 2) for stage, seconds in
            counters["gordo_compile_jax_seconds_total"].items()
        },
        "n_machines": n_machines,
        "fleet_built": summary["fleet_built"],
        "single_built": summary["single_built"],
        "demoted": summary["demoted"]["machines"],
        "aot_fallbacks": summary["aot_fallbacks"],
        "persistent_cache": {"hits": int(hits), "writes": int(writes)},
        "final_loss_max": max(
            m["model"]["base_estimator"]["history"]["loss"][-1]
            for m in machines.values()
        ),
    }
    return report, machines


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Http:
    """Minimal client that counts responses by status."""

    def __init__(self, base: str):
        self.base = base
        self.by_status: Dict[str, int] = {}

    def request(self, path: str, doc: Any = None, timeout: float = 300.0):
        data = None if doc is None else json.dumps(doc).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, body = resp.status, resp.read()
        except urllib.error.HTTPError as exc:
            status, body = exc.code, exc.read()
        self.by_status[str(status)] = self.by_status.get(str(status), 0) + 1
        check(status == 200, f"{path}: HTTP {status}: {body[:500]!r}")
        return body


def _series(scrape: str, metric: str) -> Dict[str, float]:
    """``{first label value: value}`` of a labelled metric in a
    Prometheus text scrape (every series read here has one label)."""
    return {
        line.split('"')[1]: float(line.rsplit(" ", 1)[1])
        for line in scrape.splitlines()
        if line.startswith(metric + "{")
    }


def _grown(before: str, after: str, metric: str) -> Dict[str, float]:
    """Per-series growth of a counter between two scrapes (grown only)."""
    was = _series(before, metric)
    return {
        key: value - was.get(key, 0.0)
        for key, value in _series(after, metric).items()
        if value > was.get(key, 0.0)
    }


def request_rows(
    meta: Dict[str, Any], n_rows: int, seed: int, perturb_tail: int = 0
) -> List[List[float]]:
    """Rows near the machine's training distribution (per-tag mean and
    std from its build metadata); the last ``perturb_tail`` rows are
    pushed 100 std away — a gross fault."""
    tags = [t["name"] for t in meta["dataset"]["tag_list"]]
    stats = meta["dataset"]["summary_statistics"]
    mean = np.array([stats[t]["mean"] for t in tags])
    std = np.array([stats[t]["std"] for t in tags])
    rng = np.random.default_rng(seed)
    X = mean + 0.1 * std * rng.standard_normal((n_rows, len(tags)))
    if perturb_tail:
        X[-perturb_tail:] += 100.0 * std
    return np.round(X, 5).tolist()


def _finite_scores(result: Dict[str, Any], where: str) -> np.ndarray:
    check("error" not in result, f"{where}: {result.get('error')}")
    total = np.asarray(result["total-anomaly-score"], dtype=np.float64)
    check(total.size and np.all(np.isfinite(total)),
          f"{where}: total-anomaly-score not finite")
    for key in ("model-output", "tag-anomaly-scores"):
        check(np.all(np.isfinite(np.asarray(result[key], dtype=np.float64))),
              f"{where}: {key} not finite")
    return total


def serve_phase(
    label: str,
    workdir: str,
    model_dir: str,
    machines: Dict[str, Dict[str, Any]],
    n_devices: int,
    expect_platform: str,
    env: Dict[str, str],
    timeout: float,
    sizes: Sizes,
    lookback: int = 1,
) -> Dict[str, Any]:
    """One ``run-server --warmup`` child over ``model_dir``: wait for
    ``/healthz`` ready, answer a single, a bulk and a stream request,
    scrape ``/metrics``, stop the server."""
    check(timeout > 0, f"{label}: no time left to serve")
    deadline = time.monotonic() + timeout
    port = _free_port()
    argv = CLI + ["run-server", "--model-dir", model_dir, "--host",
                  "127.0.0.1", "--port", str(port), "--project", PROJECT,
                  "--warmup"]
    if n_devices > 1:
        argv.append("--model-parallel")
    log_path = os.path.join(workdir, f"{label}-server.log")
    http = _Http(f"http://127.0.0.1:{port}")
    names = sorted(machines)
    t0 = time.monotonic()
    with open(log_path, "wb") as err:
        proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdout=err, stderr=err,
            start_new_session=True,
        )
    try:
        health: Dict[str, Any] = {}
        while True:
            check(proc.poll() is None,
                  f"{label}: server exited {proc.returncode}\n"
                  f"{_tail(log_path)}")
            check(time.monotonic() < deadline,
                  f"{label}: server not ready in {timeout:.0f}s "
                  f"(last /healthz: {health})\n{_tail(log_path)}")
            try:
                with urllib.request.urlopen(
                    http.base + "/healthz", timeout=10
                ) as resp:
                    health = json.loads(resp.read())
            except (urllib.error.URLError, OSError, ValueError):
                pass  # not listening yet
            if health.get("state") == "ready":
                break
            time.sleep(1.0)
        ready_seconds = time.monotonic() - t0
        log(f"{label}: server ready in {ready_seconds:.1f}s: {health}")
        check("warmup_error" not in health and not health.get("warmup_errors"),
              f"{label}: warm-up failed: {health}")

        before = http.request("/metrics").decode()
        prefix = f"/gordo/v0/{PROJECT}"
        t_req = time.monotonic()

        # single machine, JSON; the same rows with a gross fault at the end
        target = names[0]
        rows = max(sizes.single_rows, 4 * lookback)
        tail = max(8, 2 * lookback)
        calm = request_rows(machines[target], rows, seed=1)
        faulty = request_rows(
            machines[target], rows, seed=1, perturb_tail=tail)
        single = {}
        for kind, X in (("calm", calm), ("faulty", faulty)):
            doc = json.loads(http.request(
                f"{prefix}/{target}/anomaly/prediction", {"X": X}))
            single[kind] = _finite_scores(
                doc["data"], f"{label} single {kind}")
        check(single["faulty"][-1] > single["calm"][-1],
              f"{label}: a row 100 std off scored {single['faulty'][-1]} "
              f"<= the calm row's {single['calm'][-1]}")

        # stacked bulk, two shapes: the replayed-stream request (a subset
        # of the bucket at many rows — dispatched as an unsharded gather)
        # and the whole bucket at the single request's row count (the
        # full-bucket program, the one a mesh shards).  The single-machine
        # program above is the reference for the target machine's slot.
        n_single = single["calm"].shape[0]
        bulk_shapes = []
        for n_bulk, bulk_rows in (
            (min(sizes.bulk_machines, len(names)), max(sizes.bulk_rows, rows)),
            (len(names), rows),
        ):
            if bulk_shapes and bulk_shapes[0][0] == n_bulk:
                continue  # the first request already covered the bucket
            bulk_shapes.append([n_bulk, bulk_rows])
            payload = {
                name: request_rows(machines[name], bulk_rows, seed=2 + i)
                for i, name in enumerate(names[:n_bulk])
            }
            payload[target] = calm + payload[target][rows:]
            doc = json.loads(http.request(
                f"{prefix}/_bulk/anomaly/prediction", {"X": payload},
                timeout=max(60.0, deadline - time.monotonic())))
            where = f"{label} bulk {n_bulk}x{bulk_rows}"
            check(sorted(doc["data"]) == sorted(payload),
                  f"{where}: answered {len(doc['data'])} machines")
            for name in payload:
                total = _finite_scores(doc["data"][name], f"{where} {name}")
                check(total.shape[0] == bulk_rows - (lookback - 1),
                      f"{where} {name}: {total.shape[0]} scores at "
                      f"lookback {lookback}")
            stacked = np.asarray(
                doc["data"][target]["total-anomaly-score"], dtype=np.float64
            )[:n_single]
            check(np.allclose(stacked, single["calm"], rtol=0.05, atol=1e-3),
                  f"{where}: stacked and single-machine programs disagree "
                  f"on {target}: max |diff| "
                  f"{np.max(np.abs(stacked - single['calm']))}")

        # stream: a handful of rows, verdicts read back by long-poll
        stream_rows = lookback - 1 + sizes.stream_rows
        ingest = json.loads(http.request(
            f"{prefix}/stream/ingest",
            {"machine": target, "x": calm[:stream_rows]}))
        check(ingest["accepted"] == stream_rows,
              f"{label}: stream accepted {ingest}")
        polled = json.loads(http.request(
            f"{prefix}/stream?mode=poll&after=0&timeout=5&machines={target}"))
        verdicts = [e["data"] for e in polled["events"]
                    if e["type"] == "verdict"]
        check(len(verdicts) == sizes.stream_rows,
              f"{label}: {len(verdicts)} stream verdicts for "
              f"{sizes.stream_rows} scoreable rows")
        streamed = np.array([v["total-anomaly-score"] for v in verdicts])
        check(np.all(np.isfinite(streamed)),
              f"{label}: stream scores {streamed}")
        check(np.allclose(streamed, single["calm"][: sizes.stream_rows],
                          rtol=0.05, atol=1e-3),
              f"{label}: stream step and request path disagree: "
              f"{streamed} vs {single['calm'][: sizes.stream_rows]}")
        request_seconds = time.monotonic() - t_req

        after = http.request("/metrics").decode()
        health = json.loads(http.request("/healthz"))
    finally:
        _kill_group(proc)

    device = health["device"]
    check(device["platform"] == expect_platform,
          f"{label}: served on {device}, expected {expect_platform}")
    check(device["used"] == device["count"] == n_devices,
          f"{label}: serving arrays on {device['used']} of "
          f"{device['count']} devices (build saw {n_devices})")
    check(health["aot_fallbacks"] == 0 and "warmup_error" not in health
          and not health.get("warmup_errors"), f"{label}: /healthz {health}")
    dispatches = sum(
        _grown(before, after, "gordo_serve_dispatches_total").values())
    transfers = sum(
        _grown(before, after, "gordo_serve_input_transfers_total").values())
    check(dispatches > 0 and transfers == dispatches,
          f"{label}: {dispatches} dispatches but {transfers} input transfers")
    if n_devices > 1:
        # every device must have been fed by the requests themselves, not
        # just at load: one growing transfer series per device
        fed = sorted(
            _grown(before, after, "gordo_mesh_device_transfers_total"))
        check(len(fed) == n_devices,
              f"{label}: requests fed {fed} of {n_devices} devices")
    scan_for_fallbacks(log_path)
    return {
        "device": device,
        "model_parallel": n_devices > 1,
        "ready_seconds": round(ready_seconds, 2),
        "request_seconds": round(request_seconds, 2),
        "compile_seconds": {
            stage: round(seconds, 2) for stage, seconds in
            _series(after, "gordo_compile_jax_seconds_total").items()
        },
        "requests_by_status": dict(http.by_status),
        "dispatches": int(dispatches),
        "input_transfers": int(transfers),
        "bulk_shapes": bulk_shapes,
        "persistent_cache": {
            "hits": int(_series(
                after, "gordo_compile_cache_hits_total"
            ).get("persistent", 0)),
            "writes": int(_series(
                after, "gordo_compile_cache_misses_total"
            ).get("persistent", 0)),
        },
    }


def mesh_plan_phase(
    label: str, workdir: str, model_dir: str, n_devices: int,
    env: Dict[str, str], timeout: float,
) -> Dict[str, Any]:
    """``mesh info --model-dir`` in its own child: the per-device-slots
    plan must cover every device (several-device hosts only)."""
    out = run_child(
        CLI + ["mesh", "info", "--model-dir", model_dir],
        os.path.join(workdir, f"{label}-mesh.log"), env, timeout,
    )
    doc = json.loads(out[out.index("{"):])
    check(doc["n_devices"] == n_devices and doc["sharded"],
          f"{label}: mesh info {doc}")
    check(doc["mesh_shape"]["models"] == n_devices,
          f"{label}: models axis {doc['mesh_shape']} over {n_devices} devices")
    for bucket in doc["buckets"]:
        slots = bucket.get("per-device-slots", {})
        check(len(slots) == n_devices,
              f"{label}: bucket {bucket['bucket']} plans slots on "
              f"{sorted(slots)} of {n_devices} devices")
    return {"mesh_shape": doc["mesh_shape"],
            "buckets": len(doc["buckets"])}


# ---------------------------------------------------------------------------
# the whole smoke
# ---------------------------------------------------------------------------

def run_smoke(
    workdir: str,
    sizes: Sizes,
    device: Dict[str, Any],
    env: Optional[Dict[str, str]] = None,
    deadline_seconds: float = DEADLINE_SECONDS,
) -> Dict[str, Any]:
    """Both phases end to end.  ``device`` is what the probe found: every
    child must then report that platform and that many devices.  The
    persistent compile cache is XLA:CPU-excluded by design, so cache
    hits are demanded only off CPU."""
    env = dict(os.environ if env is None else env)
    expect_platform = device["platform"]
    t_start = time.monotonic()

    def left() -> float:
        return deadline_seconds - (time.monotonic() - t_start)

    need_hits = 0 if expect_platform == "cpu" else 1
    n_devices = device["count"]
    scratch = tempfile.mkdtemp(prefix="gordo-chip-smoke-")
    phases: Dict[str, Any] = {}
    try:
        for label, n, tags, model, lookback in (
            ("dense", sizes.dense_machines, sizes.dense_tags, None, 1),
            ("lstm", sizes.lstm_machines, sizes.lstm_tags,
             LSTM_MODEL, LSTM_LOOKBACK),
        ):
            if n == 0:
                continue
            config = os.path.join(workdir, f"{label}-project.yaml")
            names = write_project(config, label, n, tags, model)
            out_dir = os.path.join(scratch, label)
            build, machines = build_phase(
                label, workdir, config, names, out_dir, expect_platform,
                env, left(),
            )
            check(build["device"]["count"] == n_devices,
                  f"{label}: build saw {build['device']}, probe saw {device}")
            phases[f"{label}_build"] = build
            phases[f"{label}_serve"] = serve_phase(
                label, workdir, out_dir, machines, n_devices,
                expect_platform, env, left(), sizes, lookback=lookback,
            )
            if n_devices > 1:
                phases[f"{label}_mesh"] = mesh_plan_phase(
                    label, workdir, out_dir, n_devices, env, left())
            if label == "dense":
                # the same project, process-cold, into a fresh output dir,
                # same cache dir: the only check that the persistent cache
                # works on this backend
                phases["dense_rebuild"], _ = build_phase(
                    "dense-rebuild", workdir, config, names,
                    os.path.join(scratch, "dense-rebuild"),
                    expect_platform, env, left(),
                    min_persistent_hits=need_hits,
                )
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return {
        "phases": phases,
        "total_seconds": round(time.monotonic() - t_start, 1),
    }


def _version(dist: str) -> Optional[str]:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def result_line(device: Dict[str, Any]) -> str:
    """The last line of stdout: exactly ``ok`` and ``device`` with exactly
    ``platform``, ``kind`` (jax's ``device_kind``) and ``count`` — the
    checker that runs this script accepts no other key."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["device_kind"]),
            "count": int(device["count"]),
        },
    })


def main() -> int:
    if not os.path.isdir(os.path.join(REPO, "gordo_tpu")):
        log(f"no gordo_tpu package beside {__file__}: nothing to smoke")
        return 5
    workdir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(workdir, exist_ok=True)
    env = dict(os.environ)
    try:
        device = probe_device(workdir, env, timeout=180.0)
        if device["platform"] == "cpu":
            log(f"jax found no accelerator ({device}; JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}): refusing to run — "
                "a CPU run proves nothing about the chip")
            return 4
        log(f"device: {device}")
        result = run_smoke(workdir, Sizes(), device, env)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    evidence = {
        "device": device,
        "versions": {
            "python": sys.version.split()[0],
            **{d: _version(d) for d in ("jax", "jaxlib", "libtpu")},
        },
        "phases": result["phases"],
        "total_seconds": result["total_seconds"],
        "compile_cache": {
            "dir": cache_dir(),
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries": len(glob.glob(os.path.join(cache_dir(), "*-cache"))),
        },
        # whether the server encoded through the C kernel it builds on
        # first use, or quietly through stdlib json (no cc)
        "native_json": bool(glob.glob(
            os.path.join(REPO, "gordo_tpu", "_native", "fastjson-*.so"))),
        "note": "bring-up evidence, not benchmark numbers",
        "claim": None,
    }
    with open(os.path.join(workdir, "result.json"), "w") as fh:
        json.dump(evidence, fh, indent=1)
    print(json.dumps({"evidence": evidence}))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
