#!/usr/bin/env python
"""Simulated-multiprocess dryrun of the multi-host build path.

A CPU SIMULATION by design: parent and workers are pinned to
``JAX_PLATFORMS=cpu`` with virtual devices, so nothing here competes for
an accelerator, and the parent touches no backend before it forks.

Forks N real worker processes (default 2), each with its own
``--xla_force_host_platform_device_count`` virtual-CPU backend, wired
into ONE ``jax.distributed`` job via the ``GORDO_*`` env contract — the
same mechanism as the driver's ``dryrun_multichip``, except the process
boundary (coordination service, heartbeats, barriers) is real.  Asserts:

1. cross-process init succeeds: every worker reports
   ``N x local_devices`` global devices and validates a sharded program
   over the process-spanning mesh;
2. the process shards are disjoint and exhaustive;
3. the merged registry + artifacts are byte-identical to a single-host
   build of the same project (model.pkl/definition.yaml byte-for-byte;
   metadata.json modulo build-timing fields);
4. killing one worker mid-build leaves a resumable per-shard state —
   survivors exit EXIT_SHARD_RESUMABLE — and a re-run completes the
   project with the survivor's machines all cache hits.

Run:  python scripts/multihost_dryrun.py [--processes 2]
      [--local-devices 2] [--skip-kill] [--keep]
Exit: 0 on success; 1 with a FAIL line otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the parent only orchestrates: no jax backend init here, so worker env
# construction can't inherit a poisoned backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from gordo_tpu.distributed.launcher import (  # noqa: E402
    pick_free_port,
    wait_all,
    worker_env,
)
from gordo_tpu.distributed.partition import (  # noqa: E402
    EXIT_SHARD_RESUMABLE,
    SHARD_STATE_DIR,
    ShardState,
)
from gordo_tpu.utils import disk_registry  # noqa: E402

#: metadata fields that legitimately differ between two builds of the
#: same config (wall-clock measurements); everything else must match
VOLATILE_META = {
    "model_creation_date",
    "data_query_duration_sec",
    "cross_validation_duration_sec",
    "model_builder_duration_sec",
    "fit_samples_per_second",
    "fit_seconds",
}

#: 8 machines over 2 processes → 4-machine shards, so every stacked
#: program (single-host: 8 lanes, shard: 4) keeps >= 2 lanes per virtual
#: device.  At 1 lane/device XLA:CPU specializes the program differently
#: and per-lane params drift by 1 ulp — a width artifact, not a
#: correctness bug, but the byte-identity assertion below is strict, so
#: the dryrun stays out of that regime (real shards are hundreds wide).
N_MACHINES = 8


def project_yaml(path: str) -> str:
    """A small homogeneous project: every machine fleet-buckets, builds in
    seconds on CPU, and exercises the cache/registry path."""
    doc = {
        "machines": [
            {
                "name": f"mh-{i}",
                "dataset": {
                    "type": "RandomDataset",
                    "tags": ["t-a", "t-b", "t-c"],
                    "train_start_date": "2017-12-25T06:00:00Z",
                    "train_end_date": "2017-12-26T06:00:00Z",
                },
            }
            for i in range(N_MACHINES)
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
    import yaml

    with open(path, "w") as f:
        yaml.safe_dump(doc, f)
    return path


def build_argv(config_path, out_dir, reg_dir, extra=()):
    return [
        sys.executable, "-m", "gordo_tpu.cli.cli", "build-project",
        "--machine-config", config_path,
        "--project-name", "mhdry",
        "--output-dir", out_dir,
        "--model-register-dir", reg_dir,
        # the byte-identity contract this dryrun pins is defined at
        # per-machine granularity; v1 dirs make it directly comparable
        # (v2 pack chunking differs between a single-host and a sharded
        # build by construction — pack-level parity is the artifact
        # suite's job, tests/test_artifacts.py::TestV1V2Parity)
        "--artifact-format", "v1",
        *extra,
    ]


def launch(argv, n, local_devices, barrier_timeout, log_dir):
    coordinator = f"127.0.0.1:{pick_free_port()}"
    os.makedirs(log_dir, exist_ok=True)
    procs = []
    for pid in range(n):
        env = worker_env(
            pid, n, coordinator,
            local_devices=local_devices, barrier_timeout=barrier_timeout,
        )
        out = open(os.path.join(log_dir, f"worker-{pid}.log"), "wb")
        procs.append(subprocess.Popen(
            argv, env=env, stdout=out, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    return procs


def last_json_line(log_path):
    doc = None
    try:
        with open(log_path, "rb") as f:
            for line in f.read().decode(errors="replace").splitlines():
                line = line.strip()
                if line.startswith("{"):
                    try:
                        doc = json.loads(line)
                    except ValueError:
                        pass
    except OSError:
        pass
    return doc


def fail(msg, log_dir=None):
    print(f"FAIL: {msg}")
    if log_dir and os.path.isdir(log_dir):
        for name in sorted(os.listdir(log_dir)):
            path = os.path.join(log_dir, name)
            print(f"--- tail {name} ---")
            with open(path, "rb") as f:
                print(f.read().decode(errors="replace")[-3000:])
    sys.exit(1)


def _scrub_timings(obj, seen=None):
    """Zero wall-clock attributes (``fit_seconds_``, ``fleet_seconds``)
    and topology provenance (``bucket_size`` — the stacked-program width,
    which legitimately differs when a shard is smaller than the project)
    through the pickled object graph.  Everything else — params, scaler
    stats, thresholds, CV history — must match to the bit."""
    if seen is None:
        seen = set()
    if id(obj) in seen:
        return
    seen.add(id(obj))
    if isinstance(obj, dict):
        for key, zero in (("fleet_seconds", 0.0), ("bucket_size", 0)):
            if key in obj:
                obj[key] = zero
        for v in obj.values():
            _scrub_timings(v, seen)
        return
    if isinstance(obj, (list, tuple)):
        for v in obj:
            _scrub_timings(v, seen)
        return
    d = getattr(obj, "__dict__", None)
    if d is None:
        return
    if "fit_seconds_" in d:
        d["fit_seconds_"] = 0.0
    for v in d.values():
        _scrub_timings(v, seen)


def compare_artifacts(ref_dir, got_dir, names):
    """Byte-identity check: definition.yaml byte-for-byte; model.pkl
    byte-for-byte after zeroing wall-clock fit timings (every numeric
    array — params, scalers, thresholds, CV history — must match to the
    bit); metadata.json equal after dropping build-timing fields."""
    import pickle

    for name in names:
        a = os.path.join(ref_dir, name, "definition.yaml")
        b = os.path.join(got_dir, name, "definition.yaml")
        with open(a, "rb") as fa, open(b, "rb") as fb:
            if fa.read() != fb.read():
                return f"{name}/definition.yaml differs from single-host build"
        with open(os.path.join(ref_dir, name, "model.pkl"), "rb") as f:
            ma = pickle.load(f)
        with open(os.path.join(got_dir, name, "model.pkl"), "rb") as f:
            mb = pickle.load(f)
        _scrub_timings(ma)
        _scrub_timings(mb)
        if pickle.dumps(ma) != pickle.dumps(mb):
            return (
                f"{name}/model.pkl differs from single-host build beyond "
                "fit timings"
            )
        with open(os.path.join(ref_dir, name, "metadata.json")) as f:
            ma = json.load(f)
        with open(os.path.join(got_dir, name, "metadata.json")) as f:
            mb = json.load(f)

        drop = VOLATILE_META | {"fleet_seconds", "bucket_size"}

        def strip(v):
            if isinstance(v, dict):
                return {
                    k: strip(x) for k, x in v.items() if k not in drop
                }
            if isinstance(v, list):
                return [strip(x) for x in v]
            return v

        if strip(ma) != strip(mb):
            return f"{name}/metadata.json differs beyond timing fields"
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=2)
    ap.add_argument("--barrier-timeout", type=float, default=30.0)
    ap.add_argument("--skip-kill", action="store_true",
                    help="Skip the worker-death/resume scenario.")
    ap.add_argument("--keep", action="store_true",
                    help="Keep the work dir for inspection.")
    args = ap.parse_args()
    n = args.processes

    work = tempfile.mkdtemp(prefix="gordo-mhdry-")
    print(f"workdir: {work}")
    t_start = time.time()
    ok = {"phases": []}
    try:
        config = project_yaml(os.path.join(work, "project.yaml"))

        # ---- phase 1: single-host reference build (same code path,
        # separate process so jax state can't leak into the workers)
        ref_out = os.path.join(work, "ref-models")
        ref_reg = os.path.join(work, "ref-registry")
        log_dir = os.path.join(work, "logs-ref")
        os.makedirs(log_dir, exist_ok=True)
        # same virtual-device count as each worker, but NO distributed init
        # (empty coordinator): the byte-identity comparison must only vary
        # the process topology, never the XLA backend shape
        ref_env = worker_env(0, 1, "unused:0", local_devices=args.local_devices)
        ref_env["GORDO_COORDINATOR"] = ""
        with open(os.path.join(log_dir, "single.log"), "wb") as out:
            rc = subprocess.call(
                build_argv(config, ref_out, ref_reg),
                env=ref_env, stdout=out, stderr=subprocess.STDOUT, cwd=REPO,
            )
        if rc != 0:
            fail(f"single-host reference build rc={rc}", log_dir)
        names = sorted(os.listdir(ref_out))
        names = [x for x in names if x.startswith("mh-")]
        if len(names) != N_MACHINES:
            fail(f"reference build produced {names}", log_dir)
        ok["phases"].append("single-host-reference")

        # ---- phase 2: N-process multihost build into a shared dir
        mh_out = os.path.join(work, "mh-models")
        mh_reg = os.path.join(work, "mh-registry")
        log_dir = os.path.join(work, "logs-mh")
        procs = launch(
            build_argv(config, mh_out, mh_reg), n,
            args.local_devices, args.barrier_timeout, log_dir,
        )
        codes = wait_all(procs, timeout=600)
        if codes != [0] * n:
            fail(f"multihost build exit codes {codes}", log_dir)

        # init evidence: every worker saw the full global device count
        shards = []
        for pid in range(n):
            doc = last_json_line(os.path.join(log_dir, f"worker-{pid}.log"))
            if not doc or "multihost" not in doc:
                fail(f"worker {pid} emitted no multihost summary", log_dir)
            mh = doc["multihost"]
            expect = n * args.local_devices
            if mh["global_devices"] != expect:
                fail(
                    f"worker {pid} saw {mh['global_devices']} global "
                    f"devices, expected {expect}", log_dir,
                )
            state = ShardState.load(mh_out, pid, n)
            if state is None or state.status != "done":
                fail(f"worker {pid} shard state missing/not done", log_dir)
            shards.append(state.machines)
        flat = sorted(x for s in shards for x in s)
        if flat != sorted(names):
            fail(
                f"shards not disjoint+exhaustive: {shards} vs {names}",
                log_dir,
            )
        ok["phases"].append(f"multihost-init-{n}proc")
        ok["shards"] = shards

        # artifacts + merged registry byte-identical to single-host
        err = compare_artifacts(ref_out, mh_out, names)
        if err:
            fail(err, log_dir)
        if disk_registry.list_keys(mh_reg) != disk_registry.list_keys(ref_reg):
            fail(
                f"merged registry keys differ: {disk_registry.list_keys(mh_reg)} "
                f"vs {disk_registry.list_keys(ref_reg)}", log_dir,
            )
        ok["phases"].append("artifact-byte-identity")

        # ---- phase 3: kill one worker mid-build; survivor exits
        # resumable; a re-run completes from cache + the dead remainder
        if not args.skip_kill:
            k_out = os.path.join(work, "kill-models")
            k_reg = os.path.join(work, "kill-registry")
            log_dir = os.path.join(work, "logs-kill")
            procs = launch(
                build_argv(config, k_out, k_reg), n,
                args.local_devices, args.barrier_timeout, log_dir,
            )
            victim = procs[-1]
            victim_state = os.path.join(
                k_out, SHARD_STATE_DIR,
                f"shard-{n - 1:03d}-of-{n:03d}.json",
            )
            # kill as soon as the victim has STARTED its shard (state file
            # exists) — before it can finish everything
            deadline = time.time() + 120
            while time.time() < deadline:
                if os.path.exists(victim_state):
                    break
                if victim.poll() is not None:
                    fail("victim exited before starting its shard", log_dir)
                time.sleep(0.02)
            else:
                fail("victim never wrote its shard state", log_dir)
            victim.send_signal(signal.SIGKILL)
            codes = wait_all(procs, timeout=600)
            if codes[-1] != -signal.SIGKILL:
                fail(f"victim exit code {codes[-1]} != SIGKILL", log_dir)
            for pid, code in enumerate(codes[:-1]):
                if code != EXIT_SHARD_RESUMABLE:
                    fail(
                        f"survivor {pid} exited {code}, expected "
                        f"EXIT_SHARD_RESUMABLE={EXIT_SHARD_RESUMABLE}",
                        log_dir,
                    )
            dead = ShardState.load(k_out, n - 1, n)
            if dead is None or dead.status == "done":
                fail("dead shard state missing or claims done", log_dir)
            remaining = sorted(set(dead.machines) - set(dead.completed))
            ok["phases"].append(
                f"kill-detected (dead shard had {len(remaining)} "
                "machine(s) left)"
            )

            # re-run the SAME spec: fresh coordinator, same dirs — every
            # already-built machine must cache-hit, the remainder builds
            log_dir2 = os.path.join(work, "logs-resume")
            procs = launch(
                build_argv(config, k_out, k_reg), n,
                args.local_devices, args.barrier_timeout, log_dir2,
            )
            codes = wait_all(procs, timeout=600)
            if codes != [0] * n:
                fail(f"resume run exit codes {codes}", log_dir2)
            built = sorted(
                x for x in os.listdir(k_out) if x.startswith("mh-")
            )
            if built != sorted(names):
                fail(f"resume run left artifacts incomplete: {built}", log_dir2)
            for pid in range(n):
                state = ShardState.load(k_out, pid, n)
                if state is None or state.status != "done":
                    fail(f"resumed shard {pid} not done", log_dir2)
            # survivors' machines must have been cache hits on the re-run
            for pid in range(n - 1):
                doc = last_json_line(
                    os.path.join(log_dir2, f"worker-{pid}.log")
                )
                if doc and doc.get("fleet_built", 0) + doc.get(
                    "single_built", 0
                ) > 0 and doc.get("cached", 0) == 0:
                    fail(
                        f"survivor {pid} rebuilt instead of cache-hitting",
                        log_dir2,
                    )
            err = compare_artifacts(ref_out, k_out, names)
            if err:
                fail(f"post-resume {err}", log_dir2)
            ok["phases"].append("resume-completed")

        ok["seconds"] = round(time.time() - t_start, 1)
        print("OK " + json.dumps(ok))
    finally:
        if args.keep:
            print(f"kept workdir: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
