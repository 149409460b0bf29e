"""North-star scale proof: a 10,000-machine project, end to end.

BASELINE.md's north star is "10k per-tag models in under an hour on a
v5e-64".  This script drives the full production path at that machine
count on whatever backend is available (CPU jax for the scale proof —
the memory-bounded streaming pipeline is identical):

  project YAML (10k machines) → NormalizedConfig → workflow build_plan
  → build_project (bucketed, streaming, 2-chunk memory bound) → artifact

and writes a JSON artifact (``northstar_10k.json``) recording the plan
shape, wall time, build rate, and the peak number of machines whose
arrays were resident at once (must stay ≤ 2 × max_bucket_size).

Run detached (the full run exceeds interactive timeouts)::

    JAX_PLATFORMS=cpu \
        nohup python scripts/northstar_10k.py > /tmp/northstar.log 2>&1 &

The record names the device it ran on (``platform``, ``device_kind``,
``device_count`` from ``jax.devices()``); the committed
``northstar_10k.json`` is a CPU record and says so.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

N_MACHINES = int(os.environ.get("NORTHSTAR_MACHINES", "10000"))


def _device_fields() -> dict:
    """Where the build ran, as jax reports it (never from the environment)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }

N_TAGS = int(os.environ.get("NORTHSTAR_TAGS", "10"))
BUCKET = int(os.environ.get("NORTHSTAR_BUCKET", "512"))
OUT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "northstar_10k.json"
)


def project_yaml(n: int) -> str:
    machines = "\n".join(
        f"  - name: ns-{i:05d}\n"
        f"    dataset:\n"
        f"      type: RandomDataset\n"
        f"      tags: [{', '.join(f'ns-{i:05d}-t{j}' for j in range(N_TAGS))}]\n"
        for i in range(n)
    )
    # tiny epochs: the scale proof is about the pipeline (bucketing,
    # streaming, memory bound, artifact IO), not FLOPs
    return (
        "machines:\n" + machines + """
globals:
  model:
    gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector:
      base_estimator:
        gordo_tpu.pipeline.Pipeline:
          steps:
            - gordo_tpu.ops.scalers.MinMaxScaler
            - gordo_tpu.models.estimator.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 3
                batch_size: 64
"""
    )


def measure_config(text: str):
    """Same-session interleaved config measurement (r24 protocol).

    The r23 artifact's ``config_seconds`` was measured on different
    hardware-sharing conditions than any re-run, so the r24 fast-path
    gate (≤ 0.5×) compares against a BASELINE RE-MEASURED IN THIS RUN:
    the legacy path (pure-Python SafeLoader + eager normalization) and
    the fast path (:meth:`NormalizedConfig.from_source`: C loader,
    Counter dup-check, merge fast paths) alternate for two rounds and
    the per-path best stands.  A third number records the content-hash
    cache warm hit (parse + normalization both skipped).
    """
    import yaml

    from gordo_tpu.workflow.config import NormalizedConfig

    def legacy() -> float:
        t0 = time.time()
        cfg = yaml.load(text, Loader=yaml.SafeLoader)
        NormalizedConfig(cfg, "northstar")
        return time.time() - t0

    best = {"legacy": None, "fast": None}
    config = None
    for _ in range(2):
        dt = legacy()
        if best["legacy"] is None or dt < best["legacy"]:
            best["legacy"] = dt
        t0 = time.time()
        config = NormalizedConfig.from_source(text, "northstar")
        dt = time.time() - t0
        if best["fast"] is None or dt < best["fast"]:
            best["fast"] = dt
        print(
            f"config round: legacy {best['legacy']:.1f}s "
            f"fast {best['fast']:.1f}s", flush=True,
        )

    cache_dir = tempfile.mkdtemp(prefix="northstar-cfgcache-")
    try:
        NormalizedConfig.from_source(text, "northstar", cache_dir=cache_dir)
        t0 = time.time()
        config = NormalizedConfig.from_source(
            text, "northstar", cache_dir=cache_dir
        )
        t_warm = time.time() - t0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return config, best["fast"], best["legacy"], t_warm


def main() -> int:
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.generator import build_plan

    t_all = time.time()
    print(f"generating {N_MACHINES}-machine project yaml...", flush=True)
    text = project_yaml(N_MACHINES)
    config, t_config, t_config_base, t_config_warm = measure_config(text)
    print(
        f"config fast path {t_config:.1f}s vs legacy {t_config_base:.1f}s "
        f"(cache-warm {t_config_warm:.2f}s)", flush=True,
    )

    t0 = time.time()
    plan = build_plan(config, max_bucket_size=BUCKET)
    t_plan = time.time() - t0
    print(
        f"plan: {plan['n_machines']} machines in {plan['n_buckets']} "
        f"chunks ({t_plan:.1f}s)", flush=True,
    )

    out_dir = tempfile.mkdtemp(prefix="northstar-")
    try:
        t0 = time.time()
        result = build_project(
            config.machines, out_dir, max_bucket_size=BUCKET
        )
        t_build = time.time() - t0
        rate = len(result.artifacts) / t_build * 3600.0
        doc = {
            "n_machines": N_MACHINES,
            "n_tags": N_TAGS,
            "max_bucket_size": BUCKET,
            "plan_chunks": plan["n_buckets"],
            "config_seconds": round(t_config, 1),
            "config_seconds_baseline": round(t_config_base, 1),
            "config_ratio": round(t_config / t_config_base, 3),
            "config_cache_warm_seconds": round(t_config_warm, 2),
            "plan_seconds": round(t_plan, 1),
            "build_seconds": round(t_build, 1),
            "built_ok": len(result.artifacts),
            "fleet_built": len(result.fleet_built),
            "failed": len(result.failed),
            "models_per_hour": round(rate),
            "peak_loaded": result.peak_loaded,
            "peak_loaded_bound": 2 * BUCKET,
            "memory_bound_held": result.peak_loaded <= 2 * BUCKET,
            "loader_workers": result.loader_workers,
            "ingest": result.ingest,
            **_device_fields(),
            "total_seconds": round(time.time() - t_all, 1),
        }
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    with open(os.path.abspath(OUT_PATH), "w") as fh:
        json.dump(doc, fh, indent=1)
    print(json.dumps(doc), flush=True)
    ok = (
        doc["failed"] == 0
        and doc["built_ok"] == N_MACHINES
        and doc["memory_bound_held"]
        and doc["config_ratio"] <= 0.5
    )
    print("NORTHSTAR", "OK" if ok else "FAILED", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
