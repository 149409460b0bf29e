"""Readings that limits are set from, and the control that has to fail.

    python3 -m benchmark.control --workload <name> --seeds 1,2,3 [--control-seeds 3] [--no-build]

One process on the chip, at the cell's own size: for each seed it builds one
chunk through ``build_project`` (the timed path's entry and compiled
program), lets the reference fit a seeded sample of the chunk's machines in
one stack, folds and thresholds included as in a run, and reads the numbers
``correct`` compares: the middle machine's, as a run judges them.  For the
first ``--control-seeds`` seeds it also puts the reference, computed in
float8 (the precision below the bfloat16 the configurations state), in the
program's place and reads the same numbers; ``--no-build`` reads the
control alone.  Prints one JSON object per seed and a summary: the sound
runs' largest and the control's smallest of each number.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List

import numpy as np


def main(argv=None, require_chip: bool = True) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.control")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--no-build", action="store_true",
                        help="the control's readings only: the program is not run")
    args = parser.parse_args(argv)

    from benchmark import device, manifest as manifest_mod
    from benchmark.kinds import fleet_build as kind
    from benchmark.reference import lstm_ae

    manifest = manifest_mod.Manifest()
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    if require_chip:
        device.require_chips(int(cell["chips"]))

    chunk = int(config["deployment"]["max_bucket_size"])
    spec = config["check"]
    rows: List[Dict[str, Any]] = []
    for position, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        names = kind.machine_names(seed, chunk)
        picks = kind.sample_names(names, seed, int(spec["machines"]))
        made, failed = None, None
        if not args.no_build:
            made, failed = _build(kind, config, seed, chunk, picks)
        t_build = time.time() - t0
        t1 = time.time()
        stack = np.stack([kind.reference_rows(config, name) for name in picks])
        folds = min(int(spec["fold_machines"]), len(picks))
        ref = kind.reference_of(config, stack, kind.model_seed(seed), folds)
        sound, broken = None, None
        if made is not None:
            sound = kind.middle([
                kind.compare(made[name], kind.machine_of(ref, i),
                             lambda m, name=name: print(f"[{name}] {m}", flush=True))
                for i, name in enumerate(picks)])
        if position < args.control_seeds:
            low = kind.reference_of(config, stack, kind.model_seed(seed), folds,
                                    quantize=lstm_ae.float8)
            broken = kind.middle([
                kind.compare(kind.machine_of(low, i), kind.machine_of(ref, i))
                for i in range(len(picks))])
        row = {"seed": seed, "failed": failed, "build_s": t_build,
               "reference_s": time.time() - t1, "sound": sound, "control": broken}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": device.report(device.require_chips(int(cell["chips"])))
                      if require_chip else None}), flush=True)
    summary: Dict[str, Any] = {"workload": args.workload, "seeds": len(rows)}
    sounds = [r["sound"] for r in rows if r["sound"]]
    controls = [r["control"] for r in rows if r["control"]]
    for key in (sounds or controls or [{}])[0]:
        summary[key] = {
            "sound_max": max(s[key]["value"] for s in sounds) if sounds else None,
            "control_min": min(c[key]["value"] for c in controls) if controls else None,
        }
    print(json.dumps(summary), flush=True)
    return 0


def _build(kind, config, seed: int, chunk: int, picks):
    """One chunk through the timed path's entry; what it wrote for ``picks``."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    doc = kind.project_doc(config, seed, chunk)
    out_dir = tempfile.mkdtemp(prefix="control-")
    try:
        result = build_project(
            NormalizedConfig(doc, f"control-{seed}").machines, out_dir,
            max_bucket_size=chunk,
            artifact_format=config["deployment"]["artifact_format"],
        )
        return kind.produced(out_dir, picks), len(result.failed)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
