"""The controls that have to fail, for the ``horizons_build`` kind.

    python3 -m benchmark.horizons_control --workload <name> --seeds 1,2,3

``benchmark/sequence_control.py`` for the kind it cannot read (it imports
``kinds.sequence_build`` and ``reference.kimi_linear`` by name).  One process
on the chip, at the cell's own size: for each seed the reference fits the
project's first machine as it is (float32 at ``highest``, both horizons,
folds and thresholds included), and then once more for every fault planted
in the timed path's place:

- ``float8``: every matmul operand of the configuration's bfloat16 compute
  rounded to float8 (e4m3, the precision below), folds included;
- ``half_batch``: the second half of every minibatch left out of the loss
  (the final fit alone: no thresholds are read);
- ``frozen_leaf``: the sound fit with one matrix of one layer left at its
  initial value (no fit of its own);
- ``no_rotation``: the rotary positions left out of ``q_r`` and ``k_r`` in
  every block, folds included;
- ``no_mtp``: lambda 0, so the multi-token-prediction module is never
  trained and its matrices stay at their start (the final fit alone).

Each fault's numbers go through the harness's own ``judge`` against the
configuration's limits, as a benchmark run's do, and each has to come out
NOT correct: the process exits 1 if a fault passes.  The sound readings come
from the benchmark's own runs, which print every number compared.  Prints
one JSON object per seed and fault, then the smallest reading of each number
per fault.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List

FAULTS = ("float8", "half_batch", "frozen_leaf", "no_rotation", "no_mtp")
#: which faults run the folds too (thresholds are read)
WITH_FOLDS = ("float8", "no_rotation")
#: the matrix ``frozen_leaf`` leaves at its start: layer 0's attention output
FROZEN = (0, "mla_wo")


def main(argv=None, require_chip: bool = True, root=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.horizons_control")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--faults", default=",".join(FAULTS), help="comma-separated")
    args = parser.parse_args(argv)

    from benchmark import device, manifest as manifest_mod
    from benchmark.kinds import horizons_build as kind
    from benchmark.reference import glm_moe_lite

    manifest = manifest_mod.Manifest(root) if root else manifest_mod.Manifest()
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    limits = config["check"]["limits"]
    if require_chip:
        device.require_chips(int(cell["chips"]))
    faults = [f for f in args.faults.split(",") if f]
    unknown = sorted(set(faults) - set(FAULTS))
    if unknown:
        parser.error(f"unknown faults {unknown}; known: {list(FAULTS)}")

    rows: List[Dict[str, Any]] = []
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        name = kind.machine_names(seed, 1)[0]
        data = kind.reference_rows(config, name)
        model_seed = kind.model_seed(seed)
        t0 = time.time()
        ref = kind.reference_of(config, data, model_seed, folds=True)
        # kept on the host: a fault's fit with its folds needs the chip's room
        ref["params"] = glm_moe_lite.to_host(ref["params"])
        print(f"[{name}] sound fit with folds: {time.time() - t0:.1f}s", flush=True)
        for fault in faults:
            t0 = time.time()
            if fault == "float8":
                low = kind.reference_of(config, data, model_seed, folds=True,
                                        quantize=glm_moe_lite.float8)
            elif fault == "frozen_leaf":
                low = {**ref, "params": glm_moe_lite.freeze(
                    ref["params"], model_seed, ref["shape"], *FROZEN)}
            else:
                low = kind.reference_of(config, data, model_seed,
                                        folds=fault in WITH_FOLDS, fault=fault)
            log = lambda m, fault=fault: print(f"[{name} {fault}] {m}", flush=True)  # noqa: E731
            numbers = kind.compare(low, ref, log)
            ok, table = kind.judge(kind.middle([numbers]), limits, log)
            if ok:
                passed.append((seed, fault))
            row = {"seed": seed, "fault": fault, "correct": bool(ok),
                   "seconds": time.time() - t0,
                   "numbers": {k: v["value"] for k, v in table.items()},
                   "failed_limits": sorted(k for k, v in table.items() if not v["ok"])}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del low
        del ref
    summary: Dict[str, Any] = {"workload": args.workload, "limits": limits,
                               "passed_as_correct": passed}
    for fault in faults:
        mine = [r["numbers"] for r in rows if r["fault"] == fault]
        summary[fault] = {key: min(n[key] for n in mine if key in n)
                          for key in limits if any(key in n for n in mine)}
    print(json.dumps(summary), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
