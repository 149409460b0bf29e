"""The controls that have to fail, for the ``backbone_build`` kind.

    python3 -m benchmark.backbone_control --workload <name> --seeds 1,2,3

``benchmark/sequence_control.py`` and ``horizons_control.py`` for the kind
they cannot read (they import their kinds and their references by name); this
one reads the reference from the configuration's file, as its kind does.  One
process on the chip, at the cell's own size: for each seed the reference fits
the project's first machine as it is (float32 at ``highest``, folds and
thresholds included), and then once more for every fault planted in the
timed path's place:

- ``float8``: every matmul operand of the configuration's bfloat16 compute
  rounded to float8 (e4m3, the precision below), folds included;
- ``half_batch``: the second half of every minibatch left out of the loss
  (the final fit alone: no thresholds are read);
- ``frozen_leaf``: the sound fit with the first held layer's largest matrix
  left at its initial value (no fit of its own);
- one fault per mechanism of the reference's ``FORWARD_FAULTS``, folds
  included (``reference/lfm2_moe.py``: ``no_taps``, the convolution's two
  earlier taps zero; ``no_qk_norm``; ``no_rotation``; ``wrong_group``, query
  head ``i`` reads key/value head ``i % 8``).

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

#: the faults every backbone has; a reference adds its ``FORWARD_FAULTS``
COMMON_FAULTS = ("float8", "half_batch", "frozen_leaf")


def main(argv=None, require_chip: bool = True, root=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.backbone_control")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--faults", default=None, help="comma-separated (default: all)")
    args = parser.parse_args(argv)

    from benchmark import device, manifest as manifest_mod
    from benchmark.kinds import backbone_build as kind

    manifest = manifest_mod.Manifest(root) if root else manifest_mod.Manifest()
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    reference = kind.reference_module(config)
    limits = config["check"]["limits"]
    if require_chip:
        device.require_chips(int(cell["chips"]))
    known = COMMON_FAULTS + tuple(reference.FORWARD_FAULTS)
    faults = [f for f in args.faults.split(",") if f] if args.faults else list(known)
    unknown = sorted(set(faults) - set(known))
    if unknown:
        parser.error(f"unknown faults {unknown}; known: {list(known)}")

    rows: List[Dict[str, Any]] = []
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        name = kind.machine_names(seed, 1)[0]
        data = kind.reference_rows(config, name)
        model_seed = kind.model_seed(seed)
        t0 = time.time()
        ref = kind.reference_of(config, data, model_seed, folds=True)
        # kept on the host: a fault's fit with its folds needs the chip's room
        ref["params"] = reference.to_host(ref["params"])
        print(f"[{name}] sound fit with folds: {time.time() - t0:.1f}s", flush=True)
        for fault in faults:
            t0 = time.time()
            if fault == "float8":
                low = kind.reference_of(config, data, model_seed, folds=True,
                                        quantize=reference.float8)
            elif fault == "frozen_leaf":
                # the first held layer's largest matrix (the first by name of those)
                first = ref["params"].layers[0]
                frozen = max(sorted(first), key=lambda leaf: first[leaf].size)
                low = {**ref, "params": reference.freeze(
                    ref["params"], model_seed, ref["shape"], 0, frozen)}
            else:
                low = kind.reference_of(config, data, model_seed,
                                        folds=fault in reference.FORWARD_FAULTS, fault=fault)
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
