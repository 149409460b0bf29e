"""The benchmark's one command.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  One process: it holds the chip, sets up,
measures, checks what the window produced against the plain reference,
prints every number compared beside its limit, and ends with one JSON
object on the last line of its standard output, which has the contract's
keys and no other; the line before it (``evidence {...}``) carries the
checks and the window's completions.  No accelerator, or fewer
chips than the cell asks for: exit code 3 and no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4
EXIT_FAILED = 5
SCRATCH_DIR = ".bench_out"


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    parser = argparse.ArgumentParser(prog="benchmark.run", description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def log(message: str) -> None:
    print(f"[bench {time.time() - T_PROCESS:8.3f}s] {message}", flush=True)


def main(argv: Optional[List[str]] = None, require_chip: bool = True,
         root: Optional[str] = None) -> int:
    args = parse(argv)
    from benchmark import device, manifest as manifest_mod

    root = root or manifest_mod.ROOT
    try:
        importlib.import_module("gordo_tpu")
    except ImportError as exc:
        print(f"the program is not in this checkout: {exc}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    manifest = manifest_mod.Manifest(root)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])

    import jax

    if require_chip:
        try:
            devices = device.require_chips(int(cell["chips"]))
        except device.NoChip as exc:
            print(f"no result: {exc}", file=sys.stderr)
            return EXIT_NO_CHIP
    else:  # tests only: the rest of a run on whatever jax has
        devices = list(jax.devices()[: int(cell["chips"])])

    ctx = types.SimpleNamespace(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=float(args.seconds), trace=bool(args.trace),
        root=root, devices=devices, t_process=T_PROCESS, log=log,
        require_chip=require_chip,
        scratch=os.path.join(root, SCRATCH_DIR),
    )
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    try:
        record = kind.run(ctx)
    except kind.WindowError as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return EXIT_FAILED
    try:
        line, evidence = reduce(ctx, kind, record)
    except RuntimeError as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return EXIT_FAILED
    finally:
        kind.cleanup(record)
    print("evidence " + json.dumps(evidence), flush=True)
    print(json.dumps(line), flush=True)
    return 0


def reduce(ctx, kind, record: Dict[str, Any]):
    """From the run record to the result line and the evidence beside it."""
    from benchmark import device, readers, trace as trace_mod

    dev = device.report(ctx.devices)  # before the reference touches the chip
    log(f"device memory after the window: {ctx.devices[0].memory_stats()}")
    log(f"loaded programs, largest temporaries first: "
        f"{device.loaded_programs(ctx.devices)[:3]}")
    record["device_kind"] = dev["kind"]
    record["chips"] = len(ctx.devices)
    record["setup_s"] = record["t_setup_end"] - ctx.t_process
    breakdown = None
    if ctx.trace:
        path = trace_mod.find_xplane(record["trace_dir"])
        if path is None:
            raise RuntimeError("the traced run wrote no .xplane.pb")
        trace = trace_mod.load(path)
        if trace.has_device_ops:
            record["trace"] = trace
            if trace_mod.overflowed(trace):
                log("the profiler's buffer filled: the traced window closes "
                    "at the last device event")
            dev["busy_s"], dev["window_s"] = trace_mod.busy_seconds(trace)
            breakdown = trace_mod.breakdown(trace)
        elif ctx.require_chip:
            raise RuntimeError("no operation ran on the device in the traced window")
    metrics: Dict[str, Dict[str, Any]] = {}
    group = "per_layer" if ctx.trace else "end_to_end"
    for metric in ctx.manifest.metrics_of(ctx.cell["name"], group):
        spec = ctx.manifest.metric_spec(metric["name"])
        value = readers.read(spec, record)
        if value is None:
            log(f"metric {metric['name']}: nothing to read, left out")
            continue
        metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
        log(f"metric {metric['name']} = {value!r} {metric['unit']}")
    ok, table = kind.check(ctx, record)
    line: Dict[str, Any] = {
        "correct": bool(ok and record["failed"] == 0),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    evidence = {
        "checks": table,
        "window": {
            "seconds": record["window_seconds"],
            "models": record["models"],
            "completions": [t - record["t_setup_end"] for t in record["completions"]],
        },
    }
    return line, evidence


if __name__ == "__main__":
    sys.exit(main())
