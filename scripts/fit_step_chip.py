#!/usr/bin/env python
"""Time one optimiser step of a cell's fit ON THE CHIP, outside the build,
and say where its microseconds go: the companion of
``scripts/fleet_program_ops.py``, which counts the same step's operations
without a chip.

    python scripts/fit_step_chip.py [--workload <cell>] [--repeats 3] \\
        [--trace-steps 8] [--out chiprun_out/fit_step]

For each LSTM cell: compile the final fit, vmapped over the chunk's
machines, at the cell's real shape (``cell_program(..., "fit")``), run it
``--repeats`` times on seeded random windows and print the wall seconds of
the best run ÷ (epochs × minibatches) as ``step_ms``.  Then trace a fit of
one epoch and ``--trace-steps`` minibatches (the same step, few enough of
them for the profiler's buffer) and reduce the device's ``XLA Ops`` line to
microseconds a step by kind of operation: the layer scans (each ``while``
under the step scan, with its time steps), and the step's own operations by
opcode (``copy``, ``broadcast``, ``fusion`` ...), largest first.  The
per-operation table and the optimised HLO go to ``--out`` for reading
beside the count.

Chip only (exit 3 without one).  Leaves the persistent compile cache alone
(the benchmark's three cells fill it, PERF.md §6): what it compiles is
compiled for this run only.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def random_args(shapes, seed: int):
    """Seeded arrays for a fit's ``ShapeDtypeStruct`` arguments: uniform
    windows, unit weights, distinct keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)

    def fill(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jnp.asarray(rng.uniform(0.0, 1.0, s.shape).astype(s.dtype))
        return jnp.asarray(
            rng.integers(0, 2 ** 31, s.shape, dtype=np.int64).astype(s.dtype))
    params, X, y, w, keys = shapes
    init = jax.tree.map(
        lambda s: jnp.asarray(
            (rng.standard_normal(s.shape) * 0.1).astype(s.dtype)), params)
    return init, fill(X), fill(y), jnp.ones(w.shape, w.dtype), fill(keys)


def timed(compiled, args, repeats: int) -> float:
    import jax

    best = float("inf")
    for _ in range(repeats + 1):            # the first run warms up
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def step_breakdown(xplane: str, hlo_text: str, steps: int):
    """``(summary, by_name)``: device microseconds a step from a trace of
    ``steps`` of them, the step scan's own operations by kind and each scan
    under it; and every traced operation's ``[events, seconds]``."""
    from benchmark import trace as trace_mod
    from fleet_program_ops import parse_hlo, while_tree

    comps = parse_hlo(hlo_text)
    rows = while_tree(comps)
    step_row = next(r for r in rows if r["trip_count"] == steps and r["depth"] > 0)
    own_names = set(comps[step_row["body"]]["names"])
    inner_names = {
        name for r in rows if r["depth"] > step_row["depth"]
        for name in comps[r["body"]]["names"]
    }
    by_name = collections.defaultdict(lambda: [0, 0.0])
    for name, start, end in trace_mod.load(xplane).devices[0].ops:
        # an event is named by its whole instruction, "%name = shape op(...)"
        entry = by_name[name.split(" = ")[0].lstrip("%")]
        entry[0] += 1
        entry[1] += end - start
    own = collections.defaultdict(lambda: [0, 0.0])
    scans = {}
    other = 0.0
    for name, (n, seconds) in by_name.items():
        kind = name.split(".")[0]           # "broadcast.3160.clone.2"
        if name not in own_names:
            if name not in inner_names:
                other += seconds
        elif kind == "while":
            scans[name] = seconds / steps * 1e6
        else:
            own[kind][0] += n / steps
            own[kind][1] += seconds / steps * 1e6
    summary = {
        "step_scan": step_row["body"],
        "layer_scans_us": dict(sorted(scans.items())),
        "layer_scans_total_us": sum(scans.values()),
        "own_ops_us": {
            k: {"per_step": round(n, 1), "us": round(us, 1)}
            for k, (n, us) in sorted(own.items(), key=lambda kv: -kv[1][1])
        },
        "own_total_us": sum(us for _, us in own.values()),
        "outside_the_step_scan_s": other,
    }
    return summary, dict(by_name)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="fit_step_chip")
    parser.add_argument("--workload", default=None)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--trace-steps", type=int, default=8,
                        help="0: time only")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "fit_step"))
    args = parser.parse_args(argv)
    import jax

    from benchmark import device, manifest as manifest_mod
    from benchmark import trace as trace_mod

    try:
        device.require_chips(1)
    except device.NoChip as e:
        print(json.dumps({"error": str(e)}))
        return 3
    jax.config.update("jax_enable_compilation_cache", False)
    from fleet_program_ops import cell_program

    os.makedirs(args.out, exist_ok=True)
    manifest = manifest_mod.Manifest()
    for cell in manifest.doc["workloads"]:
        if args.workload not in (None, cell["name"]):
            continue
        if manifest.traffic(cell["traffic"])["kind"] != "fleet_build":
            continue
        config = manifest.config(cell["config"])
        epochs = int(config["model"].get("epochs", 10))
        jitted, shapes, steps = cell_program(manifest, cell, "fit")
        t0 = time.perf_counter()
        compiled = jitted.lower(*shapes).compile()
        compile_s = time.perf_counter() - t0
        fit_s = timed(compiled, random_args(shapes, args.seed), args.repeats)
        line = {
            "workload": cell["name"], "device_kind": jax.devices()[0].device_kind,
            "compile_s": round(compile_s, 1), "fit_s": fit_s,
            "epochs": epochs, "steps": steps,
            "step_ms": fit_s / (epochs * steps) * 1e3,
            "temp_gib": compiled.memory_analysis().temp_size_in_bytes / 2.0 ** 30,
        }
        if args.trace_steps:
            short, short_shapes, n = cell_program(
                manifest, cell, "fit", epochs=1, max_steps=args.trace_steps)
            short_compiled = short.lower(*short_shapes).compile()
            short_args = random_args(short_shapes, args.seed)
            jax.block_until_ready(short_compiled(*short_args))
            trace_dir = os.path.join(args.out, cell["name"] + ".trace")
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(short_compiled(*short_args))
            hlo = short_compiled.as_text()
            with open(os.path.join(args.out, cell["name"] + ".hlo.txt"), "w") as f:
                f.write(hlo)
            summary, by_name = step_breakdown(
                trace_mod.find_xplane(trace_dir), hlo, n)
            with open(os.path.join(args.out, cell["name"] + ".ops.json"), "w") as f:
                json.dump(by_name, f)
            line["traced_steps"] = n
            line.update(summary)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
