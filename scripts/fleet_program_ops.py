#!/usr/bin/env python
"""Count the device operations one optimiser step of a cell's fleet program
issues: compile one vmapped fit, or the whole ``fleet.exact``, at the cell's
real shape for a described (not attached) v5e, and print every ``while``
body of the optimised HLO with its trip count and its instructions by
opcode.

    JAX_PLATFORMS=cpu python scripts/fleet_program_ops.py \\
        [--workload <cell>] [--unit fit|program] [--layout chosen|public] \\
        [--hlo-dir DIR]

Why a count: a step of the fleet program is a few thousand small device
operations, and what a change removes from it or adds to it shows here in
a minute, before it costs chip time.  A count is not a time: on the chip
an asynchronous copy of a small leaf costs ~0.3 µs and a copy of a 4 MB
stack 19 µs (PERF.md §5), so read it beside the benchmark's
``breakdown.device_ops`` (the same ``while``s by their device seconds) and
believe the trace: docs/observability.md "Operations a step".

What is counted: every instruction of a computation but ``parameter``,
``tuple``, ``get-tuple-element``, ``constant`` and ``bitcast`` (no device
work); a fusion is one operation, ``copy-start`` and ``copy-done`` one
each.  ``ops`` are a body's own; ``ops_with_inner`` adds each inner
``while``'s ``ops_with_inner`` × its trip count.  The step scan is the
``while`` whose trip count is the fit's number of minibatches.

A rehearsal, not a chip run: nothing executes; seconds are this machine's
compile time, bytes the compiler's ``memory_analysis()``.  A compile takes
a minute, so this is a script and not a test; the topology is described
inside ``main``, never at import.  ``--layout public`` carries the public
parameter tree through the fit as before PR 27, by replacing
``train.fit.packed_layout`` in this process (the program has no such
option).  Like ``benchmark/rehearse_memory.py`` it reaches below the public
entry points and pins ``compute_dtype`` to what ``auto`` means on a TPU.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NO_DEVICE_WORK = frozenset(
    {"parameter", "tuple", "get-tuple-element", "constant", "bitcast"}
)
GIB = 2.0 ** 30

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_OPCODE = re.compile(r"([\w\-]+)\(")
_WHILE = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONSTANT = re.compile(r"%?([\w.\-]+) = [su]32\[\]\S* constant\((\d+)\)")
_ROOT_LT = re.compile(
    r"ROOT .* compare\(%?([\w.\-]+), %?([\w.\-]+)\), direction=LT")


def _opcode(line: str) -> Optional[str]:
    """The opcode of an instruction line, ``%name = <type> opcode(...)``;
    a tuple type is in brackets and may hold ``=`` and spaces."""
    _, eq, rest = line.partition(" = ")
    if not eq:
        return None
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[end + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    found = _OPCODE.match(rest)
    return found.group(1) if found else None


def parse_hlo(text: str) -> Dict[str, Dict[str, Any]]:
    """``{computation: {"ops": Counter, "whiles": [(body, trip|None)],
    "entry": bool, "names": [instruction]}}`` of an HLO module's text.  A trip count is the
    instruction's ``known_trip_count`` or, as the TPU compiler's text has
    none, the constant its condition holds the counter under (a scan
    counts up from 0)."""
    comps: Dict[str, Dict[str, Any]] = {}
    cur: Optional[Dict[str, Any]] = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = comps[head.group(1)] = {
                "ops": collections.Counter(), "whiles": [],
                "entry": line.startswith("ENTRY"),
                "constants": {}, "limit": None, "names": [],
            }
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        opcode = _opcode(line)
        if opcode is not None:
            cur["names"].append(
                line.partition(" = ")[0].strip().removeprefix("ROOT ").lstrip("%"))
        if opcode == "constant":
            const = _CONSTANT.search(line)
            if const:
                cur["constants"][const.group(1)] = int(const.group(2))
        elif opcode == "compare":
            root = _ROOT_LT.search(line)
            if root:
                cur["limit"] = cur["constants"].get(root.group(2))
        if opcode is None or opcode in NO_DEVICE_WORK:
            continue
        cur["ops"][opcode] += 1
        if opcode == "while":
            trip = _TRIP.search(line)
            cond, body = _WHILE.search(line).groups()
            cur["whiles"].append(
                (body, int(trip.group(1)) if trip else cond))
    for comp in comps.values():
        comp["whiles"] = [
            (body, trip if isinstance(trip, int) else comps[trip]["limit"])
            for body, trip in comp["whiles"]
        ]
    return comps


def while_tree(comps: Dict[str, Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One row per ``while`` body reachable from the entry computation,
    outermost first, depth-first."""
    rows: List[Dict[str, Any]] = []

    def visit(name: str, depth: int, trip: Optional[int]) -> int:
        comp = comps[name]
        row = {
            "body": name, "depth": depth, "trip_count": trip,
            "ops": sum(comp["ops"].values()),
            "by_opcode": dict(comp["ops"].most_common()),
        }
        rows.append(row)
        inner = 0
        for body, body_trip in comp["whiles"]:
            inner += visit(body, depth + 1, body_trip) * (body_trip or 1)
        row["ops_with_inner"] = row["ops"] + inner
        return row["ops_with_inner"]

    entry = next(name for name, c in comps.items() if c["entry"])
    visit(entry, 0, 1)
    return rows


def cell_program(manifest, cell, unit: str, epochs: Optional[int] = None,
                 max_steps: Optional[int] = None):
    """``(jitted, shapes, steps)`` of the cell's whole ``fleet.exact`` or of
    its final fit alone (the longest of the program's four), vmapped over
    the chunk's machines; ``shapes`` are the arguments as
    ``ShapeDtypeStruct``s, still without a device.  ``epochs`` and
    ``max_steps`` shorten the fit alone (the same step, fewer of them: what
    ``scripts/fit_step_chip.py`` traces)."""
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import fleet_build as kind
    from gordo_tpu import serializer
    from gordo_tpu.parallel import fleet as fleet_mod
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition
    from gordo_tpu.train.fit import batch_geometry, make_fit_fn

    config = manifest.config(cell["config"])
    pinned = json.loads(json.dumps(config))
    if pinned["model"].get("compute_dtype", "auto") == "auto":
        pinned["model"]["compute_dtype"] = "bfloat16"
    doc = kind.project_doc(pinned, 0, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    builder = FleetDiffBuilder(spec)
    ds = config["dataset"]
    m, rows, tags = (int(config["deployment"]["max_bucket_size"]),
                     int(ds["rows"]), int(ds["n_tags"]))
    ctx = builder._group_context(rows, tags, tags)
    cfg = spec.train_cfg
    if epochs is not None:
        cfg = dataclasses.replace(cfg, epochs=epochs)
    windows = rows - ctx.offset
    if max_steps is not None:
        windows = min(windows, max_steps * cfg.batch_size)
    steps, bs, n_pad = batch_geometry(windows, cfg.batch_size)
    f32, shape = jnp.float32, jax.ShapeDtypeStruct
    if unit == "program":
        program = builder._group_program(ctx, padded=False, warm=False)
        shapes = (shape((m, rows, tags), f32), shape((m, rows, tags), f32),
                  shape((m,), jnp.uint32))
        return program._jitted, shapes, steps
    n = steps * bs
    keys = shape((m, 2), jnp.uint32)
    params = jax.eval_shape(
        lambda k: fleet_mod.fleet_init(
            ctx.module, k, jnp.zeros((1, ctx.lookback, tags), f32)),
        keys,
    )
    vfit = jax.jit(jax.vmap(
        make_fit_fn(ctx.module, cfg, steps, bs), in_axes=(0, 0, 0, None, 0)))
    shapes = (params, shape((m, n, ctx.lookback, tags), f32),
              shape((m, n, tags), f32), shape((n,), f32), keys)
    return vfit, shapes, steps


def count(manifest, cell, unit: str, hlo_dir: Optional[str]) -> Dict[str, Any]:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    jitted, shapes, steps = cell_program(manifest, cell, unit)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes,
    )
    t0 = time.time()
    compiled = jitted.lower(*args).compile()
    compile_s = time.time() - t0
    text = compiled.as_text()
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{cell['name']}.{unit}.hlo.txt"), "w") as f:
            f.write(text)
    ma = compiled.memory_analysis()
    rows = while_tree(parse_hlo(text))
    step_scans = [r for r in rows if r["trip_count"] == steps and r["depth"] > 0]
    return {
        "workload": cell["name"], "unit": unit,
        "compile_s": round(compile_s, 1), "hlo_bytes": len(text),
        "argument_gib": ma.argument_size_in_bytes / GIB,
        "temp_gib": ma.temp_size_in_bytes / GIB,
        "final_fit_steps": steps,
        # the step scan(s) of the final fit: own ops, and with the layer scans
        "step": [
            {k: r[k] for k in ("body", "ops", "ops_with_inner", "by_opcode")}
            for r in step_scans
        ],
        "whiles": rows,
    }


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    parser = argparse.ArgumentParser(prog="fleet_program_ops")
    parser.add_argument("--workload", default=None)
    parser.add_argument("--unit", choices=("fit", "program"), default="fit")
    parser.add_argument("--layout", choices=("chosen", "public"), default="chosen")
    parser.add_argument("--hlo-dir", default=None,
                        help="also write each optimised HLO text here")
    args = parser.parse_args(argv)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import manifest as manifest_mod
    from gordo_tpu.train import fit as fit_mod

    if args.layout == "public":
        fit_mod.packed_layout = lambda module, cfg: False
    manifest = manifest_mod.Manifest()
    for cell in manifest.doc["workloads"]:
        if args.workload not in (None, cell["name"]):
            continue
        out = count(manifest, cell, args.unit, args.hlo_dir)
        out["layout"] = args.layout
        whiles = out.pop("whiles")
        print(json.dumps(out), flush=True)
        for r in whiles:
            top = ", ".join(f"{k} {v}" for k, v in list(r["by_opcode"].items())[:6])
            print(f"{'  ' * r['depth']}{r['body']} x{r['trip_count']}: "
                  f"{r['ops']} ops ({r['ops_with_inner']} with inner): {top}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
