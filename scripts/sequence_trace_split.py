"""Where a sequence cell's program spends its device seconds, by named scope
and by the jax primitive an operation was traced from.

    python scripts/sequence_trace_split.py --label parent [--seed 7] [--workload <cell>]

Chip only.  Runs ONE traced benchmark run of a sequence cell
(``kimi-linear.build-series`` unless ``--workload`` names another;
``python3 -m benchmark.run --trace 1``, unchanged) and, before the run's
scratch directory is removed, reads the ``.xplane.pb`` the way
``benchmark/readers/trace_scope_seconds.py`` does: the operations of whole
executions of the fleet program, control flow's own events left out.  Each
operation is booked under the innermost ``backbone.*`` scope in its
``tf_op`` (``unscoped`` where it has none; a scope inside the
multi-token-prediction module's as ``backbone.mtp/<scope>``) and under that path's last
component, the primitive (``triangular_solve``, ``dot_general``, ...).
Prints the split and writes it to ``chiprun_out/trace_split/<label>.json``.
The benchmark's own result line stays the last line of the output.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCOPE = re.compile(r"backbone(?:\.[a-z_]+)+")
OUT = os.path.join(ROOT, "chiprun_out", "trace_split")
WORKLOAD = "kimi-linear.build-series"


def split(path: str, program_seconds=None):
    """``{"programs", "program_s", "ops_s", "by_scope", "by_scope_primitive"}``
    of the first device's whole programs, seconds a program."""
    from benchmark.readers import trace_scope_seconds as reader

    messages = reader.xplane_messages()
    space = messages.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    plane = sorted((p for p in space.planes
                    if p.name.startswith(reader.DEVICE_PLANE_PREFIX)),
                   key=lambda p: p.name)[0]
    field_ids = {i for i, meta in plane.stat_metadata.items()
                 if meta.name == reader.SCOPE_FIELD}
    lines = {line.name: line for line in plane.lines}
    programs = reader.whole_programs(
        [(ev.offset_ps, ev.offset_ps + ev.duration_ps)
         for ev in lines[reader.MODULES_LINE].events], program_seconds)

    keys = {}

    def key_of(metadata_id):
        if metadata_id not in keys:
            meta = plane.event_metadata[metadata_id]
            text = ""
            for stat in meta.stats:
                if stat.metadata_id in field_ids:
                    kind = stat.WhichOneof("value")
                    value = getattr(stat, kind)
                    if kind == "ref_value":
                        value = plane.stat_metadata[value].name
                    text = str(value)
            head = meta.name.split(" ")[0].lstrip("%").split(".")[0]
            if head in reader.CONTROL_FLOW:
                keys[metadata_id] = None
            else:
                scopes = SCOPE.findall(text)
                scope = max(scopes, key=len) if scopes else "unscoped"
                if "backbone.mtp" in scopes and scope != "backbone.mtp":
                    scope = "backbone.mtp/" + scope
                keys[metadata_id] = (scope, text.rsplit("/", 1)[-1].rstrip(":") or head, head)
        return keys[metadata_id]

    seconds = collections.Counter()
    events = collections.Counter()
    for ev in lines[reader.OPS_LINE].events:
        key = key_of(ev.metadata_id)
        if key is None:
            continue
        start, end = ev.offset_ps, ev.offset_ps + ev.duration_ps
        if any(a <= start and end <= b for a, b in programs):
            seconds[key] += ev.duration_ps * 1e-12
            events[key] += 1
    n = max(len(programs), 1)
    ops_s = sum(seconds.values()) / n
    by_scope = collections.Counter()
    for (scope, _, _), s in seconds.items():
        by_scope[scope] += s / n
    rows = [{"scope": k[0], "primitive": k[1], "hlo": k[2], "s": s / n,
             "share": s / n / ops_s, "events": events[k] // n}
            for k, s in seconds.most_common(60)]
    return {
        "programs": len(programs),
        "program_s": sum(b - a for a, b in programs) * 1e-12 / n,
        "ops_s": ops_s,
        "by_scope": {k: {"s": s, "share": s / ops_s} for k, s in by_scope.most_common()},
        "by_scope_primitive": rows,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--label", required=True)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--workload", default=WORKLOAD)
    args = parser.parse_args()

    from benchmark import manifest as manifest_mod, run, trace as trace_mod
    from benchmark.readers import trace_scope_seconds as reader

    manifest = manifest_mod.Manifest()
    traffic = manifest.traffic(manifest.cell(args.workload)["traffic"])
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")

    cleanup = kind.cleanup

    def split_then_cleanup(record):
        path = trace_mod.find_xplane(record.get("trace_dir") or "")
        if path is not None:
            found = split(path, reader.program_seconds(record))
            os.makedirs(OUT, exist_ok=True)
            with open(os.path.join(OUT, args.label + ".json"), "w") as fh:
                json.dump(found, fh, indent=1)
            print(f"split {args.label}: {found['programs']} whole programs of "
                  f"{found['program_s']:.3f} s, operations {found['ops_s']:.3f} s")
            for scope, row in found["by_scope"].items():
                print(f"  {scope:28s} {row['s']:.4f} s {100 * row['share']:5.1f} %")
            for row in found["by_scope_primitive"][:40]:
                print(f"    {row['scope']:26s} {row['primitive']:28s} {row['hlo']:14s} "
                      f"{row['s']:.4f} s {100 * row['share']:5.1f} % x{row['events']}")
            sys.stdout.flush()
        cleanup(record)

    kind.cleanup = split_then_cleanup
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", "51", "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
