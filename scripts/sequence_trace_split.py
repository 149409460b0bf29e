"""Where a sequence cell's program spends its device seconds, by named scope
and by the jax primitive an operation was traced from.

    python scripts/sequence_trace_split.py --label parent [--seed 7] [--workload <cell>] [--sources]

Chip only.  Runs ONE traced benchmark run of a sequence cell
(``kimi-linear.build-series`` unless ``--workload`` names another;
``python3 -m benchmark.run --trace 1``, unchanged) and, before the run's
scratch directory is removed, reads the ``.xplane.pb`` the way
``benchmark/readers/trace_scope_seconds.py`` does: the operations of whole
executions of the fleet program, control flow's own events left out.  Each
operation is booked under the innermost ``backbone.*`` or ``fit.*`` name in
its ``tf_op`` (a scope inside the multi-token-prediction module's as
``backbone.mtp/<scope>``; ``fit.forecast`` where a held-out forecast's
operation has no name of its own), the held experts' ``ragged_dot`` kernels,
which no scope reaches, under ``ragged-dot*`` by their HLO name, the rest
under ``unscoped``; and under that path's last component, the primitive
(``triangular_solve``, ``dot_general``, ...).  Prints the split, then what
``benchmark/readers/trace_named_seconds.py`` and its two siblings read from
the same file under the specs of the cell's new metrics (the readings of
``lfm2-moe.build-fortnight``, whose cell lists none of them, come from
here), then every metric that ``benchmark/pending/*.per_layer.json`` holds
for the cell (the ``trinity.*`` nineteen, which ``BENCHMARK.json`` cannot
list yet: that file says why), read as ``benchmark.run`` would read them,
and writes all three to ``chiprun_out/trace_split/<label>.json``.
``--sources`` books the unscoped operations by their ``source`` field too
(file:line of the innermost user frame) and prints the top rows: the tool
that says which line of the program still has no name.  The benchmark's
own result line stays the last line of the output.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KERNEL = "ragged-dot"
SOURCE_FIELD = "source"
#: the cell whose metric files hold the specs of the five readings PR 39
#: added (the same under every cell's prefix)
SPECS_OF = "kimi-linear"
READINGS = ("moe_with_kernels_s_per_step", "ragged_dot_roofline",
            "optimizer_s_per_step", "unnamed_share")
OUT = os.path.join(ROOT, "chiprun_out", "trace_split")
WORKLOAD = "kimi-linear.build-series"


def split(path: str, sources: bool = False):
    """``{"programs", "program_s", "ops_s", "by_scope", "by_scope_primitive"}``
    of the first device's whole programs, seconds a program."""
    from benchmark.readers import trace_named_seconds, trace_scope_seconds as reader

    plane, lines = trace_named_seconds.first_device(path)
    programs = trace_named_seconds.whole(
        [(ev.offset_ps, ev.offset_ps + ev.duration_ps)
         for ev in lines[reader.MODULES_LINE].events])
    operation = trace_named_seconds.operations(plane, (SOURCE_FIELD,))
    keys = {}

    def key_of(metadata_id):
        if metadata_id not in keys:
            found = operation(metadata_id)
            if found is None:
                keys[metadata_id] = None
            else:
                head, fields = found
                text = str(fields.get(reader.SCOPE_FIELD, ""))
                scopes = trace_named_seconds.NAME.findall(text)
                # the innermost: the last in the path, but a pass's mark
                # (fit.forecast) only where nothing else names the operation
                own = [s for s in scopes if s != "fit.forecast"] or scopes
                scope = own[-1] if own else "unscoped"
                if head.startswith(KERNEL):
                    scope = KERNEL + "*"
                elif "backbone.mtp" in scopes and scope != "backbone.mtp":
                    scope = "backbone.mtp/" + scope
                where = str(fields.get(SOURCE_FIELD, "")) if sources and scope in (
                    "unscoped", "fit.forecast") else ""
                keys[metadata_id] = (scope, text.rsplit("/", 1)[-1].rstrip(":") or head,
                                     head, where)
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
    for (scope, _, _, _), s in seconds.items():
        by_scope[scope] += s / n
    row = lambda k, s: {"scope": k[0], "primitive": k[1], "hlo": k[2],  # noqa: E731
                        "source": k[3], "s": s / n, "share": s / n / ops_s,
                        "events": events[k] // n}
    return {
        "programs": len(programs),
        "program_s": sum(b - a for a, b in programs) * 1e-12 / n,
        "ops_s": ops_s,
        "by_scope": {k: {"s": s, "share": s / ops_s} for k, s in by_scope.most_common()},
        "by_scope_primitive": [row(k, s) for k, s in seconds.most_common(60)],
        "unscoped": [row(k, s) for k, s in seconds.most_common()
                     if k[0] in ("unscoped", "fit.forecast")][:60],
    }


def readings(record):
    """What the metrics PR 39 added read in this run, by the benchmark's own
    readers under the specs of the metric files, and three readings beside
    them: the scope and the kernels apart, and the roofline by the events'
    counts alone."""
    from benchmark import manifest as manifest_mod, readers

    manifest = manifest_mod.Manifest()
    specs = {name: manifest.metric_spec(f"{SPECS_OF}.{name}") for name in READINGS}
    with_kernels, roofline = specs["moe_with_kernels_s_per_step"], specs["ragged_dot_roofline"]
    specs["moe_s_per_step"] = {k: v for k, v in with_kernels.items() if k != "kernels"}
    specs["ragged_dot_s_per_step"] = {k: v for k, v in with_kernels.items() if k != "names"}
    specs["ragged_dot_roofline, the events' counts alone"] = {
        k: v for k, v in roofline.items() if k != "rows"}
    return {name: readers.read(spec, record) for name, spec in specs.items()}


def pending(record, workload):
    """What the per-layer metrics read that wait under ``benchmark/pending``
    for a place in ``BENCHMARK.json``: those that list ``workload``, each by
    the reader its spec file names; ``None`` where it finds nothing."""
    import glob

    from benchmark import manifest as manifest_mod, readers

    manifest = manifest_mod.Manifest()
    found = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "pending", "*.per_layer.json"))):
        with open(path) as fh:
            entries = json.load(fh)["per_layer"]
        for metric in entries:
            if workload in metric["workloads"]:
                found[metric["name"]] = readers.read(manifest.metric_spec(metric["name"]), record)
    return found


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--label", required=True)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--sources", action="store_true")
    args = parser.parse_args()

    from benchmark import manifest as manifest_mod, run, trace as trace_mod

    manifest = manifest_mod.Manifest()
    traffic = manifest.traffic(manifest.cell(args.workload)["traffic"])
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")

    cleanup = kind.cleanup

    def split_then_cleanup(record):
        path = trace_mod.find_xplane(record.get("trace_dir") or "")
        if path is not None:
            found = split(path, args.sources)
            found["readings"] = readings(record)
            found["pending"] = pending(record, args.workload)
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
            print("  what no name covers, by primitive:")
            for row in found["unscoped"][:40]:
                print(f"    {row['scope']:12s} {row['primitive']:28s} {row['hlo']:36s} "
                      f"{row['s']:.4f} s {100 * row['share']:5.2f} % x{row['events']} "
                      f"{row['source']}")
            for name, value in {**found["readings"], **found["pending"]}.items():
                print(f"  reading {name} = {value!r}")
            sys.stdout.flush()
        cleanup(record)

    kind.cleanup = split_then_cleanup
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", "51", "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
