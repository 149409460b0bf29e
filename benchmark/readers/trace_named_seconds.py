"""Device seconds of a fleet program's operations by what names them, per
optimiser step, from ONE pass over the traced window's ``.xplane.pb``.

``trace_scope_seconds`` parses the file once per scope and knows scopes
alone.  Since PR 39 the program names what it does outside the mixers too
(``fit.optimizer``, ``fit.loss``, ``fit.draw``, ``fit.forecast``,
``backbone.embed`` / ``.head`` / ``.norm`` / ``.stack`` / ``.residual``),
and one kind of kernel can be named by no scope at all: the TPU compiler rewrites
``lax.ragged_dot`` into custom calls ``%ragged-dot-none.N`` and
``%ragged-dot-metadata.N`` whose ``tf_op`` is ``ragged-dot-none:`` (the op
name the enclosing ``jax.named_scope`` gave them is gone after the rewrite),
so those are read by their HLO name.  Nothing else in these programs emits
an instruction of that name.

:func:`table` reduces the first device's operations inside WHOLE programs
to rows keyed by ``(the names in tf_op, the HLO name's head)``, each with
its seconds, the events' own ``flops`` and ``bytes_accessed`` (fields of the
event's metadata, per execution) and the number of events, and keeps the
result in the run record: every metric of this reader, of
``trace_kernel_roofline`` and of ``trace_unnamed_share`` reads that one
table.  A whole program is a module at least nine tenths as long as the
MIDDLE one of the trace's long modules (those at least half as long as the
longest: the fleet program's executions, whole or cut by the window's ends,
and none of the small programs beside them).  The stamps' mean, which
``trace_scope_seconds`` takes, rises with a stalled machine and then drops
every program of a sound run (PERF.md section 7), and the longest module is
itself a stalled one in one traced run of three (a program beside a slow
fetch ran 11 % long and the rule dropped the three sound ones' sibling,
PERF.md section 6, PR 39); the middle one is neither.  Control flow's
own events (``while``, ``conditional``, ``call``, by the HLO name's head as
``trace_scope_seconds`` has it, and by ``hlo_category``: the chip names a
``lax.cond``'s conditional ``%cond.N``) span the operations inside them and
are left out.

Spec: ``names`` (scope names; an operation counts when any stands in its
``tf_op``) and / or ``kernels`` (heads of HLO names, matched as prefixes).
An operation that matches both counts once.  The seconds are divided by
whole programs x the configuration's optimiser steps a machine.  ``None``
where the run has no trace, the trace no device plane or no whole program,
the message classes cannot be found, or nothing matches (a parent without
the name).
"""

from __future__ import annotations

import re
import time
from typing import Any, Dict, Optional, Tuple

from benchmark.readers import trace_scope_seconds as scope_reader

#: a name a ``jax.named_scope`` of the program gives: ``backbone.moe.experts``,
#: ``fit.optimizer``; inside ``transpose(jvp(backbone.mla))`` too
NAME = re.compile(r"(?<![\w.])(?:backbone|fit)(?:\.\w+)+")
CATEGORY_FIELD = "hlo_category"
WORK_FIELDS = ("flops", "bytes_accessed")
_KEY = "_named_table"

Row = Tuple[Tuple[str, ...], str]


def whole(modules):
    """The whole executions of the fleet program among a device's modules
    (``(start, end)`` in picoseconds): those at least nine tenths as long as
    the middle one of the modules at least half as long as the longest."""
    if not modules:
        return []
    longest = max(b - a for a, b in modules)
    long_ones = sorted(b - a for a, b in modules if 2 * (b - a) >= longest)
    return scope_reader.whole_programs(modules, long_ones[len(long_ones) // 2] * 1e-12)


def operations(plane, wanted=()):
    """``metadata id -> (the HLO name's head, {field: value})`` for a device
    plane's operations, ``None`` for control flow's own events; the fields
    are those of ``wanted`` beside ``tf_op`` and ``hlo_category``."""
    fields = {i: meta.name for i, meta in plane.stat_metadata.items()
              if meta.name in (scope_reader.SCOPE_FIELD, CATEGORY_FIELD, *wanted)}
    seen: Dict[int, Optional[Tuple[str, Dict[str, Any]]]] = {}

    def of(metadata_id: int):
        if metadata_id not in seen:
            meta = plane.event_metadata[metadata_id]
            found: Dict[str, Any] = {}
            for stat in meta.stats:
                field = fields.get(stat.metadata_id)
                if field is not None:
                    kind = stat.WhichOneof("value")
                    value = getattr(stat, kind)
                    if kind == "ref_value":
                        value = plane.stat_metadata[value].name
                    found[field] = value
            head = meta.name.split(" ")[0].lstrip("%").split(".")[0]
            control = (head in scope_reader.CONTROL_FLOW
                       or str(found.get(CATEGORY_FIELD, "")) in scope_reader.CONTROL_FLOW)
            seen[metadata_id] = None if control else (head, found)
        return seen[metadata_id]

    return of


def first_device(path: str):
    """``(plane, {line name: line})`` of the first device of an
    ``.xplane.pb``, or ``None``."""
    messages = scope_reader.xplane_messages()
    if messages is None:
        return None
    space = messages.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = sorted((p for p in space.planes
                     if p.name.startswith(scope_reader.DEVICE_PLANE_PREFIX)),
                    key=lambda p: p.name)
    if not planes:
        return None
    lines = {line.name: line for line in planes[0].lines}
    if scope_reader.OPS_LINE not in lines or scope_reader.MODULES_LINE not in lines:
        return None
    return planes[0], lines


def table(path: str) -> Optional[Dict[str, Any]]:
    """``{"programs", "program_s", "events", "rows": {(names, head):
    [seconds, flops, bytes, events]}}`` of the first device's whole
    programs, or ``None``."""
    device = first_device(path)
    if device is None:
        return None
    plane, lines = device
    programs = whole([(ev.offset_ps, ev.offset_ps + ev.duration_ps)
                      for ev in lines[scope_reader.MODULES_LINE].events])
    if not programs:
        return None
    operation = operations(plane, WORK_FIELDS)
    keys: Dict[int, Optional[Tuple[Row, float, float]]] = {}

    def key_of(metadata_id: int):
        if metadata_id not in keys:
            found = operation(metadata_id)
            if found is None:
                keys[metadata_id] = None
            else:
                head, fields = found
                names = tuple(sorted(set(NAME.findall(
                    str(fields.get(scope_reader.SCOPE_FIELD, ""))))))
                keys[metadata_id] = ((names, head), float(fields.get("flops", 0) or 0),
                                     float(fields.get("bytes_accessed", 0) or 0))
        return keys[metadata_id]

    rows: Dict[Row, list] = {}
    events = 0
    for ev in lines[scope_reader.OPS_LINE].events:
        key = key_of(ev.metadata_id)
        if key is None:
            continue
        start, end = ev.offset_ps, ev.offset_ps + ev.duration_ps
        if not any(a <= start and end <= b for a, b in programs):
            continue
        row = rows.setdefault(key[0], [0.0, 0.0, 0.0, 0])
        row[0] += ev.duration_ps * 1e-12
        row[1] += key[1]
        row[2] += key[2]
        row[3] += 1
        events += 1
    return {
        "programs": len(programs),
        "program_s": sum(b - a for a, b in programs) * 1e-12 / len(programs),
        "events": events,
        "rows": rows,
    }


def table_of(record) -> Optional[Dict[str, Any]]:
    """The run's table, built at the first metric that asks for it."""
    from benchmark import trace as trace_mod

    if not record.get("trace_dir"):
        return None
    if _KEY not in record:
        path = trace_mod.find_xplane(record["trace_dir"])
        started = time.perf_counter()
        record[_KEY] = table(path) if path is not None else None
        if record[_KEY] is not None:
            print(f"trace_named_seconds: {record[_KEY]['events']} operations of "
                  f"{record[_KEY]['programs']} whole programs read in "
                  f"{time.perf_counter() - started:.2f} s", flush=True)
    return record[_KEY]


def matches(row: Row, names=(), kernels=()) -> bool:
    found, head = row
    return (any(name in scope for name in names for scope in found)
            or any(head.startswith(kernel) for kernel in kernels))


def totals(found: Dict[str, Any], names=(), kernels=()) -> Tuple[float, float, float]:
    """``(seconds, flops, bytes)`` of the rows that match."""
    picked = [v for row, v in found["rows"].items() if matches(row, names, kernels)]
    return (sum(v[0] for v in picked), sum(v[1] for v in picked),
            sum(v[2] for v in picked))


def read(spec, record) -> Optional[float]:
    found = table_of(record)
    if found is None:
        return None
    seconds, _, _ = totals(found, spec.get("names", ()), spec.get("kernels", ()))
    steps = found["programs"] * int(record["work_per_chunk"]["steps_per_model"])
    if seconds <= 0 or steps <= 0:
        return None
    return seconds / steps
