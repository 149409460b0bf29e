"""Device seconds under one ``jax.named_scope`` of the program per
optimiser step, from the traced window's ``.xplane.pb`` itself.

``benchmark/trace.py`` reads the file through ``jax.profiler.ProfileData``,
which gives an event's name and its own fields; the scope an operation was
traced under is a field of the event's METADATA (``tf_op``:
``jit(program)/while/body/.../backbone.kda/backbone.kda.scan/dot_general``,
found on the chip, PR 29), which that reader does not hand out.  So the file
is parsed as the protocol buffer it is, with the message classes that the
installed ``tensorflow`` wheel carries (``tsl/profiler/protobuf/xplane_pb2.py``,
loaded from its file: importing ``tensorflow`` itself is neither needed nor
wanted in the process that holds the chip).

An operation belongs to a scope when the scope's name stands in its
``tf_op``; the backward pass's operations carry it inside
``transpose(jvp(..))`` and a rematerialised part's inside its checkpoint's
name, so all three passes of a step count.  A loop's or a branch's own event
spans the operations inside it, which have events of their own, and is left
out.  Only whole executions of the fleet program are read
(:func:`whole_programs`), so ``trace_seconds`` has to hold one.  Steps are
those programs times the configuration's optimiser steps a machine
(``work_per_chunk``); the folds' held-out forecasts run inside the same
programs and are counted with the steps they follow (about a twentieth of a
scope's seconds).

Spec: ``scope`` (the named scope).  ``None`` where the run has no trace, the
trace no device plane, no whole program lies inside it, the message classes
cannot be found, or no operation carries the scope (a program without the
scope, as the parent of the PR that added it).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE_FIELD = "tf_op"
CONTROL_FLOW = ("while", "conditional", "call")
_PROTO = os.path.join("tsl", "profiler", "protobuf", "xplane_pb2.py")


def xplane_messages():
    """The generated message classes of ``xplane.proto``, or ``None``."""
    try:
        spec = importlib.util.find_spec("tensorflow")
        for root in (spec.submodule_search_locations if spec else ()):
            path = os.path.join(root, _PROTO)
            if os.path.exists(path):
                module_spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
                module = importlib.util.module_from_spec(module_spec)
                module_spec.loader.exec_module(module)
                return module
    except Exception:
        return None
    return None


def whole_programs(modules: Sequence[Tuple[int, int]],
                   program_seconds: Optional[float]) -> List[Tuple[int, int]]:
    """The whole executions of the fleet program among a device's modules
    (``(start, end)`` in picoseconds).  A program that was running when the
    trace opened or closed has an event cut to the part inside, so a whole
    one is at least nine tenths of ``program_seconds`` long (the program's
    mean seconds by its own stamps); without that figure, at least nine
    tenths of the longest module."""
    if not modules:
        return []
    if not program_seconds:
        program_seconds = max(end - start for start, end in modules) * 1e-12
    return [(a, b) for a, b in modules if (b - a) * 1e-12 >= 0.9 * program_seconds]


def scope_seconds(path: str, scopes: Sequence[str],
                  program_seconds: Optional[float] = None,
                  ) -> Optional[Tuple[Dict[str, float], int]]:
    """``({scope: device seconds}, whole programs)`` of the first device."""
    messages = xplane_messages()
    if messages is None:
        return None
    space = messages.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = sorted((p for p in space.planes if p.name.startswith(DEVICE_PLANE_PREFIX)),
                    key=lambda p: p.name)
    if not planes:
        return None
    plane = planes[0]
    field_ids = {i for i, meta in plane.stat_metadata.items() if meta.name == SCOPE_FIELD}
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines or MODULES_LINE not in lines:
        return None
    programs = whole_programs(
        [(ev.offset_ps, ev.offset_ps + ev.duration_ps) for ev in lines[MODULES_LINE].events],
        program_seconds)
    if not programs:
        return None

    scopes_of: Dict[int, Tuple[str, ...]] = {}

    def scopes_under(metadata_id: int) -> Tuple[str, ...]:
        if metadata_id not in scopes_of:
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
            scopes_of[metadata_id] = () if head in CONTROL_FLOW else tuple(
                scope for scope in scopes if scope in text)
        return scopes_of[metadata_id]

    totals = {scope: 0.0 for scope in scopes}
    for ev in lines[OPS_LINE].events:
        found = scopes_under(ev.metadata_id)
        if not found:
            continue
        start, end = ev.offset_ps, ev.offset_ps + ev.duration_ps
        if any(a <= start and end <= b for a, b in programs):
            for scope in found:
                totals[scope] += ev.duration_ps * 1e-12
    return totals, len(programs)


PROGRAM_SERIES = ("gordo_build_pipeline_stage_seconds", ["program"])


def program_seconds(record) -> Optional[float]:
    """Mean seconds a fleet program held the device inside the window, by
    the program's own stamps (what ``seq.program_s_per_model`` reads)."""
    from benchmark.readers import series_state

    end = series_state(record["snap_end"], *PROGRAM_SERIES)
    if end is None:
        return None
    start = series_state(record["snap_start"], *PROGRAM_SERIES) or {"sum": 0.0, "count": 0}
    count = end["count"] - start["count"]
    return (end["sum"] - start["sum"]) / count if count > 0 else None


def per_step(spec, record) -> Optional[float]:
    """Seconds under ``spec["scope"]`` per optimiser step, or ``None``."""
    from benchmark import trace as trace_mod

    if not record.get("trace_dir"):
        return None
    path = trace_mod.find_xplane(record["trace_dir"])
    if path is None:
        return None
    cache = record.setdefault("_scope_seconds", {})
    if spec["scope"] not in cache:
        cache[spec["scope"]] = scope_seconds(
            path, [spec["scope"]], program_seconds(record))
    found = cache[spec["scope"]]
    if found is None:
        return None
    totals, programs = found
    steps = programs * int(record["work_per_chunk"]["steps_per_model"])
    if totals[spec["scope"]] <= 0 or steps <= 0:
        return None
    return totals[spec["scope"]] / steps


def read(spec, record):
    return per_step(spec, record)
