"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, the traced window, its longest idle
stretch, and a breakdown of where the time went.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU's plane is
named ``/device:TPU:<n>`` and carries a line of whole programs
(``XLA Modules``) and a line of their operations (``XLA Ops``); host
threads are lines of the ``/host:CPU`` plane, where the harness's
``jax.profiler.TraceAnnotation`` spans (named ``bench.*``) land beside
jax's own.  All times are seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
BREAKDOWN_ENTRIES = 10
NAMED_GAPS = 20
BETWEEN_OPS = "between device operations"
# the profiler's buffer holds about 2.5 million device events (read on the
# chip, PERF.md); a trace near that was cut short by the buffer, not by the
# device going idle
EVENT_CAP = 2_000_000

Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, float, float]]       # (name, start, end)
    modules: List[Tuple[str, float, float]]
    _busy: Optional[List[Tuple[float, float]]] = None

    @property
    def busy(self) -> List[Tuple[float, float]]:
        """Merged intervals in which an operation (a program, where the
        trace has no operations) ran: millions of events, merged once."""
        if self._busy is None:
            source = self.ops or self.modules
            self._busy = union((a, b) for _, a, b in source)
        return self._busy


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]      # harness spans on the host
    host: List[Tuple[str, float, float]]       # every other host event

    @property
    def has_device_ops(self) -> bool:
        return any(d.ops or d.modules for d in self.devices)


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    spans: List[Tuple[str, float, float]] = []
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = DeviceTrace(plane.name, [], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in _events(line):
                    (spans if ev[0].startswith(SPAN_PREFIX) else host).append(ev)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, spans, host)


def _events(line) -> List[Tuple[str, float, float]]:
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        out.append((ev.name, start, start + ev.duration_ns * 1e-9))
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``busy`` (merged, clipped to the window) leaves of the window."""
    out, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi))
    return out


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

def overflowed(trace: Trace) -> bool:
    """Whether a device's events reach ``EVENT_CAP``: the profiler then
    stopped recording before the window's end."""
    return any(len(d.ops) >= EVENT_CAP for d in trace.devices)


def window(trace: Trace) -> Interval:
    """The traced window: the harness's ``bench.window`` span, whole, so
    that an idle device at its end counts as idle.  Only where the
    profiler's buffer filled (:func:`overflowed`) does it close at the last
    device event instead.  A trace with no such span (the tests' recorded
    one) runs from its first device event to its last."""
    runs = [
        (a, b) for d in trace.devices
        for a, b in d.busy + union((a, b) for _, a, b in d.modules)
    ]
    for name, start, end in trace.spans:
        if name == WINDOW_SPAN:
            if overflowed(trace):
                end = max([min(b, end) for a, b in runs if a <= end and b >= start]
                          or [end])
            return start, end
    if not runs:
        raise ValueError("the trace holds no device event and no window span")
    return min(a for a, _ in runs), max(b for _, b in runs)


def _device_busy(dev: DeviceTrace, lo: float, hi: float) -> List[Interval]:
    return clip(dev.busy, lo, hi)


def busy_seconds(trace: Trace) -> Tuple[float, float]:
    """``(busy_s, window_s)``: seconds in which an operation ran on the
    device, averaged over the devices traced, and the window's length."""
    lo, hi = window(trace)
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    per_device = [total(_device_busy(d, lo, hi)) for d in trace.devices]
    return sum(per_device) / len(per_device), hi - lo


def longest_gap(trace: Trace) -> float:
    """Seconds of the longest stretch of the window in which no operation
    ran on the first device."""
    lo, hi = window(trace)
    idle = gaps(_device_busy(trace.devices[0], lo, hi), lo, hi)
    return max((b - a for a, b in idle), default=0.0)


def breakdown(trace: Trace) -> Dict[str, List[List]]:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing (the harness span that covers
    most of the gap; ``unattributed`` where none does)."""
    lo, hi = window(trace)
    by_op: Dict[str, float] = {}
    for dev in trace.devices:
        for name, a, b in dev.ops or dev.modules:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                by_op[name] = by_op.get(name, 0.0) + (b - a)
    n_dev = max(len(trace.devices), 1)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]
    ops = [(short_op_name(name), seconds) for name, seconds in ops]
    by_host: Dict[str, float] = {}
    if trace.devices:
        dev = trace.devices[0]
        idle = sorted(gaps(_device_busy(dev, lo, hi), lo, hi),
                      key=lambda g: g[0] - g[1])
        # the long gaps are named one by one; the holes of microseconds
        # between a program's operations, millions of them, are one entry
        for a, b in idle[:NAMED_GAPS]:
            name = _covering_span(trace, a, b)
            by_host[name] = by_host.get(name, 0.0) + (b - a)
        rest = sum(b - a for a, b in idle[NAMED_GAPS:])
        if rest > 0:
            by_host[BETWEEN_OPS] = rest
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]
    return {
        "device_ops": [[name, seconds / n_dev] for name, seconds in ops],
        "idle_gaps": [[name, seconds] for name, seconds in idle],
    }


_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")


def short_op_name(name: str) -> str:
    """``%while.2946 while`` from a line of HLO text: the instruction's name
    and its opcode, without the shapes."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    match = _OPCODE.search(" " + rest)
    return f"{head} {match.group(1)}" if match else head[:80]


def _covering_span(trace: Trace, a: float, b: float) -> str:
    """What the host was doing in a gap: the innermost harness span (the
    shortest) that covers more than half of it, then jax's own host event
    that overlaps it longest without being several times longer."""
    span, span_len = "unattributed", float("inf")
    for name, s, e in trace.spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(b, e) - max(a, s)
        if overlap > 0.5 * (b - a) and (e - s) < span_len:
            span, span_len = name, e - s
    host, host_overlap = None, 0.0
    for name, s, e in trace.host:
        overlap = min(b, e) - max(a, s)
        if overlap > host_overlap and (e - s) <= 4.0 * (b - a):
            host, host_overlap = name, overlap
    return span if host is None else f"{span} > {host[:60]}"


def describe(path: str, top: int = 12) -> str:
    """A trace's planes, lines and most frequent event names: what to read
    by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names: Dict[str, List[float]] = {}
            n = 0
            for ev in line.events:
                n += 1
                slot = names.setdefault(ev.name, [0, 0.0])
                slot[0] += 1
                slot[1] += ev.duration_ns * 1e-9
            out.append(f"  line {line.name!r}: {n} events")
            for name, (count, secs) in sorted(
                names.items(), key=lambda kv: -kv[1][1]
            )[:top]:
                out.append(f"    {count:8d} x {secs:10.4f}s  {name[:100]}")
    return "\n".join(out)
