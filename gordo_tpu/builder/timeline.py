"""The chunk timeline of a project build: where each chunk's seconds went,
on the host and on the device, from inside ``build_project``.

Every phase boundary of a chunk is one ``telemetry.span`` named
``gordo.build.<phase>`` (so it also lies on the clock of any open profiler
session), kept here as an interval on the chunk's row and (all but
``program_wait``) observed into
``gordo_build_pipeline_stage_seconds{stage=<phase>}``:

============ ============ =============================================
phase        thread       what
============ ============ =============================================
load         loader pool  a chunk's ingest load (``_load_chunk_ingest``;
                          the ingest plane's stages are spans inside it,
                          ``gordo.build.load.<stage>``)
load_wait    drive        the drive loop blocked on that load
stage        drive        pad + stack + H2D through ``mesh.place()``
enqueue      drive        the ``program(*args)`` call alone
program_wait watcher      enqueued to the program's smallest output ready
fetch        drive        blocking D2H of a chunk's results
assemble     drive        per-machine detectors from the fetched tree
handoff      drive        manifest, baselines, metadata, writer hand-off
write        writer pool  one pack (v2) or one artifact (v1); a pack's
                          stages are span records inside it,
                          ``gordo.build.write.<stage>``, and the
                          row's ``counts.write`` (``serialize_s``,
                          ``file_s``, ``fsync_s``)
============ ============ =============================================

The device's side is worked out from two host stamps per fleet program:
the end of its enqueue call, and the return of a wait on its smallest
output (the ``gordo.build.program_wait`` span of a watcher thread, see
``parallel/anomaly.py``).  One chip runs its programs in order, so for
program k ``start_k = max(enqueued_k, ready_{k-1})``,
``program_k = ready_k - start_k`` and ``device_gap_k = start_k -
ready_{k-1}``: the label values ``program`` and ``device_gap`` of the same
histogram, the ``gordo_build_device_idle_seconds`` counter, and one
``gordo.build.device_gap`` span-log record per program that carries the
gap's split over the host phases open during it — all three as soon as the
program's ready stamp is in, so a reader that takes the histogram's delta
over a window of time gets the programs and gaps that ENDED in it.
``fetch_exposed`` is the part of a chunk's fetch + assemble during which no
program ran: the collect time the dispatch/collect overlap failed to hide.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from gordo_tpu import telemetry

SPAN_PREFIX = "gordo.build."
GAP_SPAN = SPAN_PREFIX + "device_gap"
#: the drive thread's phases; a gap's split adds them up as they stand,
#: the three that finish the previous chunk under one name
DRIVE_PHASES = ("load_wait", "stage", "enqueue", "fetch", "assemble", "handoff")
FINISH_PHASES = ("fetch", "assemble", "handoff")
#: what ``telemetry.span`` itself puts on a span; the rest is what callers
#: and ``add_to_span`` counted onto it
SPAN_FIELDS = frozenset({"id", "parent", "start", "end", "seconds", "chunk"})

STAGE_SECONDS = telemetry.histogram(
    "gordo_build_pipeline_stage_seconds",
    "Seconds per pipeline stage unit (load, load_wait, stage, enqueue, "
    "dispatch, fetch, assemble, handoff, device, fetch_exposed: one chunk; "
    "program, device_gap: one fleet program, which is one chunk unless its "
    "machines differ in length; load with the ingest plane off: one "
    "machine; write: one pack or artifact; write.serialize, write.file, "
    "write.fsync: one pack's seconds in each stage of its write, observed "
    "by artifacts/pack.py inside or outside a build)",
    labels=("stage",),
)
DEVICE_IDLE_SECONDS = telemetry.counter(
    "gordo_build_device_idle_seconds",
    "Seconds between one fleet program's end on the device and the next "
    "one's start (from build start for the first), by the host's stamps: "
    "load, staging and collect time the pipeline failed to hide behind "
    "device compute",
)

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        elif end > start:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def intersect(intervals: Iterable[Interval],
              within: Iterable[Interval]) -> List[Interval]:
    """The parts of ``intervals`` (merged) that lie inside ``within``."""
    spans = union(intervals)
    return union(
        (max(a, lo), min(b, hi))
        for lo, hi in union(within) for a, b in spans
    )


def subtract(intervals: Iterable[Interval],
             minus: Iterable[Interval]) -> List[Interval]:
    """The parts of ``intervals`` (merged) that lie outside ``minus``."""
    out = []
    for a, b in union(intervals):
        for lo, hi in union(minus):
            if hi <= a or lo >= b:
                continue
            if lo > a:
                out.append((a, lo))
            a = max(a, hi)
        if b > a:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


class DeviceOccupancy:
    """When each fleet program ran on the device, from the host's two
    stamps per program, and the idle seconds between them — the
    ``gordo_build_device_idle_seconds`` series.

    ``enqueued(t)`` comes from the drive thread when a program's enqueue
    call has returned, ``ready(k, t)`` from that program's watcher thread
    when its output is there; programs resolve in order, each once its
    predecessor has, and ``on_resolved`` (if given) is then called with a
    copy of its record on the thread that brought the stamp.  The first
    program's gap runs from ``t0`` (build start: plan, load and compile
    included)."""

    def __init__(self, t0: float,
                 on_resolved: Optional[Callable[[Dict[str, Any]], None]] = None):
        self._on_resolved = on_resolved
        self._lock = threading.Lock()
        self._programs: List[Dict[str, Any]] = []
        self._resolved = 0
        self._last_ready = t0
        self.idle_seconds = 0.0

    def enqueued(self, t: float, **attrs: Any) -> int:
        with self._lock:
            self._programs.append({"enqueued": t, "ready": None, **attrs})
            return len(self._programs) - 1

    def ready(self, k: int, t: float) -> None:
        idle, resolved = 0.0, []
        with self._lock:
            self._programs[k]["ready"] = t
            while (self._resolved < len(self._programs)
                   and self._programs[self._resolved]["ready"] is not None):
                p = self._programs[self._resolved]
                p["idle_from"] = self._last_ready
                p["start"] = max(p["enqueued"], self._last_ready)
                # two watchers may stamp a few microseconds out of order
                p["ready"] = max(p["ready"], p["start"])
                idle += p["start"] - p["idle_from"]
                self._last_ready = p["ready"]
                self._resolved += 1
                resolved.append(dict(p))
            self.idle_seconds += idle
        if idle:
            DEVICE_IDLE_SECONDS.inc(idle)
        if self._on_resolved is not None:
            for p in resolved:
                self._on_resolved(p)

    def busy(self) -> List[Interval]:
        """The resolved programs' ``[start, ready]``; a program enqueued
        but not yet ready counts as running from its start on."""
        with self._lock:
            out = [(p["start"], p["ready"])
                   for p in self._programs[:self._resolved]]
            if self._resolved < len(self._programs):
                pending = self._programs[self._resolved]
                out.append((max(pending["enqueued"], self._last_ready),
                            float("inf")))
            return out


class ChunkClock:
    """What one chunk's fleet builder is handed: spans that land on the
    chunk's row, and a ready stamp for every program it enqueues."""

    def __init__(self, timeline: "BuildTimeline", chunk: int):
        self._timeline = timeline
        self.chunk = chunk

    def span(self, name: str, **attrs: Any):
        return self._timeline.phase(name, self.chunk, observe=False, **attrs)

    def enqueued(self, t: float) -> Callable[[float], None]:
        """A program's enqueue call returned at ``t``; the callable takes
        the stamp at which its output was ready."""
        occupancy = self._timeline.occupancy
        return functools.partial(
            occupancy.ready, occupancy.enqueued(t, chunk=self.chunk))


class BuildTimeline:
    """One row per chunk: every phase's intervals, its programs with their
    gaps and the gaps' split, and the exposed part of its collect."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.occupancy = DeviceOccupancy(t0, self._program_resolved)
        self._lock = threading.Lock()
        self._rows: Dict[int, Dict[str, Any]] = {}

    def _row(self, chunk: int) -> Dict[str, Any]:
        return self._rows.setdefault(chunk, {"chunk": chunk, "phases": {}})

    @contextlib.contextmanager
    def phase(self, name: str, chunk: int, observe: bool = True,
              **attrs: Any) -> Iterator[Dict[str, Any]]:
        """The span ``gordo.build.<name>`` of ``chunk``, kept on its row
        with the numbers counted onto it (``counts``: ``bytes`` and
        ``leaves`` placed in ``stage``, ``carry_leaves`` of the fits traced
        in ``enqueue``), summed where a phase comes more than once;
        ``observe`` puts its seconds into the stage histogram at once
        (phases that come once per group are summed per chunk instead, by
        :meth:`observe`)."""
        sp: Dict[str, Any] = {}
        try:
            with telemetry.span(SPAN_PREFIX + name, chunk=chunk, **attrs) as sp:
                yield sp
        finally:
            if "end" in sp:  # telemetry was on when the span opened
                with self._lock:
                    row = self._row(chunk)
                    row["phases"].setdefault(name, []).append(
                        (sp["start"], sp["end"]))
                    for key, value in sp.items():
                        if key not in SPAN_FIELDS and type(value) in (int, float):
                            counts = row.setdefault("counts", {}).setdefault(name, {})
                            counts[key] = counts.get(key, 0) + value
                if observe:
                    STAGE_SECONDS.observe(sp["seconds"], name)

    def observe(self, chunk: int, *names: str) -> None:
        """One observation per name: the chunk's seconds in that phase."""
        with self._lock:
            phases = dict(self._rows.get(chunk, {}).get("phases", ()))
        for name in names:
            if name in phases:
                STAGE_SECONDS.observe(total(phases[name]), name)

    def clock(self, chunk: int) -> ChunkClock:
        return ChunkClock(self, chunk)

    def _intervals(self, name: str) -> List[Interval]:
        return [iv for row in self._rows.values()
                for iv in row["phases"].get(name, ())]

    def split(self, lo: float, hi: float) -> Dict[str, float]:
        """Seconds of ``[lo, hi]`` by what the host was doing, as parts
        that add up to it: the drive thread's ``stage``, ``enqueue`` and
        ``finish`` (fetch, assemble and hand-off of an earlier chunk);
        of what they leave, ``load`` where the loader was at work and
        ``load_wait`` where the drive thread waited for it all the same;
        ``other`` for the rest."""
        with self._lock:
            by_phase = {name: self._intervals(name)
                        for name in DRIVE_PHASES + ("load",)}
        rest = [(lo, hi)]
        out = {}
        for name, phases in (("stage", ("stage",)), ("enqueue", ("enqueue",)),
                             ("finish", FINISH_PHASES)):
            held = intersect(
                [iv for phase in phases for iv in by_phase[phase]], rest)
            out[name] = total(held)
            rest = subtract(rest, held)
        for name in ("load", "load_wait"):
            held = intersect(by_phase[name], rest)
            out[name] = total(held)
            rest = subtract(rest, held)
        out["other"] = total(rest)
        return out

    def _program_resolved(self, p: Dict[str, Any]) -> None:
        """A program's interval is known (its ready stamp and its
        predecessor's are in): observe ``program`` and ``device_gap``,
        write the gap's ``gordo.build.device_gap`` record with its split,
        and keep the program on its chunk's row.  Every phase the gap can
        overlap closed before the program was enqueued."""
        entry = {
            "enqueued": p["enqueued"], "start": p["start"],
            "ready": p["ready"], "program_s": p["ready"] - p["start"],
            "device_gap_s": p["start"] - p["idle_from"],
            "gap_split": self.split(p["idle_from"], p["start"]),
        }
        telemetry.record_span(
            GAP_SPAN, p["idle_from"], p["start"], chunk=p["chunk"],
            program_s=round(entry["program_s"], 6),
            **{k: round(v, 6) for k, v in entry["gap_split"].items()},
        )
        STAGE_SECONDS.observe(entry["program_s"], "program")
        STAGE_SECONDS.observe(entry["device_gap_s"], "device_gap")
        with self._lock:
            self._row(p["chunk"]).setdefault("programs", []).append(entry)

    def chunk_collected(self, chunk: int) -> None:
        """A chunk's programs have ended and its collect has returned:
        sum its programs onto its row and observe ``fetch_exposed``, the
        part of its fetch + assemble during which no program ran."""
        with self._lock:
            row = self._rows.get(chunk)
            if row is None or not row.get("programs"):
                return
            collect = [iv for name in ("fetch", "assemble")
                       for iv in row["phases"].get(name, ())]
        exposed = total(subtract(collect, self.occupancy.busy()))
        STAGE_SECONDS.observe(exposed, "fetch_exposed")
        with self._lock:
            row["program_s"] = sum(p["program_s"] for p in row["programs"])
            row["device_gap_s"] = sum(p["device_gap_s"] for p in row["programs"])
            row["fetch_exposed_s"] = exposed

    def rows(self) -> List[Dict[str, Any]]:
        """The rows in chunk order, stamps in seconds from build start."""
        rel = lambda t: round(t - self.t0, 6)  # noqa: E731
        out = []
        with self._lock:
            rows = [dict(self._rows[c]) for c in sorted(self._rows)]
        for row in rows:
            row["phases"] = {
                name: [[rel(a), rel(b)] for a, b in ivs]
                for name, ivs in row["phases"].items()
            }
            row["programs"] = [
                {**p, **{k: rel(p[k]) for k in ("enqueued", "start", "ready")}}
                for p in row.get("programs", ())
            ]
            out.append(row)
        return out

    def write(self, path: str) -> None:
        """The rows as one JSON document (atomic, like the snapshot)."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"gordo_build_timeline": 1, "t0": self.t0,
                       "trace": telemetry.current_trace_id(),
                       "device_idle_seconds": self.occupancy.idle_seconds,
                       "chunks": self.rows()}, f, indent=1)
        os.replace(tmp, path)
