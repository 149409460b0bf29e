"""From the host's enqueue to the device's first operation: the end of the
traced window's longest device gap (the next program's first operation)
minus the end of the last ``span`` event (the program's enqueue call) that
began before it.  The program works out when each fleet program started
from the end of that call; this is the check of that stamp against the
device's own clock.  Negative where the device started before the call
returned.

Spec: ``span``, ``program_prefix``.  ``None`` where the trace holds no
event of the program at all; 0 where it has spans but no enqueue began in
the traced window before the gap's end (the next program was already
queued, so nothing was waited for)."""

from benchmark.readers.trace_gap_span_overlap import longest_gap, program_events


def read(spec, record):
    trace = record.get("trace")
    if trace is None or not trace.has_device_ops:
        return None
    events = program_events(trace, spec["program_prefix"])
    gap = longest_gap(trace)
    if not events or gap is None:
        return None
    calls = [(a, b) for name, a, b in events
             if name == spec["span"] and a <= gap[1]]
    if not calls:
        return 0.0
    return gap[1] - max(calls)[1]
