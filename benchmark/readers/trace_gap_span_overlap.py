"""Seconds of the traced window's longest device gap during which a named
span of the program, or one of its sub-spans, was open on the host.

The program's spans are ``jax.profiler.TraceAnnotation`` events on the host
plane; only ``bench.*`` go to ``Trace.spans``, so they are found in
``Trace.host`` by name.  The profiler records an annotation only if it
BEGAN inside the session, and the traced window opens at a chunk
completion, when the next chunk's load is already under way: so the span's
sub-spans (``<span>.<stage>``, which begin inside the window) count with
it, as one union.  Spec: ``span`` (the event's name), ``program_prefix``
(what every span of the program starts with).  ``None`` where the trace
holds no event of the program at all (a program without spans); 0 where it
has spans but neither this one nor a sub-span was open in the gap."""

from benchmark import trace as trace_mod


def longest_gap(trace):
    """``(start, end)`` of the longest stretch of the window with no
    operation on the first device, or ``None``."""
    lo, hi = trace_mod.window(trace)
    busy = trace_mod.clip(trace.devices[0].busy, lo, hi)
    idle = trace_mod.gaps(busy, lo, hi)
    return max(idle, key=lambda g: g[1] - g[0], default=None)


def program_events(trace, prefix):
    return [ev for ev in trace.host if ev[0].startswith(prefix)]


def read(spec, record):
    trace = record.get("trace")
    if trace is None or not trace.has_device_ops:
        return None
    events = program_events(trace, spec["program_prefix"])
    gap = longest_gap(trace)
    if not events or gap is None:
        return None
    span = spec["span"]
    open_in_gap = trace_mod.clip(
        trace_mod.union((a, b) for name, a, b in events
                        if name == span or name.startswith(span + ".")),
        *gap)
    return trace_mod.total(open_in_gap)
