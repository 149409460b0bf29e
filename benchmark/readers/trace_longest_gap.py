"""Seconds of the longest idle stretch of the device in the traced window.

The traced window opens at a chunk completion (the end of set-up), where
the device waits for the next chunk to be loaded and dispatched, so this is
that wait as the device saw it.  It is what the trace holds and no more: an
idle stretch elsewhere in a chunk is outside the traced seconds."""

from benchmark import trace as trace_mod


def read(spec, record):
    trace = record.get("trace")
    if trace is None or not trace.has_device_ops:
        return None
    return trace_mod.longest_gap(trace)
