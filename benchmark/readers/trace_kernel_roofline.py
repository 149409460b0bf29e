"""A kind of kernel's share of its roofline by the events' OWN counts: over
the events of whole programs whose HLO name starts with one of ``kernels``
(``trace_named_seconds.table``), the larger of the sum of their ``flops``
over the peak FLOP/s and the sum of their ``bytes_accessed`` over the peak
bytes/s (``benchmark/peaks.json``), over the sum of their device seconds.

The counts are the compiler's for the instruction as it stands, a field of
the event's metadata: for ``ragged-dot`` every row of the left operand
times a group's matrix, whether a group holds the row or not.  The kernel
skips the rows of no group, and they are most of what it is handed: the
worst-case buffer of ``kimi_linear``'s preset is all ``N k`` selected pairs
for the quarter that fall on held experts, and a row block's tail is empty.
So with ``rows`` the counts are scaled by the share of the counted rows that
hold a pair, from the program's counters inside the window: pairs held over
pairs selected (``held`` / ``selected``), over blocks run over blocks full
where the program walks row blocks and so has that series (``blocks``,
labels ``run`` / ``full``: the pairs it hands the kernel are ``selected x
run / full``).  The counters count the final fits' optimiser steps, the
events every pass of whole programs: the share is the window's, not the
event's.  Without ``rows`` the share errs high by the rows skipped.  Never
clamped.

Spec: ``kernels``, ``rows`` (optional).  ``None`` wherever
``trace_named_seconds`` reads nothing, the events carry no count, or
``rows`` names counters the window did not move."""

from benchmark import device
from benchmark.readers import series_state, trace_named_seconds


def _moved(record, series, labels=()):
    end = series_state(record["snap_end"], series, labels)
    if end is None:
        return None
    return float(end) - float(series_state(record["snap_start"], series, labels) or 0.0)


def rows_held_share(rows, record):
    """Of the rows the kernel's events count, the share that holds a pair."""
    held, selected = (_moved(record, rows[key]) for key in ("held", "selected"))
    if not held or not selected:
        return None
    run, full = (_moved(record, rows["blocks"], [state]) for state in ("run", "full"))
    handed = selected * run / full if run and full else selected
    return held / handed


def read(spec, record):
    found = trace_named_seconds.table_of(record)
    if found is None:
        return None
    seconds, flops, moved = trace_named_seconds.totals(found, kernels=spec["kernels"])
    if seconds <= 0 or (flops <= 0 and moved <= 0):
        return None
    share = rows_held_share(spec["rows"], record) if "rows" in spec else 1.0
    if share is None:
        return None
    peaks = device.peaks(record["device_kind"])
    least = share * max(flops / peaks["flops_per_s"], moved / peaks["bytes_per_s"])
    return 100.0 * least / seconds
