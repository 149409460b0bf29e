"""What a counter gathered inside the window: its value at the last counted
completion minus its value at the end of set-up.  Spec: ``series``,
``label_values`` (tuples whose deltas add).  ``None`` where the program has
no such series."""

from benchmark.readers import series_state


def read(spec, record):
    found, total = False, 0.0
    for labels in spec["label_values"]:
        end = series_state(record["snap_end"], spec["series"], labels)
        if end is None:
            continue
        found = True
        start = series_state(record["snap_start"], spec["series"], labels)
        total += float(end) - float(start or 0.0)
    return total if found else None
