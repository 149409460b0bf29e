"""Seconds a histogram series gathered inside the window (between the
snapshots at set-up's end and at the last counted completion), per machine:
the sum over observations x chunk size.

Spec: ``series``, ``label_values`` (a list of label tuples whose sums add)."""

from benchmark.readers import series_state


def read(spec, record):
    total, count = 0.0, 0
    for labels in spec["label_values"]:
        end = series_state(record["snap_end"], spec["series"], labels)
        if end is None:
            continue
        start = series_state(record["snap_start"], spec["series"], labels) or {
            "sum": 0.0, "count": 0}
        total += end["sum"] - start["sum"]
        count = max(count, end["count"] - start["count"])
    if count <= 0:
        return None
    return total / (count * record["chunk_machines"])
