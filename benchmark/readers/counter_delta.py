"""What a counter had gathered by the end of set-up, from process start.
Spec: ``series``, ``label_values`` (tuples whose values add)."""

from benchmark.readers import series_state


def read(spec, record):
    found, total = False, 0.0
    for labels in spec["label_values"]:
        value = series_state(record["snap_start"], spec["series"], labels)
        if value is None:
            continue
        found = True
        total += float(value)
    return total if found else None
