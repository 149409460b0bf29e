"""The largest over the mean of what a labelled counter's series gathered
inside the window (value at the last counted completion minus value at the
end of set-up), over every label tuple the series has: 1 is perfect balance.
Spec: ``series``.  ``None`` where the program has no such series or nothing
was counted in the window."""


def read(spec, record):
    end = record["snap_end"].get(spec["series"])
    if end is None:
        return None
    start = (record["snap_start"].get(spec["series"]) or {}).get("series", {})
    deltas = [float(value) - float(start.get(labels, 0.0))
              for labels, value in end["series"].items()]
    if not deltas or sum(deltas) <= 0:
        return None
    return max(deltas) / (sum(deltas) / len(deltas))
