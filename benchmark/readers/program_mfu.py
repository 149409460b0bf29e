"""Share of the chips' peak FLOP/s that the fleet program reaches while it
runs: the operations one chunk needs by the count in ``benchmark/flops.py``
over the mean seconds a chunk's program held the device inside the window
(the program's own ``program`` stage: from the later of its enqueue's end
and the previous program's end to its output being ready) times the peak of
``benchmark/peaks.json``.  Idle time between programs does not count
against it, which is what sets it apart from ``build.mfu``.

Spec: ``series``, ``labels`` (one label tuple).  ``None`` where the program
does not time its programs."""

from benchmark import device
from benchmark.readers import series_state


def read(spec, record):
    end = series_state(record["snap_end"], spec["series"], spec["labels"])
    if end is None:
        return None
    start = series_state(record["snap_start"], spec["series"], spec["labels"]) or {
        "sum": 0.0, "count": 0}
    seconds, chunks = end["sum"] - start["sum"], end["count"] - start["count"]
    if chunks <= 0 or seconds <= 0:
        return None
    peak = device.peaks(record["device_kind"])["flops_per_s"] * record["chips"]
    return 100.0 * record["work_per_chunk"]["flops"] / (seconds / chunks * peak)
