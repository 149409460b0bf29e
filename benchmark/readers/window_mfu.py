"""Share of the chips' peak FLOP/s that the whole build reaches: the
operations the window's machines need by the count in ``benchmark/flops.py``
over the window's seconds on the host's clock times the peak of
``benchmark/peaks.json``.  Idle time, ingest and writing all count against
it; it is not a kernel's share of its roofline, which needs the program's
own seconds on the device."""

from benchmark import device


def read(spec, record):
    if record["window_seconds"] <= 0:
        return None
    flops = record["work_per_chunk"]["flops_per_model"] * record["models"]
    peak = device.peaks(record["device_kind"])["flops_per_s"] * record["chips"]
    return 100.0 * flops / (record["window_seconds"] * peak)
