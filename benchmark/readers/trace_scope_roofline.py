"""A named scope's share of its roofline: the least time the chip could take
for one optimiser step's work under the scope (the larger of its operations
over the peak FLOP/s and its bytes over the peak bytes/s, both from
``benchmark/backbone_work.py`` and ``benchmark/peaks.json``) over the device
seconds the scope took per step (``trace_scope_seconds``).  Never clamped: a
share above 100 % says the count is too high or the seconds leave work out.

Spec: ``scope`` (also the key of ``work_per_chunk["per_step"]``).  ``None`` wherever ``trace_scope_seconds`` reads nothing."""

from benchmark import device
from benchmark.readers import trace_scope_seconds


def read(spec, record):
    seconds = trace_scope_seconds.per_step(spec, record)
    work = record["work_per_chunk"].get("per_step", {}).get(spec["scope"])
    if seconds is None or work is None:
        return None
    peaks = device.peaks(record["device_kind"])
    least = max(work["flops"] / (peaks["flops_per_s"] * record["chips"]),
                work["bytes"] / (peaks["bytes_per_s"] * record["chips"]))
    return 100.0 * least / seconds
