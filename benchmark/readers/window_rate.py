"""Machines written in the window per hour per chip: all the window's
work over all its time, on the host's clock.  Spec: ``per_seconds``
(3600 for a rate per hour)."""


def read(spec, record):
    if record["window_seconds"] <= 0:
        return None
    rate = record["models"] / record["window_seconds"]
    return rate * float(spec.get("per_seconds", 1.0)) / record["chips"]
