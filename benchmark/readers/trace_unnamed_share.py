"""The share of whole programs' operation seconds that nothing names: of the
operations ``trace_named_seconds.table`` holds (control flow's own events
left out), those whose ``tf_op`` holds no name under one of ``prefixes``
other than the names in ``passes`` (a name that marks a pass and wraps
operations which have, or lack, names of their own: ``fit.forecast``) and
whose HLO name starts with none of ``kernels``.  What is left on a program
that names all it can is the compiler's own: the loops' copies and slices,
zero fills and conversions that carry no ``tf_op`` at all.

Spec: ``prefixes``, ``passes``, ``kernels``.  ``None`` wherever
``trace_named_seconds`` reads nothing."""

from benchmark.readers import trace_named_seconds


def read(spec, record):
    found = trace_named_seconds.table_of(record)
    if found is None:
        return None
    prefixes, passes = tuple(spec["prefixes"]), set(spec.get("passes", ()))
    kernels = tuple(spec.get("kernels", ()))
    total = unnamed = 0.0
    for (names, head), (seconds, _, _, _) in found["rows"].items():
        total += seconds
        named = any(name.startswith(prefixes) and name not in passes for name in names)
        if not named and not head.startswith(kernels):
            unnamed += seconds
    return 100.0 * unnamed / total if total > 0 else None
