"""Cold-start measurement child: one fresh process, one first request.

The quantity under test — what a request pays when it is the FIRST to hit
an uncompiled serving program — only exists in a process whose jit and
compile-plane caches are empty, so ``bench.py --stage cold_start`` (and
the slow-lane smoke test) fork this module instead of measuring in-process:

    python -m gordo_tpu.compile.coldstart --artifacts DIR --mode cold|warm

``cold``: load the artifact collection and immediately score — the first
request eats the compile (today's no-warmup behavior).  ``warm``: run the
compile-plane warmup (manifest-driven AOT pre-compiles) first, then score
— the first request should cost dispatch only.  Either way the child
prints ONE JSON line with ``time_to_ready_s`` (process start → able to
serve), ``first_request_s``, ``second_request_s``, and the
``gordo_compile_*`` counter lines from the telemetry exposition (the same
text ``/metrics`` serves), so the parent can attest compile-cache hits.

Persistent-cache runs are driven by the parent via the normal env
contract (``GORDO_COMPILE_CACHE=force`` + ``JAX_COMPILATION_CACHE_DIR``):
back-to-back children on one machine populate then reuse the on-disk
cache, measuring cached-restart time-to-ready against the cold one.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # as close to process start as a module can get

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def _compile_metric_lines(scrape: str) -> list:
    return [
        line
        for line in scrape.splitlines()
        if not line.startswith("#")
        and line.startswith((
            "gordo_compile_cache_", "gordo_compile_seconds_count",
            "gordo_compiled_programs",
        ))
    ]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--artifacts", required=True,
                        help="Project artifact dir (build_project output)")
    parser.add_argument("--mode", choices=("cold", "warm"), required=True)
    parser.add_argument("--rows", type=int, default=256,
                        help="Request row count for the measured requests")
    args = parser.parse_args(argv)

    import numpy as np

    from gordo_tpu import telemetry
    from gordo_tpu.serve.server import ModelCollection
    from gordo_tpu.utils.compile_cache import enable_persistent_compile_cache

    persistent = enable_persistent_compile_cache()
    collection = ModelCollection.from_directory(args.artifacts)

    warm_stats = None
    if args.mode == "warm":
        from gordo_tpu.compile import warmup_collection

        warm_stats = warmup_collection(collection)
        if warm_stats["errors"]:
            print(json.dumps({"error": "warmup failed", **warm_stats}))
            return 1
    time_to_ready = time.monotonic() - _T0

    # the measured request: the per-machine anomaly route's scoring path
    name = sorted(collection.entries)[0]
    entry = collection.get(name)
    n_feat = len(entry.tags) or 1
    rng = np.random.default_rng(0)
    X = rng.standard_normal((args.rows, n_feat)).astype(np.float32)

    t0 = time.perf_counter()
    entry.scorer.anomaly_arrays(X)
    first_request = time.perf_counter() - t0
    t0 = time.perf_counter()
    entry.scorer.anomaly_arrays(X)
    second_request = time.perf_counter() - t0

    doc = {
        "mode": args.mode,
        "persistent_cache": bool(persistent),
        "time_to_ready_s": round(time_to_ready, 4),
        "first_request_s": round(first_request, 4),
        "second_request_s": round(second_request, 4),
        "warmup": warm_stats and {
            "buckets": warm_stats["buckets"],
            "programs": len(warm_stats["programs"]),
            "compile_seconds": warm_stats["compile_seconds"],
        },
        "compile_metrics": _compile_metric_lines(telemetry.render()),
    }
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
