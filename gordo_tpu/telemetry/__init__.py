"""Fleet-wide observability: metrics, Prometheus exposition, trace spans.

The telemetry plane every layer reports through:

- :mod:`gordo_tpu.telemetry.metrics` — process-wide registry of counters,
  gauges and fixed-bucket histograms; Prometheus text exposition
  (``serve/server.py`` mounts it at ``GET /metrics``); JSON snapshots the
  multi-host builder writes per shard and watchman/CLI merge.
- :mod:`gordo_tpu.telemetry.spans` — wall-clock trace spans with a
  context-propagated trace id (``X-Gordo-Trace-Id`` header) and parent
  span; each also holds a ``jax.profiler.TraceAnnotation``, so it shows
  in any profiler session that is open.
- :mod:`gordo_tpu.telemetry.fleet_health` — per-machine anomaly-score
  distribution sketches (mergeable log-bucket histograms), build-time
  baselines, and the baseline-vs-live drift signal behind the
  ``gordo_machine_*`` gauges, ``/fleet-health`` docs, and rollup files.

Kill switch: ``GORDO_TELEMETRY=off`` (or :func:`set_enabled`) turns every
record call into a cheap no-op; ``bench.py --stage telemetry_overhead``
attests the instrumented hot path costs <= 2% vs the switch.
"""

from gordo_tpu.telemetry.metrics import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
    add_instance_label,
    counter,
    enabled,
    gauge,
    histogram,
    load_snapshot_dir,
    log_event,
    merge_expositions,
    merge_snapshots,
    render,
    render_snapshot,
    set_enabled,
)
from gordo_tpu.telemetry.fleet_health import (  # noqa: F401
    FLEET_HEALTH,
    FleetHealth,
    ScoreSketch,
    baselines_from_archive,
    drift_score,
    load_rollups,
    merge_health_docs,
    normalize_health_doc,
    read_rollups,
    sketch_from_scores,
    write_rollup,
)
from gordo_tpu.telemetry.spans import (  # noqa: F401
    DEADLINE_HEADER,
    TRACE_HEADER,
    add_to_span,
    current_trace_id,
    ensure_trace_id,
    new_trace_id,
    record_span,
    set_trace_id,
    span,
)

#: directory (under a build's output dir) where shard-local metric
#: snapshots land — one file per process of a (multi-host) project build
SNAPSHOT_DIR = ".gordo-telemetry"

__all__ = [
    "FLEET_HEALTH",
    "FleetHealth",
    "REGISTRY",
    "MetricsRegistry",
    "SNAPSHOT_DIR",
    "DEADLINE_HEADER",
    "ScoreSketch",
    "TRACE_HEADER",
    "add_instance_label",
    "add_to_span",
    "counter",
    "drift_score",
    "current_trace_id",
    "enabled",
    "ensure_trace_id",
    "gauge",
    "histogram",
    "load_rollups",
    "load_snapshot_dir",
    "log_event",
    "merge_expositions",
    "merge_health_docs",
    "merge_snapshots",
    "new_trace_id",
    "normalize_health_doc",
    "baselines_from_archive",
    "read_rollups",
    "record_span",
    "render",
    "render_snapshot",
    "set_enabled",
    "set_trace_id",
    "sketch_from_scores",
    "span",
    "write_rollup",
]
