"""Wall-clock trace spans with a context-propagated trace id.

This is the request-scoped half of the telemetry plane: where
``telemetry.metrics`` answers "how often / how slow on aggregate",
spans answer "where did THIS request's time go" — client → HTTP header →
server handler → coalescer dispatch → scorer, all stitched by one trace
id riding the ``X-Gordo-Trace-Id`` header.

One span, three sinks, one clock.  :func:`span` always feeds the
``gordo_span_seconds`` histogram; with ``GORDO_SPAN_LOG`` set it appends
one JSONL line; and for its duration it holds a
``jax.profiler.TraceAnnotation`` of the same name, so whenever a profiler
session is open (the benchmark's, or ``utils/profiling.trace`` under
``GORDO_PROFILE_DIR``) the span lies on the device trace's own clock
beside the device's operations.  With no session open the annotation
costs an atomic load; where jax was never imported it is skipped.

Span log: set ``GORDO_SPAN_LOG=/path/spans.jsonl`` and every finished
span appends one JSON line ``{ts, trace, span, id, parent, start, end,
seconds, ...attrs}``: ``parent`` is the ``id`` of the span that enclosed
it in the same context (a worker thread started through
``contextvars.copy_context().run`` inherits both it and the trace id).
Off by default — the histograms alone carry the aggregate signal.
The file is size-capped: at ``GORDO_SPAN_LOG_MAX_BYTES`` (default
64 MiB) it rotates to ``spans.jsonl.1``, keeping the last 2 files — a
long-lived server under heavy traffic previously grew it unboundedly.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterator, Optional

from gordo_tpu.telemetry import metrics
from gordo_tpu.telemetry.rotate import append_jsonl_line

logger = logging.getLogger(__name__)

#: the propagation header: clients send it, servers echo it back and tag
#: their spans with it; absent on ingress the server mints one so every
#: request is traceable end-to-end regardless of the caller
TRACE_HEADER = "X-Gordo-Trace-Id"

#: deadline propagation: the REMAINING request budget in integer
#: milliseconds, restamped by the client at each send.  The server
#: middleware converts it back to an absolute monotonic deadline and the
#: coalescer drops riders whose budget expired before dispatch — work
#: that is already dead upstream never reaches the device.
DEADLINE_HEADER = "X-Gordo-Deadline-Ms"

ENV_SPAN_LOG = "GORDO_SPAN_LOG"
ENV_SPAN_LOG_MAX_BYTES = "GORDO_SPAN_LOG_MAX_BYTES"

#: span-log rotation threshold (bytes); the crossing line starts the
#: next generation and the previous one survives as ``<path>.1``
DEFAULT_SPAN_LOG_MAX_BYTES = 64 * 1024 * 1024

_trace_id: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "gordo_trace_id", default=None
)

#: the innermost open span's attrs dict (``id`` inside) of this context
_open_span: "contextvars.ContextVar[Optional[Dict[str, Any]]]" = (
    contextvars.ContextVar("gordo_open_span", default=None)
)

_SPAN_SECONDS = metrics.histogram(
    "gordo_span_seconds",
    "Wall-clock duration of named trace spans",
    labels=("span",),
)

_log_lock = threading.Lock()


def new_trace_id() -> str:
    """16-hex-char trace id (random; uniqueness, not secrecy)."""
    return uuid.uuid4().hex[:16]


def current_trace_id() -> Optional[str]:
    """The trace id bound to this execution context, or None."""
    return _trace_id.get()


def set_trace_id(trace_id: Optional[str]) -> "contextvars.Token":
    """Bind a trace id to the current context (handlers call this on
    ingress); returns the token for symmetric reset."""
    return _trace_id.set(trace_id)


def ensure_trace_id() -> str:
    """Current trace id, minting and binding one if absent."""
    tid = _trace_id.get()
    if tid is None:
        tid = new_trace_id()
        _trace_id.set(tid)
    return tid


def span_log_path() -> Optional[str]:
    return os.environ.get(ENV_SPAN_LOG) or None


def span_log_max_bytes() -> int:
    try:
        return int(
            os.environ.get(ENV_SPAN_LOG_MAX_BYTES, "")
            or DEFAULT_SPAN_LOG_MAX_BYTES
        )
    except ValueError:
        return DEFAULT_SPAN_LOG_MAX_BYTES


def _write_span_line(doc: Dict[str, Any]) -> None:
    path = span_log_path()
    if not path:
        return
    try:
        line = json.dumps(doc)
        with _log_lock:
            # size-capped keep-last-2 rotation: a busy server's span log
            # is bounded at ~2x the cap instead of growing forever
            append_jsonl_line(path, line, max_bytes=span_log_max_bytes())
    except Exception:  # the span log must never break the traced path
        logger.exception("span log append failed")


def add_to_span(**counts: float) -> None:
    """Add ``counts`` onto the enclosing span's numeric attributes (no
    enclosing span: nothing).  Lets the layer where work happens count it
    (``mesh.place``: bytes and leaves transferred) on whichever span the
    caller opened around it."""
    attrs = _open_span.get()
    if attrs is not None:
        for key, amount in counts.items():
            attrs[key] = attrs.get(key, 0) + amount


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for an open span.  jax is never
    imported for this: a process that has not loaded it has no profiler
    session to write to."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def span(name: str, trace_id: Optional[str] = None,
         **attrs: Any) -> Iterator[Dict[str, Any]]:
    """Time a section: feeds ``gordo_span_seconds{span=name}``, holds a
    profiler ``TraceAnnotation`` of the same name and (when
    ``GORDO_SPAN_LOG`` is set) appends one JSONL line.  ``name`` is a
    histogram label — keep it a BOUNDED set (route names, stage names);
    per-request values belong in ``attrs``, which only reach the span
    log.  Yields the attrs dict so callers can attach results
    (e.g. batch sizes known only at exit); it carries ``id``, ``parent``
    and ``start`` while the span is open and ``end`` and ``seconds`` once
    it has closed, so the caller that needs the interval reads it there
    instead of timing the section again.  With telemetry disabled nothing
    is timed and the dict holds the caller's attrs only."""
    if not metrics.enabled():
        yield attrs
        return
    tid = trace_id if trace_id is not None else current_trace_id()
    enclosing = _open_span.get()
    attrs["id"] = uuid.uuid4().hex[:16]
    attrs["parent"] = enclosing["id"] if enclosing is not None else None
    _open_span.set(attrs)
    t0 = time.perf_counter()
    attrs["start"] = time.time()
    try:
        with _annotation(name):
            yield attrs
    finally:
        seconds = time.perf_counter() - t0
        # set, not reset(token): a span may close in another context than
        # it opened in (a handler's span across an await), where a token
        # would refuse
        _open_span.set(enclosing)
        attrs["seconds"] = seconds
        attrs["end"] = attrs["start"] + seconds
        _finish(name, tid, attrs)


def record_span(name: str, start: float, end: float,
                seconds: Optional[float] = None, **attrs: Any) -> None:
    """A span whose interval was worked out after the fact (the device's
    idle gap between two programs): histogram and span log like any other,
    under the context's trace id and open span, but no profiler
    annotation — that cannot be written backwards.  ``seconds`` where the
    interval holds pauses (a stage that was entered many times between
    ``start`` and ``end``: its busy seconds); ``end - start`` otherwise."""
    if not metrics.enabled():
        return
    enclosing = _open_span.get()
    attrs.update(
        id=uuid.uuid4().hex[:16],
        parent=enclosing["id"] if enclosing is not None else None,
        start=start, end=end,
        seconds=end - start if seconds is None else seconds,
    )
    _finish(name, current_trace_id(), attrs)


def _finish(name: str, tid: Optional[str], attrs: Dict[str, Any]) -> None:
    _SPAN_SECONDS.observe(attrs["seconds"], name)
    if span_log_path():
        doc: Dict[str, Any] = {"ts": round(attrs["end"], 6), "span": name}
        if tid:
            doc["trace"] = tid
        doc.update(attrs)
        for key in ("start", "end", "seconds"):
            doc[key] = round(doc[key], 6)
        _write_span_line(doc)
