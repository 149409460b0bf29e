"""Opt-in ``jax.profiler`` dumps around named sections.

Reference status (SURVEY.md §6.1): essentially absent — the reference only
records build wall-times into metadata.  The TPU build keeps that
metadata-first design: ``trace`` is a thin caller of ``telemetry.span``
(the span ``profile.<head>`` feeds ``gordo_span_seconds`` and the span
log, and shows in any open profiler session), and with
``GORDO_PROFILE_DIR`` set (or ``directory`` passed) it also opens a
profiler session of its own around the section and dumps a
Perfetto/TensorBoard-loadable trace.  jax allows one session per process:
do not combine ``GORDO_PROFILE_DIR`` with an outer session (the
benchmark's ``--trace 1``, or another ``trace`` section around this one).
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator, Optional

from gordo_tpu import telemetry

logger = logging.getLogger(__name__)

ENV_VAR = "GORDO_PROFILE_DIR"


def profile_dir() -> Optional[str]:
    return os.environ.get(ENV_VAR) or None


@contextlib.contextmanager
def trace(section: str, directory: Optional[str] = None) -> Iterator[None]:
    """Wrap a section in the span ``profile.<head>`` (``head``: the
    section name before any '/', so ``fit/m-1`` and ``fit/m-2`` share one
    bounded histogram series; the full name reaches the span log).  When
    profiling is enabled (``GORDO_PROFILE_DIR``), a ``jax.profiler`` trace
    also dumps to ``<dir>/<section>/`` (one subdir per section so repeated
    builds don't clobber each other)."""
    directory = directory or profile_dir()
    head = section.split("/", 1)[0]
    with contextlib.ExitStack() as stack:
        if directory:
            import jax

            dest = os.path.join(directory, section.replace("/", "_"))
            os.makedirs(dest, exist_ok=True)
            logger.info("Profiling %r -> %s", section, dest)
            # the session opens first, so the span's annotation lands in it
            stack.enter_context(jax.profiler.trace(dest))
        stack.enter_context(telemetry.span("profile." + head, section=section))
        yield
