"""Reader kinds: one module per kind, found by the ``reader`` a metric's
file names.  A reader takes the metric's spec and the run record and
returns a number, or ``None`` when it found nothing to read — the harness
then leaves the metric out of the line."""

from __future__ import annotations

import importlib
import json
from typing import Any, Dict, Optional


def read(spec: Dict[str, Any], record: Dict[str, Any]) -> Optional[float]:
    module = importlib.import_module(f"{__name__}.{spec['reader']}")
    return module.read(spec, record)


def series_state(snapshot: Dict[str, Any], series: str, labels) -> Optional[Any]:
    """One labelled series of a registry snapshot (``None`` if absent)."""
    metric = snapshot.get(series)
    if metric is None:
        return None
    return metric["series"].get(json.dumps(list(labels)))
