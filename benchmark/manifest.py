"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name the manifest
gives: ``configs/<config>.json`` (or the entry's ``file``),
``traffic/<traffic>.json`` and ``metrics/<metric>.json``.  A later PR adds
files and entries; it edits none.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.path = os.path.join(root, "BENCHMARK.json")
        with open(self.path) as fh:
            self.doc: Dict[str, Any] = json.load(fh)

    # -- lookups ------------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        known = ", ".join(c["name"] for c in self.doc["workloads"])
        raise KeyError(f"no workload {name!r} in {self.path}; there are: {known}")

    def _json(self, relative: str) -> Dict[str, Any]:
        with open(os.path.join(self.root, relative)) as fh:
            return json.load(fh)

    def config(self, name: str) -> Dict[str, Any]:
        for entry in self.doc["configs"]:
            if entry["name"] == name:
                return self._json(entry["file"])
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json(self._beside("traffic", name))

    def metric_spec(self, name: str) -> Dict[str, Any]:
        return self._json(self._beside("metrics", name))

    def _beside(self, kind: str, name: str) -> str:
        """``<first path>/<kind>/<name>.json`` — searched in every
        directory of ``paths`` so that a later PR's own directory works."""
        for base in self.doc["paths"]:
            candidate = os.path.join(base, kind, f"{name}.json")
            if os.path.exists(os.path.join(self.root, candidate)):
                return candidate
        raise FileNotFoundError(
            f"no {kind}/{name}.json under any of {self.doc['paths']}"
        )

    # -- which metrics a cell reports ---------------------------------------
    def metrics_of(self, cell_name: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics reported in a cell: a
        metric with a ``workloads`` key where it lists the cell; one without
        wherever the cell reports what it moves (``end_to_end``: everywhere)."""
        e2e = {m["name"]: m for m in self.doc["end_to_end"]}
        out = []
        for metric in self.doc[group]:
            cells = metric.get("workloads")
            if cells is None and group == "per_layer":
                cells = e2e[metric["moves"]].get("workloads")
            if cells is None or cell_name in cells:
                out.append(metric)
        return out
