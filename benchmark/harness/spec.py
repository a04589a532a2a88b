"""Finds the benchmark's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric sits in a file of its own under ``benchmark/``;
a later PR adds files and entries and edits none that are there:

- ``configs/<config>.json``: the sizes as run, the serving options, and
  dotted paths to the family's config class, plain reference and byte
  model;
- ``traffic/<traffic>.json``: the parameters the one generator reads;
- ``cells/<cell>.json``: the offered rate of that cell and the sweep it
  came from;
- ``layer_metrics/<metric>.json``: the reader (``module:function``) that
  takes the metric from spans, counters or the trace.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def resolve(dotted: str) -> Any:
    """``package.module:attr`` -> the object."""
    module, _, attr = dotted.partition(":")
    if not attr:
        raise ValueError(f"{dotted!r}: want 'package.module:attr'")
    obj = importlib.import_module(module)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


class Spec:
    """``BENCHMARK.json`` plus the data files its names point to."""

    def __init__(self, benchmark_json: Optional[str] = None):
        self.path = os.path.abspath(
            benchmark_json or os.path.join(REPO, "BENCHMARK.json"))
        self.doc = _load(self.path)
        self.base = os.path.dirname(self.path)
        self.root = os.path.join(self.base, self.doc["paths"][0])

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load(os.path.join(self.base, c["file"]))
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        doc = _load(os.path.join(self.root, "traffic", f"{name}.json"))
        doc.setdefault("name", name)
        return doc

    def cell(self, name: str) -> dict:
        return _load(os.path.join(self.root, "cells", f"{name}.json"))

    def peaks(self, device_kind: str) -> dict:
        table = _load(os.path.join(self.root, "peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"peaks.json ({sorted(table)}): no default")
        return table[device_kind]

    def metrics(self, section: str, workload: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.doc[section]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable[..., Optional[float]]:
        doc = _load(os.path.join(self.root, "layer_metrics",
                                 f"{metric}.json"))
        fn = resolve(doc["reader"])
        params: Dict[str, Any] = doc.get("params", {})
        return lambda ctx: fn(ctx, **params)
