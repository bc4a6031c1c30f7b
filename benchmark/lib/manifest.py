"""Reading ``BENCHMARK.json`` and finding each cell's files by name.

A cell names a configuration and a traffic mix.  The configuration's file is
the ``file`` of its ``configs`` entry; the traffic mix is
``<path>/traffic/<traffic>.json`` and a per-layer metric's reader is
``<path>/layer_metrics/<metric>.py``, looked for under each directory of
``paths`` in order.  Nothing here knows the name of any cell, configuration,
mix or metric: adding one is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional


class ManifestError(Exception):
    """The manifest, or a file it names, is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, manifest_path: str, name: str):
        self.manifest_path = os.path.abspath(manifest_path)
        self.root = os.path.dirname(self.manifest_path)
        self.manifest = _load_json(self.manifest_path)
        cells = {w["name"]: w for w in self.manifest.get("workloads", [])}
        if name not in cells:
            raise ManifestError(f"no workload {name!r} in {manifest_path} "
                                f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest.get("configs", [])}
        if self.entry["config"] not in configs:
            raise ManifestError(f"workload {name!r} names configuration "
                                f"{self.entry['config']!r}, which "
                                f"{manifest_path} does not list")
        self.config_entry = configs[self.entry["config"]]
        self.config_path = os.path.join(self.root, self.config_entry["file"])
        self.config = _load_json(self.config_path)
        self.traffic_path = self._find("traffic", self.entry["traffic"]
                                       + ".json")
        self.traffic = _load_json(self.traffic_path)

    def _find(self, sub: str, filename: str) -> str:
        tried = []
        for p in self.manifest["paths"]:
            path = os.path.join(self.root, p, sub, filename)
            if os.path.isfile(path):
                return path
            tried.append(path)
        raise ManifestError(f"found none of {tried}")

    def metrics(self, group: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
        metric with no ``workloads`` key belongs to every cell."""
        return [m for m in self.manifest.get(group, [])
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        """The ``read(ctx)`` function of a per-layer metric's own file."""
        path = self._find("layer_metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_metric_" + "".join(
                c if c.isalnum() else "_" for c in metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not callable(getattr(mod, "read", None)):
            raise ManifestError(f"{path} defines no read(ctx)")
        return mod.read


def metric_line(values: Dict[str, Optional[float]],
                metrics: List[dict]) -> Dict[str, dict]:
    """``{"name": {"value": v, "unit": u}}`` for the metrics that have a
    value; one a reader returned nothing for is left out of the line."""
    out = {}
    for m in metrics:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
