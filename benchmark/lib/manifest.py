"""Reading ``BENCHMARK.json`` and finding each cell's files by name.

A cell names a configuration and a traffic mix.  The configuration's file is
the ``file`` of its ``configs`` entry; the traffic mix is
``<path>/traffic/<traffic>.json``, a per-layer metric's reader is
``<path>/layer_metrics/<metric>.py`` and the configuration's block kind is
``<path>/models/<model_type>.py`` (``model_type`` is the published key of
that name in the configuration file), each looked for under each directory
of ``paths`` in order.  Nothing here knows the name of any cell,
configuration, mix, metric or model: adding one is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional


class ManifestError(Exception):
    """The manifest, or a file it names, is missing or malformed."""


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

#: what a block kind's file has to define (README.md, "A block kind")
MODEL_API = ("program_config", "init_params", "init_cache", "prefill",
             "decode_step", "logits", "loss", "num_params",
             "train_flops_per_token", "decode_step_bytes",
             "decode_step_flops")


def load_file(kind: str, path: str):
    """The module in the file at ``path``, loaded by file and not by import
    path: files of several ``paths`` directories may share a name."""
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(path: str):
    """A block kind's module, checked for the functions the harness calls
    (the replica's worker loads it from the path the parent resolved)."""
    mod = load_file("model", path)
    lacks = [f for f in MODEL_API if not callable(getattr(mod, f, None))]
    if lacks:
        raise ManifestError(f"{path} defines no {', '.join(lacks)}")
    return mod


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
        kind = self.config.get("model_type")
        if not (isinstance(kind, str) and _NAME.match(kind)):
            tried = [os.path.join(self.root, p, "models", "<model_type>.py")
                     for p in self.manifest["paths"]]
            raise ManifestError(
                f"{self.config_path}: model_type {kind!r} names no block "
                f"kind's file (looked for {tried})")
        self.model_path = self._find("models", kind + ".py")
        self.model = load_model(self.model_path)

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
        mod = load_file("layer_metric", path)
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
