"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here **by name**:

- configuration ``c``  -> the manifest entry's ``file`` (a JSON object);
- traffic mix ``t``    -> ``<path>/traffic/<t>.json`` in any of ``paths``;
- per-layer metric ``m`` -> ``<path>/layer_metrics/<m>.py`` with a
  ``read(ctx)`` function.

A later PR adds a cell by adding such files and manifest entries; no
file that is here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os


class ManifestError(ValueError):
    pass


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        with open(path) as f:
            self.data = json.load(f)
        self.paths = list(self.data["paths"])

    # -- lookups ---------------------------------------------------------

    def _by_name(self, section: str, name: str) -> dict:
        for entry in self.data[section]:
            if entry["name"] == name:
                return entry
        raise ManifestError(
            f"{section} has no entry named {name!r} (has: "
            f"{[e['name'] for e in self.data[section]]})")

    def workload(self, name: str) -> dict:
        return self._by_name("workloads", name)

    def _find(self, sub: str, filename: str) -> str:
        for p in self.paths:
            cand = os.path.join(self.root, p, sub, filename)
            if os.path.isfile(cand):
                return cand
        raise ManifestError(
            f"no {sub}/{filename} under any of paths={self.paths}")

    def config(self, name: str) -> dict:
        entry = self._by_name("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            cfg = json.load(f)
        cfg["_name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        with open(self._find("traffic", name + ".json")) as f:
            traffic = json.load(f)
        traffic["_name"] = name
        return traffic

    def end_to_end_for(self, workload: str) -> list:
        """Metrics the cell reports: those that list it, and those that
        list no cells at all."""
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer_for(self, workload: str) -> list:
        """Per-layer metrics read in the cell: those that list it; one
        that lists no cells is read wherever the metric it moves is
        reported."""
        e2e = {m["name"] for m in self.end_to_end_for(workload)}
        return [m for m in self.data["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def layer_reader(self, metric_name: str):
        """The ``read(ctx)`` function of a per-layer metric's own file."""
        path = self._find("layer_metrics", metric_name + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_layer_metric_" + metric_name.replace(".", "_")
            .replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not callable(getattr(mod, "read", None)):
            raise ManifestError(f"{path} defines no read(ctx)")
        return mod.read
