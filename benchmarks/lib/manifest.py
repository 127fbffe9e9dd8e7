"""Reads ``BENCHMARK.json`` and finds what belongs to one cell by name: the
configuration's file, the traffic file ``<paths[0]>/traffic/<cell>.json`` and
the metrics the cell reports."""
from __future__ import annotations

import json
import os


class Manifest:
    def __init__(self, path, repo_root):
        with open(path) as f:
            self.data = json.load(f)
        self.repo_root = repo_root
        self.root = os.path.join(repo_root, self.data["paths"][0])

    def cell(self, name):
        for cell in self.data["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit("benchmark: no workload %r in the manifest" % name)

    def config(self, cell):
        for cfg in self.data["configs"]:
            if cfg["name"] == cell["config"]:
                path = os.path.join(self.repo_root, cfg["file"])
                with open(path) as f:
                    sizes = json.load(f)
                family = sizes.get("family") or os.path.basename(
                    os.path.dirname(path))
                return sizes, family
        raise SystemExit("benchmark: no config %r" % cell["config"])

    def traffic(self, cell):
        path = os.path.join(self.root, "traffic", cell["name"] + ".json")
        with open(path) as f:
            return json.load(f)

    def _reports(self, metric, cell, e2e_names):
        if "workloads" in metric:
            return cell["name"] in metric["workloads"]
        moved = metric.get("moves")
        return moved is None or moved in e2e_names

    def end_to_end(self, cell):
        """The end-to-end metrics this cell reports."""
        return [m for m in self.data["end_to_end"]
                if self._reports(m, cell, ())]

    def per_layer(self, cell):
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if self._reports(m, cell, e2e)]
