"""What ``BENCHMARK.json`` names, found by name: cells, configuration
files, traffic files and per-layer metric readers.

A new configuration, traffic mix or per-layer metric is new files plus a
new entry in ``BENCHMARK.json``: a config file ``configs/<config>.json``
(named by the entry's ``file``), a traffic file ``traffic/<traffic>.json``,
the limits of a cell's comparison ``limits/<cell>.json`` and a reader
``metrics/<metric>.py`` with ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Bench:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.here = self.root / "chipbench"

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.spec["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        """The limit of each number compared in the cell (``limits/<cell>.json``)."""
        return json.loads((self.here / "limits" / f"{cell}.json").read_text())["limits"]

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose moved metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)]

    def reader(self, metric: str):
        path = self.here / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench.metrics.{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
