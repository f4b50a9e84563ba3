"""BENCHMARK.json and the files it names, found by name.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own under this directory; a later PR adds
files and manifest entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "benchmarks"
        self.doc = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{name}.json")

    def metrics_of(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those with no ``workloads`` key, and those that list it."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def layer_metric(self, name: str) -> dict:
        return load_json(self.bench_dir / "layer_metrics" / f"{name}.json")

    def reader(self, name: str):
        """The module ``benchmarks/readers/<name>.py``; it has
        ``read(ctx, params) -> float | None``."""
        return importlib.import_module(f"benchmarks.readers.{name}")
