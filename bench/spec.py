"""Find a cell's configuration, traffic mix, limits and metric readers by name.

Everything that belongs to one configuration, one traffic mix, one cell's
correctness limits or one per-layer metric lives in a file of its own:

  BENCHMARK.json                  cells, metrics, bounds
  bench/configs/<config>.json     sizes as run (named by BENCHMARK.json)
  bench/mixes/<traffic>.json      traffic parameters; names its driver
  bench/drivers/<driver>.py       general code that serves one kind of mix
  bench/limits/<cell>.json        limits of the numbers `correct` compares
  bench/metrics/<metric>.py       reader of one per-layer metric

A new cell or metric is added by adding files and entries; nothing here
names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent      # <checkout>/bench
REPO = BENCH.parent                           # <checkout>


class Spec:
    """BENCHMARK.json plus the bench directory it points into."""

    def __init__(self, repo: Path = REPO, bench: Path = BENCH):
        self.repo = Path(repo)
        self.bench = Path(bench)
        with open(self.repo / "BENCHMARK.json") as f:
            self.doc = json.load(f)

    # -- entries -------------------------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(self.repo / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return _load_json(self.bench / "mixes" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return _load_json(self.bench / "limits" / f"{cell}.json")

    def driver(self, name: str) -> ModuleType:
        return load_module(self.bench / "drivers" / f"{name}.py")

    # -- metrics -------------------------------------------------------
    def end_to_end(self, cell: str) -> List[dict]:
        """End-to-end metrics this cell reports (``--trace 0``)."""
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[dict]:
        """Per-layer metrics this cell reports (``--trace 1``): those that
        list it, and those without a list whose moved metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.bench / "metrics" / f"{name}.py")


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: Dict[Path, ModuleType] = {}


def load_module(path: Path) -> ModuleType:
    """Import a bench file by path (metric names hold dots, so they are not
    importable module names)."""
    path = Path(path).resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(path)
        name = f"bench_{len(_MODULES)}_" + "".join(
            c if c.isalnum() else "_" for c in path.stem)
        s = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]
