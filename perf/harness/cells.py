"""Finding a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

  perf/configs/<config>.json    sizes as run, ``family`` names the builder
  perf/families/<family>.py     system builder + plain float32 reference
  perf/traffic/<traffic>.json   parameters, ``driver`` names the loop
  perf/drivers/<driver>.py      one general load loop per kind of traffic
  perf/metrics/<metric>.py      the reader of one per-layer metric

A later PR adds files and entries and edits none: nothing here (or in
run.py) branches on the name of a workload, a configuration or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)


class CellError(Exception):
    """The benchmark's own files do not describe the cell asked for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, perf_dir: str = PERF_DIR):
    """Import ``perf/<kind>/<name>.py`` by path (metric names hold dots,
    so they cannot be imported by name)."""
    path = os.path.join(perf_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _with_rehearsal(d: dict, rehearse: bool) -> dict:
    """The tiny CPU preset rides in the same file under ``rehearsal``."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    if rehearse:
        out.update(d.get("rehearsal", {}))
    return out


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, *, root: str = ROOT, rehearse: bool = False):
        self.root = root
        self.perf_dir = os.path.join(root, "perf")
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise CellError(f"unknown workload {name!r}; BENCHMARK.json has "
                            f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config = _with_rehearsal(
            load_json(os.path.join(root, entry["file"])), rehearse)
        self.traffic = _with_rehearsal(
            load_json(os.path.join(self.perf_dir, "traffic",
                                   self.workload["traffic"] + ".json")),
            rehearse)
        self.family = load_module("families", self.config["family"],
                                  self.perf_dir)
        self.driver = load_module("drivers", self.traffic["driver"],
                                  self.perf_dir)

    def metrics(self, section: str) -> list[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell
        reports: those with no ``workloads`` key, or that list it."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        return load_module("metrics", metric, self.perf_dir).read
