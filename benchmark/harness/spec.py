"""Where the benchmark's files are, and how each is found by its name.

BENCHMARK.json (root) lists configurations, cells and metrics. Each cell has a
data file benchmark/workloads/<cell>.json (driver + traffic parameters), each
configuration the file BENCHMARK.json names, each per-layer metric a file
benchmark/layer_metrics/<metric>.json (reader kind + parameters), each traffic
kind a module benchmark/drivers/<driver>.py. Nothing here names any of them.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    pass


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {os.path.relpath(path, ROOT)}") from e


def load_benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _entry(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


class Cell:
    """One entry of BENCHMARK.json's `workloads` with its files read."""

    def __init__(self, bench: dict, name: str, rehearse: bool = False):
        self.bench = bench
        self.name = name
        self.entry = _entry(bench["workloads"], name, "workload")
        self.chips = int(self.entry["chips"])
        cfg_entry = _entry(bench["configs"], self.entry["config"], "config")
        self.config = _load(os.path.join(ROOT, cfg_entry["file"]))
        self.workload = _load(os.path.join(BENCH, "workloads", name + ".json"))
        if self.workload["config"] != self.entry["config"]:
            raise SpecError(f"{name}: workload file and BENCHMARK.json name "
                            f"different configurations")
        self.driver_name = self.workload["driver"]
        # sizes: the configuration's shapes, then the cell's traffic
        # parameters; --rehearse lays each file's "rehearse" block over them
        self.params = dict(self.config["shapes"])
        self.params.update(self.workload["traffic"])
        if rehearse:
            self.params.update(self.config.get("rehearse", {}))
            self.params.update(self.workload.get("rehearse", {}))

    def reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def layer_metrics(self) -> list[tuple[dict, dict]]:
        """(BENCHMARK.json entry, reader file) of each per-layer metric that
        this cell reports."""
        out = []
        for m in self.bench["per_layer"]:
            if self.reports(m):
                spec = _load(os.path.join(BENCH, "layer_metrics",
                                          m["name"] + ".json"))
                out.append((m, spec))
        return out

    def driver(self):
        return importlib.import_module(f"benchmark.drivers.{self.driver_name}")
