"""Resolve a cell by name: ``BENCHMARK.json`` -> ``workloads/<cell>.json``
-> ``configs/<config>.json`` + its module -> the metric files that list
the cell. Adding a cell, a configuration or a metric is adding files and
``BENCHMARK.json`` entries; nothing here names one."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A module from a file whose name may hold dots."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, name: str, rehearse: bool = False,
                 index: str = os.path.join(ROOT, "BENCHMARK.json"),
                 workloads: str = os.path.join(BENCH_DIR, "workloads")):
        """``index`` and ``workloads`` are the benchmark's own; the
        harness's tests give a ``BENCHMARK.json``-shaped file and a
        directory of their own for a configuration that is never
        measured."""
        self.name, self.rehearse = name, rehearse
        self.bench = load_json(index)
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"run.py: no cell {name!r} in BENCHMARK.json")
        self.entry = entry[0]
        self.traffic = load_json(os.path.join(workloads, name + ".json"))
        if self.traffic["config"] != self.entry["config"]:
            raise SystemExit(f"run.py: {name}: workload file and "
                             "BENCHMARK.json name different configurations")
        conf = [c for c in self.bench["configs"]
                if c["name"] == self.entry["config"]][0]
        self.cfg = load_json(os.path.join(ROOT, conf["file"]))
        self.module = load_module(os.path.join(
            os.path.dirname(os.path.join(ROOT, conf["file"])),
            self.cfg["module"]))
        self.chips = int(self.entry["chips"])
        if rehearse:
            # the tiny sizes of the harness's own CPU tests, from the
            # workload's file; a measuring run never takes this branch
            tiny = self.traffic.get("rehearsal", {})
            self.cfg.update(tiny.get("config", {}))
            self.traffic.update(tiny.get("traffic", {}))

    def _reports(self, m: dict) -> bool:
        """A metric with a ``workloads`` list is reported by the cells it
        lists; a per-layer metric without one by every cell that reports
        the end-to-end metric it moves, cells of later PRs too."""
        if "workloads" in m:
            return self.name in m["workloads"]
        return "moves" not in m or any(
            e["name"] == m["moves"] and self._reports(e)
            for e in self.bench["end_to_end"])

    def metrics(self, group: str) -> list:
        """(BENCHMARK.json entry, metric file) of every metric of
        ``group`` (``end_to_end`` or ``per_layer``) this cell reports."""
        return [(m, load_json(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".json")))
                for m in self.bench[group] if self._reports(m)]
