"""The readers ``sg2.saturated``'s per-layer metrics brought: a module's
share of the traced window, and a counter ratio that reports nothing for a
program from before the counter existed. And the cell's metric files: each
``.sg2`` metric is reported by ``sg2.saturated`` alone."""

import json
import os
import types

import pytest

from harness.cell import BENCH_DIR, ROOT, Cell, load_module
from harness.stats import StatsWindow


def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name))


def ctx(trace=None, start=None, end=None, roles=None, events=1000):
    return types.SimpleNamespace(
        trace=trace, events=events, window_s=2.0,
        stats=StatsWindow(start or {}, end or {}, roles or {}))


def test_module_time_share_by_hand():
    read = reader("module_time_share.py").read
    t = {"window_s": 3.0, "modules": [["jit_step", 1.5], ["jit_fire", 0.6],
                                      ["jit_step_2", 0.3], ["jit_map_avg", 0.1]]}
    assert read(ctx(t), {"modules": "^jit_step"}) == pytest.approx(60.0)
    assert read(ctx(t), {"modules": "^jit_fire"}) == pytest.approx(20.0)
    # nothing ran under that name, or no trace: nothing, never 0
    assert read(ctx(t), {"modules": "^jit_rebuild"}) is None
    assert read(ctx(None), {"modules": "^jit_step"}) is None


def test_counter_ratio_present_is_the_ratio_or_nothing():
    read = reader("counter_ratio_present.py").read
    roles = {"window": "win"}
    start = {"win": {"Windows_fired": 100, "Dispatch_batches": 10}}
    end = {"win": {"Windows_fired": 600, "Dispatch_batches": 20},
           "snk": {"Inputs_received": 7}}
    params = {"num": [["window", "Windows_fired"]],
              "den": [["window", "Dispatch_batches"]]}
    assert read(ctx(None, start, end, roles), params) == pytest.approx(50.0)
    assert read(ctx(None, start, end, roles),
                {"num": [["window", "Windows_fired"]],
                 "den": "events"}) == pytest.approx(0.5)
    # a program from before the counter existed has no such field
    old = {"win": {"Dispatch_batches": 20}}
    assert read(ctx(None, {"win": {"Dispatch_batches": 10}}, old, roles),
                params) is None


def test_sg2_metrics_are_reported_by_the_sg2_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sg2 = [m for m in bench["per_layer"] if m["name"].endswith(".sg2")]
    assert len(sg2) == 9
    for m in sg2:
        assert m["workloads"] == ["sg2.saturated"]
        assert m["moves"] == "events_per_s"
    mine = {m["name"] for m, _ in Cell("sg2.saturated").metrics("per_layer")}
    other = {m["name"] for m, _ in Cell("ysb.saturated").metrics("per_layer")}
    assert {m["name"] for m in sg2} <= mine
    assert not {m["name"] for m in sg2} & other
    # the accepted metrics without a list are the new cell's at once
    assert {"device_idle_share.sat", "compiles_in_window.sat",
            "launch_us_per_program.sat"} <= mine & other
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200, w["name"]
