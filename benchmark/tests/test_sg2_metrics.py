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
    """Follows ``BENCHMARK.json``: however many ``.sg2`` metrics it lists,
    each has its file, lists ``sg2.saturated`` alone and moves
    ``events_per_s``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sg2 = [m for m in bench["per_layer"] if m["name"].endswith(".sg2")]
    assert sg2
    for m in sg2:
        assert m["workloads"] == ["sg2.saturated"]
        assert m["moves"] == "events_per_s"
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           spec["reader"]))
    # and no metric file is left without its entry
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g]}
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))
             if f.endswith(".json")}
    assert files == listed
    mine = {m["name"] for m, _ in Cell("sg2.saturated").metrics("per_layer")}
    other = {m["name"] for m, _ in Cell("ysb.saturated").metrics("per_layer")}
    assert {m["name"] for m in sg2} <= mine
    assert not {m["name"] for m in sg2} & other
    # the accepted metrics without a list are the new cell's at once
    assert {"device_idle_share.sat", "compiles_in_window.sat",
            "launch_us_per_program.sat"} <= mine & other
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200, w["name"]


def test_window_step_roofline_counts_the_fields_its_file_names():
    """``fields`` of the metric's params reaches ``window_step_bytes``:
    with sg2's sizes the share of two words a node is the one-word share
    times the ratio of the bytes, and ``.sg2``'s file says 2."""
    from harness import roofline
    read = reader("window_step_roofline.py").read
    cfg = Cell("sg2.saturated").cfg
    c = ctx({"window_s": 3.0, "modules": [["jit_step", 2.75]]},
            {"win": {}}, {"win": {"Device_batches_in": 2400,
                                  "Inputs_received": 2400 * 32768}},
            {"window": "win"})
    c.cfg, c.offered_s, c.device = cfg, 30.0, {"kind": "TPU v5 lite"}
    c.clock = types.SimpleNamespace(rows=32768, rate=4250)
    c.fired_in_window = lambda: 2400 * 16400
    params = {"modules": "^jit_(step|fire|rebuild)"}
    one, two = read(c, params), read(c, {**params, "fields": 2})
    kw = dict(rows=32768, keys_touched=2125,
              panes_per_batch=32768 / 4250 + 1, fired=16400,
              ring=roofline.ring_size(3600, 1), win_units=3600)
    assert two / one == pytest.approx(
        roofline.window_step_bytes(**kw, fields=2)
        / roofline.window_step_bytes(**kw))
    assert 1.6 < two / one < 2.0 and 0 < two < 1.0
    with open(os.path.join(BENCH_DIR, "metrics",
                           "window_step_roofline.sg2.json")) as f:
        assert json.load(f)["params"]["fields"] == 2
