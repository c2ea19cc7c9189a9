"""Peaks, byte counts, the event clock and ``BENCHMARK.json``'s files."""

import json
import os
import re

import numpy as np
import pytest

from harness import roofline
from harness.cell import BENCH_DIR, ROOT, Cell
from harness.traffic import EventClock, draw_ids

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_peaks_have_a_source_and_no_default():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_window_step_bytes_by_hand():
    # 1000 rows over 10 keys, 2 panes a batch, ring 32, a 4-leaf window,
    # 20 windows fired: rows 8000 + leaves 20*2*5 + ancestors 10*(5+1)*15
    # + fire 20*4*5 + results 20*17
    got = roofline.window_step_bytes(rows=1000, keys_touched=10,
                                     panes_per_batch=2, fired=20, ring=32,
                                     win_units=4)
    assert got == 8000 + 200 + 900 + 400 + 340
    assert roofline.ring_size(4, 1) == 32 and roofline.ring_size(1, 1) == 32


def test_event_clock_never_steps_back():
    tr = {"nominal_rate": 1_600_000,
          "warmup": {"blocks": 3, "block_gap_us": 7}}
    c = EventClock(65536, tr)
    seq = [c.warm_ts(b) for b in range(3)] + [c.ts(b) for b in range(4)]
    flat = np.concatenate(seq)
    assert (np.diff(flat) >= 0).all()
    assert seq[3][0] == c.t0
    # event i after warm-up is i / rate seconds of event time after t0
    assert c.ts(1)[-1] - c.t0 == ((2 * 65536 - 1) * 10**6) // 1_600_000


def test_benchmark_json_names_files_that_exist():
    b = bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    assert "setup_s" in e2e
    for group, same in (("end_to_end", ("unit", "source")),
                        ("per_layer", ("layer", "unit", "source", "moves"))):
        for m in b[group]:
            with open(os.path.join(BENCH_DIR, "metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                               spec["reader"]))
            assert set(m.get("workloads", cells)) <= cells
            assert "workloads" not in spec    # BENCHMARK.json alone says
            assert [spec[k] for k in same] == [m[k] for k in same]
            assert m.get("moves", "setup_s") in e2e


@pytest.mark.parametrize("name", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_and_reports_something(name):
    cell = Cell(name)
    assert {m["name"] for m, _ in cell.metrics("end_to_end")} > {"setup_s"}
    assert cell.metrics("per_layer")
    assert set(cell.cfg["limits"]) == {"result_mismatches",
                                       "events_unaccounted", "late_records"}


@pytest.mark.parametrize("dist", [{"distribution": "uniform"},
                                  {"distribution": "zipf", "s": 1.1}])
def test_ids_follow_the_distribution_the_file_names(dist):
    ids = draw_ids(np.random.default_rng(2**31 + 5), 1000, 200_000, dist)
    assert ids.min() >= 0 and ids.max() < 1000
    share = np.sort(np.bincount(ids, minlength=1000))[::-1] / len(ids)
    if dist["distribution"] == "uniform":
        assert share[0] < 0.002
    else:
        # rank 1 of Zipf 1.1 over 1,000 ids holds 1 / H(1000, 1.1) = 18%
        assert share[0] == pytest.approx(0.18, abs=0.01)
        assert share[0] / share[1] == pytest.approx(2 ** 1.1, rel=0.05)


def test_a_cell_file_can_skew_the_keys_without_code():
    cell = Cell("ysb.saturated", rehearse=True)
    flat = cell.module.make_stream(7, cell.cfg, cell.traffic)
    skew = cell.module.make_stream(7, cell.cfg, {
        **cell.traffic, "ads": {"distribution": "zipf", "s": 1.1}})
    top = lambda st: np.bincount(np.concatenate(
        [c["ad_id_lo"] for c in st["pool"]]), minlength=1000).max()
    assert top(skew) > 5 * top(flat)
    rec = cell.cfg["record"]
    width = sum(c.dtype.itemsize for c in flat["pool"][0].values()) + 8
    assert width == cell.cfg["record_bytes"] == sum(
        int(t[-2:]) // 8 for t in rec.values())
