"""The benchmark's ``sd`` configuration (SpikeDetection of DSPBench /
ParaGroup StreamBenchmarks: per mote a moving average over a count-based
window of its last readings, slide 1, then a threshold filter,
``benchmark/configs/sd.py``) at a small size on the CPU backend, through
``PipeGraph`` and the public builders: 5 motes, windows of 64 readings,
512-row blocks, a fire budget of 512 (the rehearsal sizes of
``benchmark/workloads/sd.saturated.json``). The system is held to the
configuration's plain numpy ``reference``."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import BENCH_DIR, Cell, load_module  # noqa: E402
from harness.stats import StatsWindow  # noqa: E402
from harness.windows import table_rows  # noqa: E402

from common import run_benchmark_config  # noqa: E402

BLOCKS, SEED = 12, 2_147_483_659


def run_sd(**config):
    """BLOCKS blocks through the configuration's own graph; the delivered
    columns, the cell, its stream and what was offered."""
    return run_benchmark_config("sd.saturated", BLOCKS, SEED, **config)


@pytest.fixture(scope="module")
def sd():
    return run_sd()


@pytest.fixture(scope="module")
def expected(sd):
    cell, off = sd["cell"], sd["offered"]
    return cell.module.reference(off.blocks(), cell.cfg, sd["stream"],
                                 off.last_ts)


def valid_rows(run):
    c = run["cols"]
    keep = c["valid"].astype(bool)
    return {k: v[keep] for k, v in c.items()}


def exit_stats(run):
    """The fused chain after the window is one stage, ``avg∘spikes``."""
    return [st for name, st in run["stats"].items()
            if run["roles"]["exit"] in name][0]


def test_sizes_are_the_rehearsal_sizes(sd):
    cfg = sd["cell"].cfg
    assert cfg["keys"]["count"] == 5 == len(sd["stream"]["baseline"])
    assert cfg["count_window"]["win_rows"] == 64
    assert cfg["count_window"]["slide_rows"] == 1
    assert cfg["batch_rows"] == 512 == cfg["num_win_per_batch"]
    assert sd["eos"] == 1


def test_every_passing_window_reaches_the_sink_once_and_no_other(sd,
                                                                 expected):
    rows = valid_rows(sd)
    k, w, _ = table_rows(expected)
    want = set(zip(k.tolist(), w.tolist()))
    got = list(zip(rows["device"].tolist(), rows["wid"].tolist()))
    assert len(got) == len(set(got)) == len(want) > 100
    assert set(got) == want
    # most windows are dropped by the filter, and the sink sees none of
    # them: the window operator fired one for every reading
    fired = sd["stats"]["win"]["Windows_fired"]
    assert fired == BLOCKS * 512
    assert 0.02 * fired < len(got) < 0.08 * fired
    st = exit_stats(sd)
    assert st["Inputs_received"] == fired
    assert st["Outputs_sent"] == len(got)


@pytest.mark.parametrize("column,table,dtype", [
    ("sum", "value", np.float32), ("count", "count", np.int32),
    ("last", "last", np.float32)])
def test_sum_count_and_last_equal_the_reference_exactly(sd, expected, column,
                                                        table, dtype):
    rows = valid_rows(sd)
    want = expected[table][rows["device"], rows["wid"]]
    assert rows[column].dtype == dtype
    assert (rows[column].astype(np.int64) == want).all()
    assert want.min() > 0
    assert expected["count"].max() == 64


def test_incremental_average_is_sum_over_count_within_one_ulp(sd):
    rows = valid_rows(sd)
    want = rows["sum"] / rows["count"].astype(np.float32)
    got = rows["incremental_average"]
    assert got.dtype == np.float32
    assert (np.abs(got - want) <= np.spacing(want)).all()
    # and every delivered row is a spike by upstream's own predicate, in
    # doubles (the integer form is the same inequality times count * 40)
    avg = rows["sum"].astype(np.float64) / rows["count"]
    assert (np.abs(rows["last"] - avg) > 0.025 * avg).all()


def test_the_reference_against_a_fold_of_one_reading_at_a_time(sd, expected):
    """Plain Python, upstream's shape: per mote the last ``win`` readings
    in a list; the window that ends with a reading is ``[w, w + win)``
    with ``w = n - win``. Partial windows (the flush's) hold the mote's
    last readings."""
    cell, off = sd["cell"], sd["offered"]
    win = cell.cfg["count_window"]["win_rows"]
    inverse = cell.cfg["threshold_inverse"]
    readings = {}
    for cols, _ in off.blocks():
        vals = cell.module.words_double(cols["value_lo"], cols["value_hi"])
        for mote, v in zip(cols["device_lo"].tolist(), vals.tolist()):
            assert v == int(v)
            readings.setdefault(mote, []).append(int(v))
    want = {}
    for mote, vs in readings.items():
        for w in range(len(vs)):
            held = vs[w:w + win]
            total, last = sum(held), held[-1]
            if abs(last * len(held) - total) * inverse > total:
                want[(mote, w)] = (total, len(held), last)
    k, w, v = table_rows(expected)
    got = {(a, b): (c, int(expected["count"][a, b]),
                    int(expected["last"][a, b]))
           for a, b, c in zip(k.tolist(), w.tolist(), v.tolist())}
    assert got == want and len(want) > 100


@pytest.mark.parametrize("case", ["whole_numbers", "random_doubles",
                                  "zeros"])
def test_narrow_is_astype_float32(case):
    """The ``narrow`` map's bit operations against numpy's conversion:
    exact where float32 holds the double (every whole number below
    2**24), within one ulp elsewhere (it truncates, numpy rounds), zero
    to zero with its sign."""
    import jax

    mod = Cell("sd.saturated").module
    rng = np.random.default_rng(7)
    if case == "whole_numbers":
        x = np.concatenate([rng.integers(-2**24 + 1, 2**24, 4096),
                            [1, -1, 2**24 - 1, 1 - 2**24, 1199, 200]]
                           ).astype(np.float64)
        x = x[x != 0]
    elif case == "random_doubles":
        x = rng.normal(0, 1, 4096) * 10.0 ** rng.integers(-30, 30, 4096)
    else:
        x = np.array([0.0, -0.0])
    lo, hi = mod.double_words(x)
    assert (mod.words_double(lo, hi) == x).all()
    got = np.asarray(jax.jit(mod.narrow_double)(lo, hi))
    want = x.astype(np.float32)
    assert got.dtype == np.float32
    if case == "random_doubles":
        assert (np.abs(got - want) <= np.spacing(np.abs(want))).all()
        assert (np.abs(got) <= np.abs(x)).all()     # truncation
    else:
        assert (got == want).all()
        assert (np.signbit(got) == np.signbit(want)).all()


def test_more_windows_than_one_fire_block_gives_the_same_rows(sd):
    """A 512-row block fires 512 windows. With the budget 512 they leave
    in one wide program; with a budget of 64 a block needs eight
    programs. The rows are the same, and ``Fire_lanes`` says how wide
    the programs were: a budget given is the width of every one."""
    narrow = run_sd(num_win_per_batch=64)
    win, nwin = sd["stats"]["win"], narrow["stats"]["win"]
    assert nwin["Windows_fired"] == win["Windows_fired"]
    assert nwin["Fire_programs"] > 4 * win["Fire_programs"]
    assert nwin["Fire_lanes"] == 64 * nwin["Fire_programs"]
    # the budget given is every program's width: 512, nothing else
    assert win["Fire_lanes"] == 512 * win["Fire_programs"] > 4 * 512

    def as_set(run):
        r = valid_rows(run)
        return set(zip(*(r[k].tolist() for k in
                         ("device", "wid", "sum", "count", "last",
                          "incremental_average"))))
    assert as_set(narrow) == as_set(sd)


def test_counters_of_the_window_operator(sd):
    win = sd["stats"]["win"]
    assert win["Fire_lanes"] >= win["Windows_fired"] > 0
    assert 0 < win["Fire_programs"] <= win["Device_programs_run"]
    # count-based windows start at a per-mote arrival index: no two motes
    # share a ring range, so no program walks by range; a mote's windows
    # of a program are consecutive ranges of its own ring, answered by
    # sliding scan where the rule says so (PR 33)
    assert win["Fire_grouped_programs"] == 0 == win["Fire_groups"]
    assert win["Fire_range_cuts"] == 0
    assert win["Fire_plan_total_usec"] > 0
    assert exit_stats(sd)["Exit_process_total_usec"] > 0
    # an operator with no window fires nothing
    assert sd["stats"]["narrow"]["Fire_lanes"] == 0


def test_the_host_plans_by_the_key_and_its_counters_say_so(sd):
    """PR 35: a count-based operator's prep builds no batch-sized plane
    but the rows' slots (every dispatched batch), and the plan the host
    builds a fire program is its chunk rows, at most a row a mote (five
    at the rehearsal sizes), where the program runs hundreds of lanes.
    An operator with no count-based window reads 0 in both."""
    win = sd["stats"]["win"]
    assert win["Prep_by_key_batches"] == win["Dispatch_batches"] > 4
    assert 0 < win["Fire_plan_rows"] <= 5 * win["Fire_programs"]
    assert win["Fire_plan_rows"] * 10 < win["Fire_lanes"]
    for other in (sd["stats"]["narrow"], exit_stats(sd)):
        assert other["Prep_by_key_batches"] == 0 == other["Fire_plan_rows"]


def test_sliding_programs_are_the_ones_the_rule_names(sd):
    """``Fire_sliding_programs`` is ``Fire_programs`` minus the programs
    the rule left on the lane walk. At the rehearsal sizes (8 slots, a
    ring grown from 128 leaves to 256) the budget's width slides, and a
    64-lane program would too; at the cell's sizes so do 16,384 lanes
    over 64 x 2,048 leaves, and a 4,096-slot operator's 64-lane program
    does not."""
    from windflow_tpu.tpu.ffat_tpu import fire_slides

    win = sd["stats"]["win"]
    rep = [n for w in sd["graph"]._workers for n in w.chain
           if getattr(getattr(n, "op", None), "name", None) == "win"][0]
    assert (rep.K_cap, rep.F, rep.W_cap, rep.W_wide) == (8, 256, 512, 512)
    assert all(fire_slides(W, 8, F) for W in (64, 512) for F in (128, 256))
    assert win["Fire_sliding_programs"] == win["Fire_programs"] > 4
    assert all(fire_slides(W, 64, F) for W in (64, 16384)
               for F in (1024, 2048))
    assert not fire_slides(64, 4096, 2048)
    assert sd["stats"]["narrow"]["Fire_sliding_programs"] == 0


@pytest.mark.parametrize("name,low,high,new", [
    ("fire_lane_occupancy.sd", 80.0, 100.0, "Fire_lanes"),
    ("fire_lanes_per_program.sd", 64.0, 512.0, "Fire_lanes"),
    ("fire_sliding_share.sd", 100.0, 100.0, "Fire_sliding_programs"),
    ("fire_plan_rows_per_program.sd", 1.0, 5.0, "Fire_plan_rows"),
    ("prep_by_key_share.sd", 100.0, 100.0, "Prep_by_key_batches"),
    ("windows_per_fire_program.sd", 64.0, 512.0, None),
    ("fire_programs_per_batch.sd", 1.0, 2.0, None),
    ("filter_pass_share.sd", 2.0, 8.0, None)])
def test_the_sd_metrics_read_the_counters(sd, name, low, high, new):
    """The counter metrics of the cell read this run's stats inside their
    range; those that read a counter a later PR brought (``new``:
    ``Fire_lanes``, PR 32; ``Fire_sliding_programs``, PR 33;
    ``Fire_plan_rows`` and ``Prep_by_key_batches``, PR 35) give
    nothing, not 0, for a program from before the counter existed."""
    cell = sd["cell"]
    entry, spec = [(m, f) for m, f in cell.metrics("per_layer")
                   if m["name"] == name][0]
    assert entry["workloads"] == ["sd.saturated"]
    assert entry["layer"] == spec["layer"]
    assert entry["moves"] == spec["moves"] == "events_per_s"
    assert entry["unit"] == spec["unit"]
    read = load_module(os.path.join(BENCH_DIR, "metrics",
                                    spec["reader"])).read
    zeros = {op: dict.fromkeys(st, 0) for op, st in sd["stats"].items()}

    def ctx(end):
        return types.SimpleNamespace(
            trace=None, events=1, window_s=1.0,
            stats=StatsWindow(zeros, end, sd["roles"]))

    assert low <= read(ctx(sd["stats"]), spec["params"]) <= high
    old = {op: {k: v for k, v in st.items() if k != new}
           for op, st in sd["stats"].items()}
    if new:
        assert spec["reader"] == "counter_ratio_present.py"
        assert read(ctx(old), spec["params"]) is None
    else:
        assert read(ctx(old), spec["params"]) \
            == read(ctx(sd["stats"]), spec["params"])


def test_every_sd_metric_has_its_file_and_lists_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".sd")]
    assert len(mine) == 12 and [m["name"] for m in mine[-3:]] == [
        "fire_sliding_share.sd", "fire_plan_rows_per_program.sd",
        "prep_by_key_share.sd"]
    assert not any(m["name"].startswith("fire_grouped_share")
                   for m in mine)
    for m in mine:
        assert m["workloads"] == ["sd.saturated"]
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           spec["reader"]))
    reported = {m["name"] for m, _ in
                Cell("sd.saturated").metrics("per_layer")}
    assert {m["name"] for m in mine} <= reported
    # the accepted metrics without a list are the new cell's at once
    assert {"device_idle_share.sat", "compiles_in_window.sat",
            "h2d_bytes_per_event.sat", "launch_us_per_program.sat"} \
        <= reported
    assert not any(n.endswith(".sg2") for n in reported)
    e2e = [e for e in bench["end_to_end"] if e["name"] == "events_per_s"][0]
    assert "sd.saturated" in e2e["workloads"]    # later cells follow it


def test_window_step_roofline_cb_by_hand():
    """The count-based reader hands ``window_step_bytes`` the window's
    own sizes: rows / motes leaves a mote a batch, the ring of the
    window, the operator's ``Windows_fired`` a batch, three words a
    node."""
    from harness import roofline
    read = load_module(os.path.join(
        BENCH_DIR, "metrics", "window_step_roofline_cb.py")).read
    cfg = Cell("sd.saturated").cfg
    stats = {"win": {"Device_batches_in": 600,
                     "Inputs_received": 600 * 16384,
                     "Windows_fired": 600 * 16384}}
    c = types.SimpleNamespace(
        trace={"window_s": 3.0, "modules": [["jit_step", 2.7],
                                            ["jit_map_narrow", 0.1]]},
        stats=StatsWindow({"win": {}}, stats, {"window": "win"}),
        cfg=cfg, offered_s=30.0, device={"kind": "TPU v5 lite"})
    with open(os.path.join(BENCH_DIR, "metrics",
                           "window_step_roofline.sd.json")) as f:
        params = json.load(f)["params"]
    assert params["fields"] == 3
    need = roofline.window_step_bytes(
        rows=16384, keys_touched=54, panes_per_batch=16384 / 54,
        fired=16384, ring=1024, win_units=1000, fields=3)
    want = need / 819e9 / (2.7 / 60) * 100.0
    assert read(c, params) == pytest.approx(want)
    assert 0 < want < 1.0
    c.trace = None
    assert read(c, params) is None
    c.trace = {"window_s": 3.0, "modules": [["jit_map_narrow", 0.1]]}
    assert read(c, params) is None


@pytest.mark.parametrize("high,ok", [(800, True), (20_000, False)])
def test_make_stream_refuses_readings_whose_window_sum_leaves_float32(high,
                                                                      ok):
    cell = Cell("sd.saturated")
    cell.traffic["pool_blocks"] = 2
    cell.cfg["value"] = {**cell.cfg["value"], "baseline_high": high}
    if ok:
        st = cell.module.make_stream(3, cell.cfg, cell.traffic)
        assert st["window_sum_bound"] < 2 ** 24
        vals = cell.module.words_double(st["pool"][0]["value_lo"],
                                        st["pool"][0]["value_hi"])
        assert (vals == np.round(vals)).all() and vals.min() > 0
        assert vals.max() * 1000 <= st["window_sum_bound"]
    else:
        with pytest.raises(ValueError, match=r"2\*\*24"):
            cell.module.make_stream(3, cell.cfg, cell.traffic)


def test_the_record_crosses_at_its_width(sd):
    """Three 8-byte fields as two int32 words each: 24 payload bytes an
    event, one dtype group, so one ``device_put`` a batch; the fourth
    field is the program's own event-time column."""
    cfg = Cell("sd.saturated").cfg
    pool = sd["stream"]["pool"]
    assert {c.dtype for c in pool[0].values()} == {np.dtype(np.int32)}
    width = sum(c.dtype.itemsize for c in pool[0].values())
    assert width == 24 and width + 8 == cfg["record_bytes"] == 32 == sum(
        int(t[-2:]) // 8 for t in cfg["record"].values())
    src = sd["stats"]["src"]
    assert src["Device_bytes_H2D"] == 24 * BLOCKS * 512
    assert src["Stage_h2d_puts"] == src["Stage_batches"] == BLOCKS
    assert cfg["reduced"] == ["trace"]
    w = cfg["count_window"]
    assert (w["win_rows"], w["slide_rows"]) == (1000, 1)
    assert cfg["keys"]["count"] == 54 and cfg["threshold"] == 0.025
    assert cfg["threshold_inverse"] * cfg["threshold"] == 1
    assert cfg["num_win_per_batch"] == cfg["batch_rows"] == 16384
    assert {"count_window", "threshold", "num_win_per_batch", "batch_rows",
            "key_capacity", "channel_capacity", "motes"} <= set(
                cfg["assumed"])
    assert len(cfg["departures"]) == 3


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "configs", "sd.py")) as f:
        src = f.read()
    ref = src[src.index("def counted_mask("):]
    assert "windflow_tpu" not in ref and "import" not in ref
    assert "jax" not in ref
    head = src[:src.index("def narrow_double(")]
    assert "windflow_tpu" not in head and "jax" not in head
