"""The benchmark's ``sg2`` configuration (SABER / LightSaber query SG2:
per-plug sliding average, ``benchmark/configs/sg2.py``) at a small size on
the CPU backend, through ``PipeGraph`` and the public builders: 37 plugs in
5 houses, 60 s windows sliding by 1 s, 1,024-row blocks (the rehearsal
sizes of ``benchmark/workloads/sg2.saturated.json``). The system is held to
the configuration's plain numpy ``reference``."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import Cell  # noqa: E402
from harness.windows import table_rows  # noqa: E402

from common import run_benchmark_config  # noqa: E402

BLOCKS, SEED = 14, 2_147_483_659


def run_sg2(**config):
    """BLOCKS blocks through the configuration's own graph; the delivered
    columns, the cell, its stream and what was offered."""
    return run_benchmark_config("sg2.saturated", BLOCKS, SEED, **config)


@pytest.fixture(scope="module")
def sg2():
    return run_sg2()


@pytest.fixture(scope="module")
def expected(sg2):
    cell, off = sg2["cell"], sg2["offered"]
    return cell.module.reference(off.blocks(), cell.cfg, sg2["stream"],
                                 off.last_ts)


def valid_rows(run):
    c = run["cols"]
    keep = c["valid"].astype(bool)
    return {k: v[keep] for k, v in c.items()}


def test_sizes_are_the_rehearsal_sizes(sg2):
    cfg, reg = sg2["cell"].cfg, sg2["stream"]["registry"]
    assert cfg["keys"]["count"] == 37 == len(reg["plug"])
    assert cfg["houses"] == 5 and reg["house"].max() == 4
    assert cfg["window"]["win_us"] == 60 * cfg["window"]["slide_us"]
    assert sg2["eos"] == 1


def test_every_plug_slide_reaches_the_sink_once(sg2, expected):
    rows = valid_rows(sg2)
    k, w, _ = table_rows(expected)
    want = set(zip(k.tolist(), w.tolist()))
    got = list(zip(rows["key"].tolist(), rows["wid"].tolist()))
    assert len(got) == len(set(got)) == len(want) > 37 * 60
    assert set(got) == want


@pytest.mark.parametrize("column,table", [("sum", "value"),
                                          ("count", "count")])
def test_sum_and_count_equal_the_reference_exactly(sg2, expected, column,
                                                   table):
    rows = valid_rows(sg2)
    want = expected[table][rows["key"], rows["wid"]]
    assert rows[column].dtype == (np.float32 if column == "sum"
                                  else np.int32)
    assert (rows[column].astype(np.int64) == want).all()
    assert want.max() > 60      # windows that hold more than one pane


def test_avg_is_sum_over_count_within_one_ulp(sg2):
    rows = valid_rows(sg2)
    want = rows["sum"] / rows["count"].astype(np.float32)
    assert rows["avg"].dtype == np.float32
    assert (np.abs(rows["avg"] - want) <= np.spacing(want)).all()
    assert (rows["avg"] >= 0).all() and rows["avg"].max() < 1000


@pytest.mark.parametrize("field", ["plug", "household", "house"])
def test_the_triple_delivered_is_the_registrys(sg2, field):
    c, reg = sg2["cols"], sg2["stream"]["registry"]
    assert (c[field] == reg[field][c["key"]]).all()
    # and the registry's rows are distinct triples, in order
    triples = list(zip(reg["house"].tolist(), reg["household"].tolist(),
                       reg["plug"].tolist()))
    assert triples == sorted(set(triples))


def test_the_reference_keys_events_as_the_pack_operator_does(sg2):
    cell, reg = sg2["cell"], sg2["stream"]["registry"]
    every = {k: v for k, v in reg.items() if k != "base"}
    idx = cell.module.plug_index(every, reg, cell.cfg)
    assert (idx == np.arange(len(reg["plug"]))).all()


def test_more_windows_than_one_fire_block_gives_the_same_rows(sg2):
    """A 1,024-row block closes 13 or 14 slides of 37 plugs, about 500
    windows. With no ``num_win_per_batch`` the operator sizes its fire
    programs by its plans, and a block's windows leave in its step; with
    the key capacity given as the budget (37 windows a program: the
    operator's own sizing before PR 30) a block needs a dozen fire-only
    programs after its step. The rows are the same."""
    narrow = run_sg2(num_win_per_batch=37)
    assert sg2["cell"].cfg["num_win_per_batch"] is None
    win = sg2["stats"]["win"]
    assert narrow["stats"]["win"]["Fire_programs"] > 3 * win["Fire_programs"]
    assert narrow["stats"]["win"]["Windows_fired"] == win["Windows_fired"]
    # a firing batch is one program, and the flush a few
    assert win["Fire_programs"] <= BLOCKS + 8
    assert win["Fire_grouped_programs"] == win["Fire_programs"]

    def as_set(run):
        r = valid_rows(run)
        return set(zip(*(r[k].tolist() for k in
                         ("key", "wid", "sum", "count", "avg", "plug",
                          "household", "house"))))
    assert as_set(narrow) == as_set(sg2)


def test_counters_of_the_window_operator(sg2):
    win = sg2["stats"]["win"]
    rows = sg2["cols"]
    assert win["Windows_fired"] == len(rows["valid"]) > 0
    assert 0 < win["Fire_programs"] <= win["Device_programs_run"]
    assert win["Fire_plan_total_usec"] > 0
    assert win["Fire_plan_total_usec"] <= win["Dispatch_host_prep_total_usec"]
    assert sg2["stats"][sg2["roles"]["exit"]]["Exit_process_total_usec"] > 0
    # a time-based operator: no batch is prepared by the key, and the
    # plan the host builds a program is a row a firing plug, never a row
    # a window (PR 35's counters; its lanes until PR 39)
    assert win["Prep_by_key_batches"] == 0 < win["Dispatch_batches"]
    assert 0 < win["Fire_plan_rows"] <= 37 * win["Fire_programs"]
    assert win["Fire_plan_rows"] * 10 < win["Windows_fired"]


@pytest.mark.parametrize("name,low,high,since", [
    ("fire_grouped_share.sg2", 95.0, 100.0, "PR 28"),
    ("fire_groups_per_program.sg2", 1.0, 32.0, "PR 28"),
    ("windows_per_fire_program.sg2", 100.0, 1024.0, None),
    ("fire_range_cut_share.sg2", 0.0, 50.0, "PR 30")])
def test_the_fire_query_goes_by_range_and_its_metrics_say_so(sg2, name, low,
                                                             high, since):
    """Every plug fires the same slides, so the programs answer by range,
    a block's 13 or 14 slides in one program of at most 32 ranges (more
    than a few hundred windows each), and only the flush's programs are
    cut at the table's size; the four metrics read the counters through
    the reader that gives nothing, not 0, for a program without them
    (``since``: the PR that brought the counter; None: it always was)."""
    import types

    from harness.cell import BENCH_DIR, load_module
    from harness.stats import StatsWindow

    cell = sg2["cell"]
    entry, spec = [(m, f) for m, f in cell.metrics("per_layer")
                   if m["name"] == name][0]
    assert entry["workloads"] == ["sg2.saturated"]
    assert entry["layer"] == spec["layer"] == "device programs"
    assert entry["moves"] == spec["moves"] == "events_per_s"
    assert entry["unit"] == spec["unit"]
    assert spec["reader"] == "counter_ratio_present.py"
    read = load_module(os.path.join(BENCH_DIR, "metrics", spec["reader"])).read
    zeros = {op: dict.fromkeys(st, 0) for op, st in sg2["stats"].items()}

    def ctx(end):
        return types.SimpleNamespace(
            trace=None, events=1, window_s=1.0,
            stats=StatsWindow(zeros, end, sg2["roles"]))

    assert low <= read(ctx(sg2["stats"]), spec["params"]) <= high
    newer = {"PR 28": ("Fire_grouped_programs", "Fire_groups",
                       "Fire_range_cuts"),
             "PR 30": ("Fire_range_cuts",), None: ()}[since]
    old = {op: {k: v for k, v in st.items() if k not in newer}
           for op, st in sg2["stats"].items()}
    if since:
        assert read(ctx(old), spec["params"]) is None
    else:
        assert read(ctx(old), spec["params"]) \
            == read(ctx(sg2["stats"]), spec["params"])


@pytest.mark.parametrize("high,ok", [(1000, True), (1_000_000, False)])
def test_make_stream_refuses_values_whose_window_sum_leaves_float32(high, ok):
    cell = Cell("sg2.saturated", rehearse=True)
    cell.cfg["value"] = {"low": 0, "high": high}
    if ok:
        st = cell.module.make_stream(3, cell.cfg, cell.traffic)
        assert st["window_sum_bound"] < 2 ** 24
        assert all(c["value"].dtype == np.float32 for c in st["pool"])
    else:
        with pytest.raises(ValueError, match=r"2\*\*24"):
            cell.module.make_stream(3, cell.cfg, cell.traffic)


def test_the_record_crosses_at_its_width():
    cell = Cell("sg2.saturated")
    cfg = cell.cfg
    st = Cell("sg2.saturated", rehearse=True)
    pool = st.module.make_stream(1, st.cfg, st.traffic)["pool"]
    width = sum(c.dtype.itemsize for c in pool[0].values()) + 8
    assert width == cfg["record_bytes"] == 32 == sum(
        int(t[-2:]) // 8 for t in cfg["record"].values())
    assert set(pool[0]) | {"timestamp"} == set(cfg["record"])
    assert cfg["reduced"] == ["trace"]
    w = cfg["window"]
    assert (w["win_us"], w["slide_us"]) == (3_600_000_000, 1_000_000)
    assert cfg["keys"]["count"] == 2125 and cfg["houses"] == 40


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "configs", "sg2.py")) as f:
        src = f.read()
    ref = src[src.index("def reference("):]
    assert "windflow_tpu" not in ref and "import" not in ref
    head = src[:src.index("def build_graph(")]
    assert "windflow_tpu" not in head
