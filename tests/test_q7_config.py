"""The benchmark's ``q7`` configuration (NEXmark Query 7, the bids at the
highest price of every 10 s window: ``benchmark/configs/q7.py``) at a
small size on the CPU backend, through ``PipeGraph`` and the public
builders: the cell's generator and event rate, 512-row blocks and a
window of four blocks with the bounds to match (the rehearsal sizes of
``benchmark/workloads/q7.saturated.json``), as many blocks as its file
says. The system is held to the configuration's plain numpy
``reference``, and the reference to a plain-Python scan of every window."""

import ast
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import Cell  # noqa: E402
from harness.windows import compare_results  # noqa: E402

from common import run_benchmark_config  # noqa: E402

SEEDS = [2_147_483_659, 7]
PERSON, AUCTION, BID = 0, 1, 2
DEVICE = ("bids", "route", "max", "bid", "join", "pack")
CELL = Cell("q7.saturated", rehearse=True)
BLOCKS = CELL.traffic["rehearsal"]["blocks"]
ROWS, WIN = CELL.cfg["batch_rows"], CELL.cfg["window"]["win_us"]


@pytest.fixture(scope="module", params=SEEDS)
def q7(request):
    run = run_benchmark_config("q7.saturated", BLOCKS, request.param)
    cell, off = run["cell"], run["offered"]
    run["expected"] = cell.module.reference(off.blocks(), cell.cfg,
                                            run["stream"], off.last_ts)
    run["events"] = {k: np.concatenate([b[k] for b, _ in off.blocks()])
                     for k in run["stream"]["pool"][0]}
    run["ts"] = np.concatenate([ts for _, ts in off.blocks()])
    return run


def scan(bids):
    """Q7 in plain Python over ``(seq, time, price)`` of bids: per window
    its highest price, then every bid of that price with its time in
    ``[T - win, T]``: ``{(window, seq)}``."""
    best = {}
    for _, t, p in bids:
        best[t // WIN] = max(best.get(t // WIN, -1), p)
    return {(w, s) for w, m in best.items() for s, t, p in bids
            if p == m and (w + 1) * WIN - WIN <= t <= (w + 1) * WIN}


def pairs_of_table(table):
    """``{(window parity, seq)}`` of a table's delivered cells."""
    r, b = np.nonzero(table["count"])
    return {(int(k % 2), int(blk * ROWS + k // 2)) for k, blk in zip(r, b)}


# ---------------------------------------------------------------------------
# the system against the reference
# ---------------------------------------------------------------------------
def test_every_pair_reaches_the_sink_once_with_the_references_value(q7):
    c, exp = q7["cols"], q7["expected"]
    counts = compare_results(exp, c["row"], c["block"], c["top"],
                             c["valid"])
    assert counts["mismatches"] == 0 and q7["eos"] == 1
    # a window every four blocks, the end-of-stream flush's last among them
    windows = exp["windows"]
    assert len(windows) == BLOCKS * ROWS // WIN
    assert counts["delivered"] == counts["expected"] == int(
        exp["count"].sum()) == int(windows[:, 2].sum()) >= len(windows)
    assert exp["count"].shape == (2 * ROWS, BLOCKS)
    assert c["valid"].all() and c["top"].dtype == np.int32
    # row for row: the bid's place, its value
    assert (exp["count"][c["row"], c["block"]] == 1).all()
    assert (exp["value"][c["row"], c["block"]] == c["top"]).all()


def test_a_delivered_row_is_a_bid_at_its_windows_highest_price(q7):
    c, ev, ts = q7["cols"], q7["events"], q7["ts"]
    bid = c["seq"]
    assert (ev["event_type"][bid] == BID).all()
    for col in ("auction_lo", "auction_hi", "bidder_lo", "bidder_hi",
                "price_lo", "price_hi"):
        assert (c[col] == ev[col][bid]).all(), col
    is_bid = ev["event_type"] == BID
    for w, s, p in zip(c["wid"], bid, c["price_lo"]):
        inside = is_bid & (ts // WIN == w)
        assert p == ev["price_lo"][inside].max()
        assert (w + 1) * WIN - WIN <= ts[s] <= (w + 1) * WIN
    assert (c["block"] == bid // ROWS).all()
    assert (c["row"] == 2 * (bid % ROWS) + (c["wid"] & 1)).all()


def test_no_event_is_late_or_lost_and_the_archive_does_not_grow(q7):
    st, exp = q7["stats"], q7["expected"]
    n = BLOCKS * ROWS
    route = st["bids∘route"]
    bids = int((q7["events"]["event_type"] == BID).sum())
    assert route["Inputs_received"] == n
    assert st["max"]["Inputs_received"] == st["bid"]["Inputs_received"] \
        == bids
    # every batch to both branches whole: nothing gathered; the mask is
    # the one column read back
    assert route["Split_whole_batches"] == 2 * BLOCKS
    assert route["Split_gathered_batches"] == 0
    assert route["Device_bytes_D2H"] == 4 * bids
    for op in ("max", "bid", "join", "pack"):
        assert st[op]["Late_records"] == 0 == st[op]["Late_dropped"], op
    join = st["join"]
    assert join["Join_probe_rows_b"] == bids
    assert join["Join_probe_rows_a"] == len(exp["windows"])
    assert join["Join_pairs"] == int(exp["count"].sum()) \
        == st["pack"]["Inputs_received"]
    assert join["Join_late_probes"] == 0 == join["Join_archive_growths"]
    # B's ring as the file allocates it, A's 64 slots of the window's row
    b_rows = CELL.cfg["archive"]["b_rows"]
    assert join["Join_archive_capacity_rows"] == b_rows + 64 * 8
    assert 0 < join["Join_archive_rows"] < b_rows
    assert join["Join_purged_rows"] > 0.5 * bids
    # the probes compared with more rows than were live
    assert 0 < join["Join_scanned_rows"] < join["Join_probed_rows"]


# ---------------------------------------------------------------------------
# the reference, in plain Python
# ---------------------------------------------------------------------------
def test_the_reference_equals_a_plain_python_scan(q7):
    ev, ts = q7["events"], q7["ts"]
    is_bid = ev["event_type"] == BID
    bids = list(zip(ev["seq"][is_bid].tolist(), ts[is_bid].tolist(),
                    ev["price_lo"][is_bid].tolist()))
    want = scan(bids)
    assert {(w % 2, s) for w, s in want} == pairs_of_table(q7["expected"])
    # every event's time is its number: a bid at exactly a window's end
    # is among them (it meets the closing window where its price is that
    # window's highest)
    assert (ts == ev["seq"]).all()


def constructed_blocks():
    """Three blocks of bids by hand, four microseconds apart, over three
    windows: a tie at the first window's highest price; a bid at exactly
    its end with that price (it meets the closing window) that is also
    the second window's highest (it meets its own); the second window's
    highest tied by a bid at exactly its end, which the third window's
    bids then top."""
    rows = ROWS
    t = np.arange(3 * rows, dtype=np.int64) * (3 * WIN) // (3 * rows)
    at_w, at_2w = (int(np.searchsorted(t, x)) for x in (WIN, 2 * WIN))
    assert t[at_w] == WIN and t[at_2w] == 2 * WIN
    price = np.full(3 * rows, 150, np.int64)
    for i, p in ((10, 900), (40, 900), (at_w, 900), (at_2w, 900),
                 (at_2w + 5, 1_000)):
        price[i] = p
    blocks = []
    for b in range(3):
        sl = slice(b * rows, (b + 1) * rows)
        lo, hi = CELL.module.q5.words(price[sl])
        cols = {"event_type": np.full(rows, BID, np.int32),
                "price_lo": lo, "price_hi": hi,
                "seq": np.arange(sl.start, sl.stop, dtype=np.int32)}
        blocks.append((cols, t[sl]))
    return blocks, t, price


def test_the_reference_takes_ties_and_a_bid_at_the_windows_end():
    blocks, t, price = constructed_blocks()
    table = CELL.module.reference(iter(blocks), CELL.cfg, {}, int(t[-1]))
    bids = list(zip(range(len(t)), t.tolist(), price.tolist()))
    want = scan(bids)
    at_w = int(np.searchsorted(t, WIN))
    at_2w = int(np.searchsorted(t, 2 * WIN))
    # the tie, the bid at the first end for both windows it meets, the bid
    # at the second end for the second window only (the third's highest
    # is 1,000)
    assert want == {(0, 10), (0, 40), (0, at_w), (1, at_w), (1, at_2w),
                    (2, at_2w + 5)}
    assert {(w % 2, s) for w, s in want} == pairs_of_table(table)
    assert table["count"].sum() == 6
    r, b = CELL.module.cell_of(np.array([at_w, at_w]), np.array([0, 1]),
                               ROWS)
    # windows of 512 bids each: the low bits are (id + 512) & 15
    assert table["value"][r, b].tolist() == [900 << 4 | 0, 900 << 4 | 1]
    assert table["windows"].tolist() == [
        [0, WIN, 3, 512], [1, 2 * WIN, 2, 512], [2, 3 * WIN, 1, 512]]


def test_the_control_one_withheld_highest_bid_reads_as_a_mismatch(q7):
    """The reference with one delivered bid lost, in the system's place:
    its window's highest price falls to another bid, and the comparison
    sees it."""
    exp, off = q7["expected"], q7["offered"]
    victim = int(q7["cols"]["seq"][0])
    other = victim + 1 + int(np.argmax(
        q7["events"]["event_type"][victim + 1:] == BID))

    def withheld():
        for cols, ts in off.blocks():
            keep = cols["seq"] != victim
            yield {k: v[keep] for k, v in cols.items()}, ts[keep]

    bad = CELL.module.reference(withheld(), CELL.cfg, q7["stream"],
                                off.last_ts)
    r, b = np.nonzero(bad["count"])
    counts = compare_results(exp, r, b, bad["value"][r, b],
                             np.ones(len(r), bool))
    assert counts["missing"] >= 1 and counts["mismatches"] >= 2
    # and any bid lost: its window's count changes every pair's value
    bad = CELL.module.reference(
        (({k: v[c["seq"] != other] for k, v in c.items()},
          t[c["seq"] != other]) for c, t in off.blocks()),
        CELL.cfg, q7["stream"], off.last_ts)
    r, b = np.nonzero(bad["count"])
    counts = compare_results(exp, r, b, bad["value"][r, b],
                             np.ones(len(r), bool))
    assert counts["wrong_value"] >= 1
    first = next(iter(off.blocks()))[0]
    assert (CELL.module.counted_mask(first, CELL.cfg)
            == (first["event_type"] == BID)).all()


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------
def test_no_price_draw_recurs_within_two_windows():
    stream = CELL.module.make_stream(11, CELL.cfg, CELL.traffic)
    pool, cycle = stream["pool"], CELL.traffic["price_pool_blocks"]
    assert stream["price_draws_every"] == cycle * ROWS >= 2 * WIN
    draws = [CELL.module.prices(i, ROWS, 11, cycle)
             for i in range(cycle + 1)]
    # by the counter, block by block: no two blocks of a cycle alike, the
    # cycle's first again after it; the other draws keep q5's cycle
    assert len({p.tobytes() for p in draws[:cycle]}) == cycle
    assert (draws[cycle] == draws[0]).all()
    assert (pool[CELL.traffic["pool_blocks"]]["auction_lo"]
            != pool[0]["auction_lo"]).any()
    # a block's prices are those draws, 0 for a Person
    for i in (0, 5, cycle - 1):
        b = pool[i]
        person = b["event_type"] == PERSON
        assert (b["price_lo"][~person] == draws[i][~person]).all()
        assert (b["price_lo"][person] == 0).all()
        assert (b["price_hi"] == 0).all()
    p = np.concatenate(draws[:cycle])
    assert p.min() >= 100 and p.max() <= 10**8
    assert (pool[3]["seq"] == 3 * ROWS + np.arange(ROWS)).all()


def test_a_stream_whose_prices_recur_within_two_windows_is_refused():
    short = dict(CELL.traffic, price_pool_blocks=2 * WIN // ROWS - 1)
    with pytest.raises(ValueError, match="within two windows"):
        CELL.module.make_stream(11, CELL.cfg, short)
    CELL.module.make_stream(11, CELL.cfg, dict(
        CELL.traffic, price_pool_blocks=2 * WIN // ROWS))


def test_the_cells_prices_never_recur_in_a_run():
    cell = Cell("q7.saturated")
    t = cell.traffic
    events = t["price_pool_blocks"] * cell.cfg["batch_rows"]
    assert events >= 2 * cell.cfg["window"]["win_us"] \
        * cell.cfg["event_rate"] // 10**6
    assert events > 10**9


# ---------------------------------------------------------------------------
# the program the configuration may use
# ---------------------------------------------------------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "configs", "q7.py")
    src = open(path).read()
    tree = ast.parse(src)
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = {a.name if isinstance(n, ast.Import) else n.module
             for n in top for a in n.names}
    assert names == {"__future__", "os", "numpy", "harness.cell"}
    build = next(f for f in tree.body if isinstance(f, ast.FunctionDef)
                 and f.name == "build_graph")
    inside = {n.module.split(".")[0]
              for f in tree.body if isinstance(f, ast.FunctionDef)
              for n in ast.walk(f) if isinstance(n, ast.ImportFrom)}
    assert inside == {"windflow_tpu"} == {
        n.module.split(".")[0] for n in ast.walk(build)
        if isinstance(n, ast.ImportFrom)}
    # and the reference never reads the join's bounds
    ref = next(f for f in tree.body if isinstance(f, ast.FunctionDef)
               and f.name == "reference")
    assert "join" not in ast.get_source_segment(src, ref)


def test_the_graph_is_the_public_builders_alone():
    stream = CELL.module.make_stream(3, CELL.cfg, CELL.traffic)
    graph, roles = CELL.module.build_graph(lambda s: None, lambda c, t: None,
                                           CELL.cfg, stream)
    assert roles["device"] == list(DEVICE)
    assert (roles["first"], roles["window"], roles["exit"]) == (
        "bids", "join", "pack")
    from windflow_tpu.tpu import Ffat_Windows_TPU, Interval_Join_TPU
    ops = {op.name: op for op in graph._ops}
    join, win = ops["join"], ops["max"]
    assert isinstance(join, Interval_Join_TPU)
    assert isinstance(win, Ffat_Windows_TPU)
    assert (join.lower_bound, join.upper_bound) == (WIN - 1, 1)
    assert join.key_field == "price_lo" and join.parallelism == 1
    assert join.capacity == (None, CELL.cfg["archive"]["b_rows"])
    assert (win.win_len, win.slide_len) == (WIN, WIN)
    # seven threads: the route map runs in the bids filter's program
    assert len(graph._stages) == 7


def test_a_program_without_the_multicast_split_is_refused_at_once(
        monkeypatch):
    from windflow_tpu.topology.multipipe import MultiPipe

    def split(self, splitting_logic, n_branches):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiPipe, "split", split)
    stream = CELL.module.make_stream(3, CELL.cfg, CELL.traffic)
    with pytest.raises(SystemExit, match="multicast split"):
        CELL.module.build_graph(lambda s: None, lambda c, t: None,
                                CELL.cfg, stream)


def test_the_files_state_the_deployment():
    cell = Cell("q7.saturated")
    cfg, t = cell.cfg, cell.traffic
    q5 = Cell("q5.saturated").cfg
    assert cfg["generator"] == q5["generator"]
    assert cfg["window"]["win_us"] == 10_000_000
    assert (cfg["join"]["lower_us"], cfg["join"]["upper_us"]) == (
        9_999_999, 1)
    assert cfg["event_rate"] == t["nominal_rate"] == 1_000_000
    assert cfg["reduced"] == ["strings", "event_rate"]
    assert {"generator", "window", "join", "archive", "batch_rows",
            "pool"} <= set(cfg["assumed"])
    assert "recalled, not read" in cfg["source_note"]
    assert "q7.sql" in cfg["source"] and "q7.sql" in cfg["origin"]
    assert "MAX(price)" in cfg["statement"]
    assert "BETWEEN B1.dateTime - INTERVAL '10' SECOND" in cfg["statement"]
    assert set(cfg["limits"].values()) == {0} and len(cfg["limits"]) == 3
    assert set(cfg["guarantees"]) == {"delivery", "results", "order"}
    assert len(cfg["departures"]) == 8
    assert (cfg["batch_rows"], cfg["channel_capacity"],
            cfg["parallelism"]) == (16_384, 16, 1)
    assert cfg["result"] == {"key": "row", "wid": "block", "value": "top",
                             "valid": "valid"}
    assert cfg["archive"]["b_rows"] % cfg["batch_rows"] == 0
    assert t["warmup"]["blocks"] * cfg["batch_rows"] > \
        cfg["window"]["win_us"] * cfg["event_rate"] // 10**6
    assert cell.module.windows_per_event(cfg) == 1 and cell.chips == 1
    names = {m["name"] for m, _ in cell.metrics("per_layer")}
    mine = {n for n in names if n.endswith(".q7")}
    assert mine == {"join_step_roofline.q7", "join_step_device_share.q7",
                    "join_archive_fill_share.q7",
                    "join_probe_live_share.q7", "split_whole_share.q7"}
    assert all(n.endswith(".sat") for n in names - mine)
    assert [m["name"] for m, _ in cell.metrics("end_to_end")] == [
        "events_per_s", "setup_s"]
