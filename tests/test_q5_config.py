"""The benchmark's ``q5`` configuration (NEXmark Query 5, hot items:
``benchmark/configs/q5.py``) at a small size on the CPU backend, through
``PipeGraph`` and the public builders: 20 events a second of event time,
128-row blocks, 64 key slots for ~40 auctions live at once and hundreds in
all (the rehearsal sizes of ``benchmark/workloads/q5.saturated.json``).
The system is held to the configuration's plain numpy ``reference``, and
the reference to a count in plain Python."""

import ast
import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import Cell  # noqa: E402

from common import ColumnRows, run_benchmark_config  # noqa: E402

BLOCKS = 90
SEEDS = [2_147_483_659, 7]
PERSON, AUCTION, BID = 0, 1, 2


def run_q5(seed=SEEDS[0], blocks=BLOCKS, **config):
    return run_benchmark_config("q5.saturated", blocks, seed, **config)


@pytest.fixture(scope="module", params=SEEDS)
def q5(request):
    run = run_q5(request.param)
    cell, off = run["cell"], run["offered"]
    run["expected"] = cell.module.reference(off.blocks(), cell.cfg,
                                            run["stream"], off.last_ts)
    return run


def valid_rows(run):
    c = run["cols"]
    keep = c["valid"].astype(bool)
    return {k: v[keep] for k, v in c.items()}


# ---------------------------------------------------------------------------
# the system against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("column,table", [
    ("auction", "auction"), ("count", "hot_count"), ("bids", "bids"),
    ("hot_item", "value")])
def test_every_window_reaches_the_sink_once_with_the_references(
        q5, column, table):
    rows, exp = valid_rows(q5), q5["expected"]
    held = np.nonzero(exp["count"][0])[0]
    assert sorted(rows["wid"].tolist()) == held.tolist()     # once each
    assert len(held) > 250
    assert (rows["all"] == 0).all() and q5["eos"] == 1
    assert rows[column].dtype == np.int32
    assert (rows[column].astype(np.int64) == exp[table][0, rows["wid"]]).all()


def test_nothing_invalid_is_delivered_for_a_window_that_holds_a_bid(q5):
    c = q5["cols"]
    empty = c["wid"][~c["valid"].astype(bool)]
    assert not q5["expected"]["count"][0, empty].any()


def test_no_event_is_late_or_lost_at_either_stage(q5):
    st, off = q5["stats"], q5["offered"]
    assert st["bids"]["Inputs_received"] == BLOCKS * 128
    for op in ("bids", "win", "one", "hot", "pack"):
        assert st[op]["Late_records"] == 0 == st[op]["Late_dropped"], op
        assert st[op]["Inputs_ignored"] == (
            BLOCKS * 128 - st["win"]["Inputs_received"]
            if op == "bids" else 0), op
    assert st["hot"]["Inputs_received"] == st["win"]["Windows_fired"]
    assert st["pack"]["Inputs_received"] == st["hot"]["Windows_fired"]
    assert off.n_warm == BLOCKS


def test_key_slots_turn_over_and_the_table_never_grows(q5):
    win, cfg = q5["stats"]["win"], q5["cell"].cfg
    bids = np.concatenate([c["auction_lo"][c["event_type"] == BID]
                           for c, _ in q5["offered"].blocks()])
    distinct = len(np.unique(bids))
    assert distinct > 10 * cfg["key_capacity"] == 640
    assert win["Keys_admitted"] == distinct == win["Keys_reclaimed"]
    assert win["Key_capacity_growths"] == 0
    assert win["Key_slots_live"] <= cfg["key_capacity"]
    assert win["Key_turnover_total_usec"] > 0
    # the second stage has one key, and all the counters of a window
    # operator
    hot = q5["stats"]["hot"]
    assert hot["Key_capacity_growths"] == 0 and hot["Keys_admitted"] >= 1


def test_twenty_eight_payload_bytes_an_event_cross(q5):
    src, n = q5["stats"]["src"], BLOCKS * 128
    assert src["Device_bytes_H2D"] == 28 * n
    assert src["Stage_h2d_puts"] == src["Stage_batches"] == BLOCKS
    block = q5["stream"]["pool"][0]
    assert sum(v.dtype.itemsize for v in block.values()) == 28
    assert all(v.dtype == np.int32 for v in block.values())


def test_a_small_fire_budget_splits_a_slide_and_nothing_is_late():
    """With 16 windows a fire program a slide of ~40 auctions leaves in
    three: every batch is stamped below the windows its drain still
    owes, so the second stage takes every row in time."""
    run = run_q5(blocks=24, num_win_per_batch=16)
    win, hot = run["stats"]["win"], run["stats"]["hot"]
    assert win["Fire_programs"] > 5 * win["Device_batches_in"]
    assert hot["Late_dropped"] == 0 == hot["Late_records"]
    cell, off = run["cell"], run["offered"]
    exp = cell.module.reference(off.blocks(), cell.cfg, run["stream"],
                                off.last_ts)
    rows = valid_rows(run)
    assert sorted(rows["wid"].tolist()) == np.nonzero(
        exp["count"][0])[0].tolist()
    assert (rows["hot_item"] == exp["value"][0, rows["wid"]]).all()


# ---------------------------------------------------------------------------
# the reference and the generator, in plain Python
# ---------------------------------------------------------------------------
def test_the_reference_equals_a_counter_a_window(q5):
    cell, exp = q5["cell"], q5["expected"]
    w = cell.cfg["window"]
    per = w["win_us"] // w["slide_us"]
    panes = collections.defaultdict(collections.Counter)
    for c, ts in q5["offered"].blocks():
        for kind, a, t in zip(c["event_type"].tolist(),
                              c["auction_lo"].tolist(), ts.tolist()):
            if kind == BID:
                panes[t // w["slide_us"]][a] += 1
    seen = 0
    for first in range(max(panes) + 1):
        c = collections.Counter()
        for p in range(first, first + per):
            c.update(panes.get(p, {}))
        v = first + per - 1
        if not c:
            assert exp["count"][0, v] == 0
            continue
        seen += 1
        top = max(c.values())
        hot = min(a for a, n in c.items() if n == top)
        bids = sum(c.values())
        assert (exp["auction"][0, v], exp["hot_count"][0, v],
                exp["bids"][0, v], exp["count"][0, v]) == (hot, top, bids,
                                                           bids)
        assert exp["value"][0, v] == (bids & 127) << 24 | top << 13 | (
            hot & 8191)
    assert seen == (exp["count"] > 0).sum() > 250


def test_a_tie_elects_the_lowest_id():
    """A stream built to tie: two auctions with three bids each in every
    window, a third with two."""
    cell = Cell("q5.saturated", rehearse=True)
    rows, slide = 16, cell.cfg["window"]["slide_us"]
    kinds = np.full(rows, BID, np.int32)
    zeros = np.zeros(rows, np.int32)
    blocks = []
    for b in range(6):
        ids = np.array([1500, 1400, 1600] * 2 + [1600, 1400, 1500, 1300,
                                                 1300] + [1200] * 5)
        ids[11:] += 10 * b + np.arange(5)     # five more, one bid each
        cols = {"event_type": kinds, "auction_lo": ids.astype(np.int32),
                "auction_hi": zeros, "bidder_lo": zeros, "bidder_hi": zeros,
                "price_lo": zeros, "price_hi": zeros}
        blocks.append((cols, b * slide + np.arange(rows, dtype=np.int64)))
    exp = cell.module.reference(iter(blocks), cell.cfg, {},
                                int(blocks[-1][1][-1]))
    held = np.nonzero(exp["count"][0])[0]
    assert (exp["auction"][0, held] == 1400).all()
    assert exp["hot_count"][0, held].max() == 15      # five panes of three

    def source(shipper, ctx=None):
        for cols, ts in blocks:
            shipper.set_next_watermark(max(0, int(ts[0]) - 1))
            shipper.push_columns(cols, ts=ts)
        shipper.set_next_watermark(int(ts[-1]))

    out = ColumnRows()
    cell.cfg["batch_rows"] = rows
    graph, _roles = cell.module.build_graph(source, out, cell.cfg, {})
    graph.run()
    c = out.columns()
    keep = c["valid"].astype(bool)
    assert sorted(c["wid"][keep].tolist()) == held.tolist()
    assert (c["auction"][keep] == 1400).all()
    assert (c["count"][keep] == exp["hot_count"][0, c["wid"][keep]]).all()
    assert (c["hot_item"][keep] == exp["value"][0, c["wid"][keep]]).all()


def test_the_mix_is_1_3_46_and_half_the_bids_are_hot():
    cell = Cell("q5.saturated", rehearse=True)
    stream = cell.module.make_stream(5, cell.cfg, cell.traffic)
    rows, g = cell.cfg["batch_rows"], cell.cfg["generator"]
    blocks = [stream["pool"][i] for i in range(50)]      # 128 epochs
    kind = np.concatenate([b["event_type"] for b in blocks])
    assert np.bincount(kind).tolist() == [128, 3 * 128, 46 * 128]
    assert kind[:50].tolist() == [PERSON] + [AUCTION] * 3 + [BID] * 46
    auction = np.concatenate([b["auction_lo"] for b in blocks])
    n = np.arange(len(kind))
    last = n // 50 * 3 + 2                   # a bid's lastBase0AuctionId
    hot = g["first_auction_id"] + last // 100 * 100
    is_hot = (auction == hot)[kind == BID]
    assert 0.47 < is_hot.mean() < 0.53
    # the others lie among the auctions in flight and the lead ahead
    rest = (auction - g["first_auction_id"])[kind == BID][~is_hot]
    lo = np.maximum(last - g["in_flight_auctions"], 0)[kind == BID][~is_hot]
    hi = (last + g["auction_id_lead"])[kind == BID][~is_hot]
    assert ((rest >= lo) & (rest <= hi)).all()
    # an Auction's own id is its number, a Person's its own; ids are whole
    own = auction[kind == AUCTION] - g["first_auction_id"]
    assert own.tolist() == list(range(3 * 128))
    person = np.concatenate([b["bidder_lo"] for b in blocks])
    assert (person[kind == PERSON] - g["first_person_id"]).tolist() == \
        list(range(128))
    assert (auction[kind == PERSON] == 0).all()
    assert all((b[f] == 0).all() for b in blocks
               for f in ("auction_hi", "bidder_hi", "price_hi"))
    price = np.concatenate([b["price_lo"] for b in blocks])[kind == BID]
    assert 100 <= price.min() and price.max() <= 100_000_000
    assert len(blocks[0]["price_lo"]) == rows


@pytest.mark.parametrize("at", ["head", "past_head", "far"])
def test_a_block_past_one_cycle_is_the_generator_at_its_events(at):
    """The pool's block ``i`` is the generator evaluated at events ``[rows
    i, rows (i + 1))``, with the random draws of block ``i % cycle``: ids
    move on with the event number, past a cycle too. Past the stream's
    head the pool moves one cycle's kept columns on by whole cycles
    instead of generating: the same columns, bit for bit."""
    cell = Cell("q5.saturated", rehearse=True)
    stream = cell.module.make_stream(11, cell.cfg, cell.traffic)
    pool, rows = stream["pool"], cell.cfg["batch_rows"]
    cycle = int(cell.traffic["pool_blocks"])
    i = {"head": 3 * cycle + 7,
         "past_head": -(-pool.head // cycle) * cycle + 7,
         "far": 4_000 * cycle + 7}[at]
    assert (i < pool.head) == (at == "head") and i % cycle == 7
    assert len(pool) > 10**9 and pool[i % len(pool)] is not None
    direct = cell.module.generate(i * rows, pool.draws[7],
                                  cell.cfg["generator"])
    block = pool[i]
    assert list(block) == list(direct)
    assert all((block[k] == direct[k]).all()
               and block[k].dtype == direct[k].dtype for k in direct)
    # same draws, later events: the kinds repeat, the ids moved on by the
    # auctions of the cycles between, but for the hot ones' hundreds
    base = pool[7]
    assert (block["event_type"] == base["event_type"]).all()
    epochs = (i - 7) * rows // 50
    bid = base["event_type"] == BID
    moved = block["auction_lo"][bid] - base["auction_lo"][bid]
    assert (abs(moved - 3 * epochs) < 100).all() and np.median(
        moved) == 3 * epochs
    # and no auction id of a later cycle is one of the first
    assert block["auction_lo"][bid].min() > base["auction_lo"][bid].max()


@pytest.mark.parametrize("change,match", [
    ({"generator": {"hot_ratio_unit": 1000, "hot_auction_ratio": 50}},
     "bids >= 2"),
    ({"nominal_rate": 20_000_000}, "span")])
def test_make_stream_refuses_a_stream_that_would_leave_hot_items_fields(
        change, match):
    cell = Cell("q5.saturated", rehearse=True)
    cfg, traffic = dict(cell.cfg), dict(cell.traffic)
    if "generator" in change:
        cfg["generator"] = dict(cfg["generator"], **change["generator"])
    else:
        traffic.update(change)
    with pytest.raises(ValueError, match=match):
        cell.module.make_stream(3, cfg, traffic)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "configs", "q5.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = {a.name if isinstance(n, ast.Import) else n.module
             for n in top for a in n.names}
    assert names == {"__future__", "numpy"}
    # the program is imported inside build_graph alone
    inside = {n.module.split(".")[0]
              for f in tree.body if isinstance(f, ast.FunctionDef)
              for n in ast.walk(f) if isinstance(n, ast.ImportFrom)}
    build = next(f for f in tree.body if isinstance(f, ast.FunctionDef)
                 and f.name == "build_graph")
    assert {n.module.split(".")[0] for n in ast.walk(build)
            if isinstance(n, ast.ImportFrom)} == {"windflow_tpu"}
    assert inside == {"windflow_tpu"}


def test_the_files_state_the_deployment():
    cell = Cell("q5.saturated")
    cfg, t = cell.cfg, cell.traffic
    g = cfg["generator"]
    assert (g["person_proportion"], g["auction_proportion"],
            g["bid_proportion"]) == (1, 3, 46)
    assert (g["in_flight_auctions"], g["hot_auction_ratio"],
            g["first_event_rate"]) == (100, 2, 10_000)
    assert (cfg["window"]["win_us"], cfg["window"]["slide_us"]) == (
        10_000_000, 2_000_000)
    assert cfg["reduced"] == ["strings"] and "generator" in cfg["assumed"]
    assert len(cfg["departures"]) == 6 and set(cfg["limits"].values()) == {0}
    assert "q5.sql" in cfg["origin"] and "BidGenerator" in cfg["origin"]
    assert (cfg["batch_rows"], cfg["key_capacity"],
            cfg["channel_capacity"]) == (16_384, 8_192, 16)
    assert t["nominal_rate"] == g["first_event_rate"]
    assert t["pool_blocks"] * cfg["batch_rows"] == 2_048_000
    assert cell.module.windows_per_event(cfg) == 5
    names = {m["name"] for m, _ in cell.metrics("per_layer")}
    mine = {n for n in names if n.endswith(".q5")}
    # nine `.sat` metrics with no `workloads` list, PR 36's eleven, and
    # PR 37's `readback_deferred_share.sat` (`bids` compacts); PR 39's
    # `fire_one_round_share.q5`
    assert len(mine) == 14 and len(names - mine) == 21
    assert "fire_one_round_share.q5" in mine
    assert "readback_deferred_share.sat" in names
    assert all(n.endswith(".sat") for n in names - mine)
