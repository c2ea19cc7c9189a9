"""The benchmark's ``q20`` configuration (NEXmark Query 20, every bid
joined with its auction of category 10: ``benchmark/configs/q20.py``) at a
small size on the CPU backend, through ``PipeGraph`` and the public
builders: the cell's generator and bounds, 512-row blocks (the rehearsal
sizes of ``benchmark/workloads/q20.saturated.json``). The system is held
to the configuration's plain numpy ``reference`` (the REGULAR join), and
the reference to a dictionary join in plain Python."""

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

BLOCKS = 48
SEEDS = [2_147_483_659, 7]
PERSON, AUCTION, BID = 0, 1, 2
DEVICE = ("kind", "bid", "cat10", "auction", "join", "pack")


@pytest.fixture(scope="module", params=SEEDS)
def q20(request):
    run = run_benchmark_config("q20.saturated", BLOCKS, request.param)
    cell, off = run["cell"], run["offered"]
    run["expected"] = cell.module.reference(off.blocks(), cell.cfg,
                                            run["stream"], off.last_ts)
    return run


# ---------------------------------------------------------------------------
# the system against the reference
# ---------------------------------------------------------------------------
def test_every_pair_reaches_the_sink_once_with_the_references_value(q20):
    c, exp = q20["cols"], q20["expected"]
    counts = compare_results(exp, c["row"], c["block"], c["joined"],
                             c["valid"])
    assert counts["mismatches"] == 0 and q20["eos"] == 1
    assert counts["delivered"] == counts["expected"] == int(
        exp["count"].sum()) > 3_000
    assert exp["count"].shape == (512, BLOCKS)
    assert c["valid"].all() and c["joined"].dtype == np.int32
    # row for row: the bid's place, its value
    assert (exp["count"][c["row"], c["block"]] == 1).all()
    assert (exp["value"][c["row"], c["block"]] == c["joined"]).all()
    assert len(set(zip(c["row"].tolist(), c["block"].tolist()))) == len(
        c["row"])


def test_a_delivered_row_carries_its_bid_and_its_auction(q20):
    """Every numeric column ``q20.sql`` selects, the auction's beside the
    bid's, equal to the events the stream held."""
    c, off = q20["cols"], q20["offered"]
    events = {k: np.concatenate([b[k] for b, _ in off.blocks()])
              for k in q20["stream"]["pool"][0]}
    bid = c["seq"]
    assert (events["event_type"][bid] == BID).all()
    for col in ("auction_lo", "auction_hi", "bidder_lo", "bidder_hi",
                "price_lo", "price_hi"):
        assert (c[col] == events[col][bid]).all(), col
    # the auction of each delivered row, by its id: ids follow the event
    at = {int(a): i for i, a in enumerate(events["auction_lo"])
          if events["event_type"][i] == AUCTION}
    auc = np.array([at[int(a)] for a in c["auction_lo"]])
    for mine, theirs in (("id_hi", "auction_hi"), ("seller_lo", "bidder_lo"),
                         ("seller_hi", "bidder_hi"),
                         ("initial_lo", "price_lo"),
                         ("reserve_lo", "reserve_lo"),
                         ("reserve_hi", "reserve_hi"),
                         ("expires_lo", "expires_lo"),
                         ("expires_hi", "expires_hi"),
                         ("category_lo", "category_lo")):
        assert (c[mine] == events[theirs][auc]).all(), mine
    assert (c["category_lo"] == 10).all() and (c["category_hi"] == 0).all()
    assert (c["block"] == bid // 512).all() and (c["row"] == bid % 512).all()


def test_no_event_is_late_or_lost_and_the_archives_do_not_grow(q20):
    st, exp = q20["stats"], q20["expected"]
    n = BLOCKS * 512
    assert st["kind"]["Inputs_received"] == n
    bids = sum(int((b["event_type"] == BID).sum())
               for b, _ in q20["offered"].blocks())
    assert st["bid"]["Inputs_received"] == bids
    assert st["cat10"]["Inputs_received"] == n - bids
    for op in DEVICE:
        assert st[op]["Late_records"] == 0 == st[op]["Late_dropped"], op
    join = st["join"]
    assert join["Join_probe_rows_b"] == bids
    assert join["Join_probe_rows_a"] == st["auction"]["Inputs_received"] \
        == n - bids - st["cat10"]["Inputs_ignored"]
    assert join["Join_pairs"] == int(exp["count"].sum()) \
        == st["pack"]["Inputs_received"]
    assert join["Join_late_probes"] == 0 == join["Join_archive_growths"]
    assert join["Join_purged_rows"] > 0.8 * bids
    assert join["Join_host_total_usec"] > 0
    # the split read one routing column back, four bytes an event
    assert st["kind"]["Device_bytes_D2H"] == 4 * n


def test_fifty_six_payload_bytes_an_event_cross(q20):
    src, n = q20["stats"]["src"], BLOCKS * 512
    assert src["Device_bytes_H2D"] == 56 * n
    assert src["Stage_h2d_puts"] == src["Stage_batches"] == BLOCKS
    block = q20["stream"]["pool"][0]
    assert sum(v.dtype.itemsize for v in block.values()) == 56
    assert all(v.dtype == np.int32 for v in block.values())
    assert "56 payload bytes" in q20["cell"].cfg["columns"]


def test_a_block_wider_than_the_interval_as_the_cell_has():
    """4,096-row blocks (410 ms) against the 225 ms interval: most pairs
    inside one block, as in the cell; the join's step sees one batch of
    bids and one of auctions a block."""
    run = run_benchmark_config("q20.saturated", 12, 11, batch_rows=4096)
    cell, off, c = run["cell"], run["offered"], run["cols"]
    exp = cell.module.reference(off.blocks(), cell.cfg, run["stream"],
                                off.last_ts)
    counts = compare_results(exp, c["row"], c["block"], c["joined"],
                             c["valid"])
    assert counts["mismatches"] == 0 and counts["expected"] > 5_000
    b_seq, a_seq, _ = cell.module.pairs_of(
        (b for b, _ in off.blocks()), cell.cfg)
    assert 0.5 < (b_seq // 4096 == a_seq // 4096).mean() < 1.0


# ---------------------------------------------------------------------------
# the reference and the generator, in plain Python
# ---------------------------------------------------------------------------
def test_the_reference_equals_a_dictionary_join(q20):
    cell, exp = q20["cell"], q20["expected"]
    auctions, seen = {}, 0
    blocks = [b for b, _ in q20["offered"].blocks()]
    for b in blocks:
        for kind, a, cat, seller, reserve in zip(
                b["event_type"].tolist(), b["auction_lo"].tolist(),
                b["category_lo"].tolist(), b["bidder_lo"].tolist(),
                b["reserve_lo"].tolist()):
            if kind == AUCTION and cat == 10:
                auctions[a] = (seller, reserve)
    for i, b in enumerate(blocks):
        for r, (kind, a, price) in enumerate(zip(
                b["event_type"].tolist(), b["auction_lo"].tolist(),
                b["price_lo"].tolist())):
            hit = kind == BID and a in auctions
            assert exp["count"][r, i] == hit
            if hit:
                seller, reserve = auctions[a]
                assert exp["value"][r, i] == (
                    (price & 4095) << 19 | (seller & 255) << 11
                    | (reserve & 255) << 3)
                seen += 1
    assert seen == exp["count"].sum() > 3_000


def test_the_interval_join_is_the_regular_join_on_this_generator():
    """Every pair of the regular join lies inside the bounds (16,384
    events back at most 172 ms, ahead at most 19.7 ms), so the interval
    join loses none; and ``make_stream`` refuses bounds that would."""
    cell = Cell("q20.saturated", rehearse=True)
    stream = cell.module.make_stream(5, cell.cfg, cell.traffic)
    lo, hi = stream["pair_dt_us"]
    assert -25_000 < lo < -15_000 and 150_000 < hi < 200_000
    assert stream["head_pairs"] > 5_000
    pool = stream["pool"]
    b_seq, a_seq, _ = cell.module.pairs_of(
        (pool[i] for i in range(60)), cell.cfg)
    first = (b_seq < a_seq).mean()          # the bid precedes its auction
    assert 0.02 < first < 0.09
    for bounds, match in (({"lower_us": 10_000}, "outside"),
                          ({"upper_us": 150_000}, "outside")):
        cfg = dict(cell.cfg, join=dict(cell.cfg["join"], **bounds))
        with pytest.raises(ValueError, match=match):
            cell.module.make_stream(5, cfg, cell.traffic)
    with pytest.raises(ValueError, match="first_event_rate"):
        cell.module.make_stream(5, cell.cfg,
                                dict(cell.traffic, nominal_rate=20_000))


def test_the_auctions_own_fields_follow_the_recalled_formulas():
    cell = Cell("q20.saturated", rehearse=True)
    pool = cell.module.make_stream(9, cell.cfg, cell.traffic)["pool"]
    blocks = [pool[i] for i in range(50)]
    cat = {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}
    kind = cat["event_type"]
    auc = kind == AUCTION
    word = lambda k: (cat[k + "_hi"].astype(np.int64) << 32) | cat[  # noqa
        k + "_lo"].view(np.uint32)
    category = word("category")
    assert set(category[auc].tolist()) == {10, 11, 12, 13, 14}
    assert 0.15 < (category[auc] == 10).mean() < 0.25
    assert (word("reserve")[auc] >= word("price")[auc] + 100).all()
    ms = cat["seq"].astype(np.int64) // 10          # 10,000 events/s
    length = word("expires")[auc] - ms[auc]
    assert length.min() >= 1 and length.max() <= 2 * 166 + 1
    for k in ("category", "reserve", "expires"):
        assert (word(k)[~auc] == 0).all(), k
    assert (cat["seq"] == np.arange(50 * 512)).all()


@pytest.mark.parametrize("at", ["head", "past_head", "far"])
def test_a_block_past_one_cycle_is_the_generator_with_the_new_columns(at):
    """Block ``i`` is ``q5``'s generator at events ``[rows i, rows (i +
    1))`` and the Auction's own fields of the draws of block ``i %
    cycle``: category, reserve and the drawn length repeat with the
    cycle, ``expires`` and ``seq`` move on with the event number."""
    cell = Cell("q20.saturated", rehearse=True)
    pool = cell.module.make_stream(11, cell.cfg, cell.traffic)["pool"]
    rows, cycle = cell.cfg["batch_rows"], int(cell.traffic["pool_blocks"])
    i = {"head": 2 * cycle + 7,
         "past_head": -(-pool.inner.head // cycle) * cycle + 7,
         "far": 4_000 * cycle + 7}[at]
    assert (i < pool.inner.head) == (at == "head")
    block, base = pool[i], pool[7]
    direct = cell.module.q5.generate(i * rows, pool.inner.draws[7],
                                     cell.cfg["generator"])
    assert all((block[k] == direct[k]).all() for k in direct)
    for k in ("category_lo", "category_hi", "reserve_hi"):
        assert (block[k] == base[k]).all(), k
    auc = block["event_type"] == AUCTION
    # the same initial bid and the same second price: the same reserve
    assert (block["reserve_lo"] == base["reserve_lo"]).all()
    moved_ms = (i - 7) * rows // 10
    assert (block["expires_lo"].astype(np.int64)[auc]
            - base["expires_lo"][auc] == moved_ms % (1 << 32)).all() \
        or at == "far"
    assert (block["seq"] == i * rows + np.arange(rows)).all()


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "configs", "q20.py")
    src = open(path).read()
    tree = ast.parse(src)
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = {a.name if isinstance(n, ast.Import) else n.module
             for n in top for a in n.names}
    # the harness's loader brings q5.py (itself numpy alone) beside it
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
    pairs = next(f for f in tree.body if isinstance(f, ast.FunctionDef)
                 and f.name == "pairs_of")
    for f in (ref, pairs):
        text = ast.get_source_segment(src, f)
        assert "join" not in text.replace("joined", "").replace(
            "regular join", "")


def test_the_graph_is_the_public_builders_alone():
    cell = Cell("q20.saturated", rehearse=True)
    stream = cell.module.make_stream(3, cell.cfg, cell.traffic)
    graph, roles = cell.module.build_graph(lambda s: None, lambda c, t: None,
                                           cell.cfg, stream)
    assert roles["device"] == list(DEVICE)
    assert (roles["first"], roles["window"], roles["exit"]) == (
        "kind", "join", "pack")
    from windflow_tpu.tpu import Interval_Join_TPU
    join = next(op for op in graph._ops if op.name == "join")
    assert isinstance(join, Interval_Join_TPU)
    assert (join.lower_bound, join.upper_bound) == (25_000, 200_000)
    assert join.key_field == "auction_lo" and join.parallelism == 1


def test_the_files_state_the_deployment():
    cell = Cell("q20.saturated")
    cfg, t = cell.cfg, cell.traffic
    q5 = Cell("q5.saturated").cfg
    assert cfg["generator"] == q5["generator"]
    assert cfg["auction"] == {"first_category": 10, "categories": 5}
    assert (cfg["join"]["lower_us"], cfg["join"]["upper_us"]) == (
        25_000, 200_000)
    assert cfg["reduced"] == ["strings"]
    assert {"generator", "auction", "join"} <= set(cfg["assumed"])
    assert "recalled, not read" in cfg["source_note"]
    assert "q20.sql" in cfg["source"] and "q20.sql" in cfg["origin"]
    assert "A.category = 10" in cfg["statement"]
    assert set(cfg["limits"].values()) == {0} and len(cfg["limits"]) == 3
    assert set(cfg["guarantees"]) == {"delivery", "results", "order"}
    assert (cfg["batch_rows"], cfg["channel_capacity"],
            cfg["parallelism"]) == (16_384, 16, 1)
    assert cfg["result"] == {"key": "row", "wid": "block",
                             "value": "joined", "valid": "valid"}
    assert t["nominal_rate"] == cfg["generator"]["first_event_rate"]
    assert t["pool_blocks"] * cfg["batch_rows"] == 2_048_000
    assert t["warmup"] == {"blocks": 16, "block_gap_us": 0}
    assert cell.module.windows_per_event(cfg) == 1 and cell.chips == 1
    names = {m["name"] for m, _ in cell.metrics("per_layer")}
    mine = {n for n in names if n.endswith(".q20")}
    assert mine == {
        "join_pairs_per_event.q20", "join_probe_rows_per_batch.q20",
        "join_output_batches_per_batch.q20", "join_archive_rows.q20",
        "join_archive_growths.q20", "join_us_per_batch.q20",
        "split_d2h_bytes_per_event.q20", "join_step_device_share.q20",
        "join_step_roofline.q20"}
    # the `.sat` metrics with no `workloads` list come with the cell
    assert len(names - mine) == 20
    assert all(n.endswith(".sat") for n in names - mine)
    assert [m["name"] for m, _ in cell.metrics("end_to_end")] == [
        "events_per_s", "setup_s"]


def test_the_least_bytes_of_a_join_step_are_counted_from_the_rows():
    from harness.cell import load_module
    roof = load_module(os.path.join(BENCH, "metrics",
                                    "join_step_roofline.py"))
    # 100 A rows of 12 columns and 1,000 B rows of 7, each read and
    # archived once with its time word; 3,000 live rows' key and time
    # scanned; 50 pairs of 18 columns written with theirs
    assert roof.join_step_bytes(100, 1_000, 3_000, 50, 100, 1_000,
                                12, 7, 18) == (
        2 * 100 * 13 * 4 + 2 * 1_000 * 8 * 4 + 3_000 * 8 + 50 * 19 * 4)
