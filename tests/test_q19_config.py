"""The benchmark's ``q19`` configuration (NEXmark Query 19, the ten
highest bids of every auction: ``benchmark/configs/q19.py``) at a small
size on the CPU backend, through ``PipeGraph`` and the public builders:
the cell's generator, 512-row blocks (the rehearsal sizes of
``benchmark/workloads/q19.saturated.json``). The system is held to the
configuration's plain numpy ``reference``, and the reference to a
per-bid model in plain Python."""

import ast
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import Cell, load_module  # noqa: E402
from harness.windows import compare_results, table_rows  # noqa: E402

from common import run_benchmark_config  # noqa: E402

BLOCKS = 48
SEEDS = [2_147_483_659, 7]
BID = 2
DEVICE = ("bids", "narrow", "top10", "ranked", "pack")


@pytest.fixture(scope="module", params=SEEDS)
def q19(request):
    run = run_benchmark_config("q19.saturated", BLOCKS, request.param)
    cell, off = run["cell"], run["offered"]
    run["expected"] = cell.module.reference(off.blocks(), cell.cfg,
                                            run["stream"], off.last_ts)
    return run


def plain_top_ten(blocks, top=10):
    """Per bid, in plain Python: its rank among its auction's bids so far
    (1 + those priced at least as high) and the seq of the tenth of them
    in (price desc, seq asc) order where it ranks and ten were there;
    and every auction's final top ten as (price, seq) pairs."""
    bids, rows = {}, {}
    for b in blocks:
        for kind, a, price, seq in zip(
                b["event_type"].tolist(), b["auction_lo"].tolist(),
                b["price_lo"].tolist(), b["seq"].tolist()):
            if kind != BID:
                continue
            earlier = bids.setdefault(a, [])
            rank = 1 + sum(1 for p, _ in earlier if p >= price)
            if rank <= top:
                held = sorted(earlier, key=lambda x: (-x[0], x[1]))
                rows[seq] = (rank, held[top - 1][1]
                             if len(held) >= top else -1)
            earlier.append((price, seq))
    final = {a: sorted(v, key=lambda x: (-x[0], x[1]))[:top]
             for a, v in bids.items()}
    return rows, final


# ---------------------------------------------------------------------------
# the system against the reference
# ---------------------------------------------------------------------------
def test_every_ranking_bid_reaches_the_sink_once_with_the_references_value(
        q19):
    c, exp = q19["cols"], q19["expected"]
    counts = compare_results(exp, c["row"], c["block"], c["ranked"],
                             c["valid"])
    assert counts["mismatches"] == 0 and q19["eos"] == 1
    assert counts["delivered"] == counts["expected"] == int(
        exp["count"].sum()) > 5_000
    assert exp["count"].shape == (512, BLOCKS)
    assert c["valid"].all() and c["ranked"].dtype == np.int32
    assert (exp["count"][c["row"], c["block"]] == 1).all()
    assert (exp["value"][c["row"], c["block"]] == c["ranked"]).all()
    assert len(set(c["seq"].tolist())) == len(c["seq"])


def test_a_delivered_row_carries_its_bid_its_rank_and_what_it_evicted(q19):
    c, off = q19["cols"], q19["offered"]
    events = {k: np.concatenate([b[k] for b, _ in off.blocks()])
              for k in q19["stream"]["pool"][0]}
    bid = c["seq"]
    assert (events["event_type"][bid] == BID).all()
    for col in ("auction_lo", "bidder_lo", "price_lo"):
        assert (c[col] == events[col][bid]).all(), col
    assert 1 <= c["rank"].min() and c["rank"].max() == 10
    ev = c["evicted"]
    gone = ev >= 0
    # an evicted bid is an earlier bid of the same auction, priced at
    # most as high
    assert gone.any() and (ev[gone] < bid[gone]).all()
    assert (events["auction_lo"][ev[gone]] == c["auction_lo"][gone]).all()
    assert (events["price_lo"][ev[gone]] <= c["price_lo"][gone]).all()
    assert (c["ranked"] >> 27 == c["rank"]).all()
    assert (c["block"] == bid // 512).all() and (c["row"] == bid % 512).all()


def test_the_rows_rebuild_every_auctions_final_top_ten(q19):
    """The update stream follows from the rows: each row enters its
    auction's list at its rank and pushes the tenth out; replayed in
    arrival order they leave every auction's final ten highest bids."""
    c = q19["cols"]
    blocks = [b for b, _ in q19["offered"].blocks()]
    _, final = plain_top_ten(blocks)
    price_of = dict(zip(c["seq"].tolist(), c["price_lo"].tolist()))
    lists = {}
    for i in np.argsort(c["seq"], kind="stable"):
        a, s, r = (int(c[k][i]) for k in ("auction_lo", "seq", "rank"))
        held = lists.setdefault(a, [])
        if int(c["evicted"][i]) >= 0:
            assert len(held) == 10 and held[-1] == int(c["evicted"][i])
        held.insert(r - 1, s)
        del held[10:]
    assert lists == {a: [s for _, s in v] for a, v in final.items()}
    assert all(price_of[s] == p for v in final.values() for p, s in v)


def test_no_event_is_lost_and_the_table_does_not_grow(q19):
    st, exp = q19["stats"], q19["expected"]
    n = BLOCKS * 512
    blocks = [b for b, _ in q19["offered"].blocks()]
    bids = sum(int((b["event_type"] == BID).sum()) for b in blocks)
    auctions = len(set(np.concatenate(
        [b["auction_lo"][b["event_type"] == BID] for b in blocks]).tolist()))
    chain = st["bids∘narrow"]
    assert chain["Inputs_received"] == n and chain["Outputs_sent"] == bids
    top = st["top10"]
    assert top["Inputs_received"] == bids == top["Scan_rows"]
    assert top["Keys_admitted"] == auctions == top["Key_slots_live"]
    assert top["Key_capacity_growths"] == 0
    assert top["Scan_programs"] == top["Dispatch_batches"] >= BLOCKS
    assert top["Scan_cells"] >= top["Scan_rows"]
    assert top["Scan_depth"] >= top["Scan_programs"]
    assert top["Scan_keys"] >= auctions
    assert top["Scan_host_total_usec"] > 0
    ranked = st["ranked∘pack"]
    assert ranked["Outputs_sent"] == int(exp["count"].sum())
    for op in ("bids∘narrow", "top10", "ranked∘pack"):
        assert st[op]["Late_records"] == 0 == st[op]["Late_dropped"], op
    assert 0.45 < ranked["Outputs_sent"] / bids < 0.75


def test_thirty_two_payload_bytes_an_event_cross(q19):
    src, n = q19["stats"]["src"], BLOCKS * 512
    assert src["Device_bytes_H2D"] == 32 * n
    assert src["Stage_h2d_puts"] == src["Stage_batches"] == BLOCKS
    block = q19["stream"]["pool"][0]
    assert all(v.dtype == np.int32 for v in block.values())
    assert sum(v.dtype.itemsize for v in block.values()) == 32
    assert "32 payload bytes" in q19["cell"].cfg["columns"]


def test_the_control_withholds_a_ranking_bid_and_reads_a_mismatch(q19):
    cell, off, exp = q19["cell"], q19["offered"], q19["expected"]
    blocks = list(off.blocks())
    rng = np.random.default_rng(5)
    for victim in rng.choice(len(blocks), 4, replace=False):
        cols, ts = blocks[victim]
        mask = cell.module.counted_mask(cols, cell.cfg)
        assert mask.any() and (cols["event_type"][mask] == BID).all()
        # every counted bid ranks, in the block's own results
        assert (exp["count"][cols["seq"][mask] % 512,
                             cols["seq"][mask] // 512] == 1).all()
        rows = np.nonzero(mask)[0]
        keep = np.ones(len(ts), bool)
        keep[rows[int(rng.integers(len(rows)))]] = False
        bad = [b if i != victim else
               ({k: v[keep] for k, v in cols.items()}, ts[keep])
               for i, b in enumerate(blocks)]
        k, w, v = table_rows(cell.module.reference(
            iter(bad), cell.cfg, q19["stream"], off.last_ts))
        assert compare_results(exp, k, w, v, np.ones(len(k), bool))[
            "mismatches"] >= 1


# ---------------------------------------------------------------------------
# the reference and the stream, in plain Python
# ---------------------------------------------------------------------------
def test_the_reference_equals_a_per_bid_model(q19):
    exp = q19["expected"]
    blocks = [b for b, _ in q19["offered"].blocks()]
    rows, _ = plain_top_ten(blocks)
    mod = q19["cell"].module
    seq = np.fromiter(rows, np.int64)
    rank = np.array([r for r, _ in rows.values()], np.int32)
    gone = np.array([e for _, e in rows.values()], np.int32)
    assert exp["count"].sum() == len(rows)
    assert (exp["count"][seq % 512, seq // 512] == 1).all()
    assert (exp["value"][seq % 512, seq // 512]
            == mod.ranked(rank, seq.astype(np.int32), gone)).all()


def test_the_reference_ranks_alike_in_any_chunking():
    """``TopBook`` carries every auction across the chunks it is given,
    so ranking the blocks one at a time or all at once is the same."""
    cell = Cell("q19.saturated", rehearse=True)
    mod = cell.module
    pool = mod.make_stream(13, cell.cfg, cell.traffic)["pool"]
    bids = [mod.bids_of(pool[i]) for i in range(30)]
    whole = mod.TopBook(10).offer(*(np.concatenate(x) for x in zip(*bids)))
    book = mod.TopBook(10)
    parts = [book.offer(*b) for b in bids]
    for got, want in zip((np.concatenate(x) for x in zip(*parts)), whole):
        assert (got == want).all()


def test_make_stream_refuses_what_ranked_cannot_hold(monkeypatch):
    cell = Cell("q19.saturated", rehearse=True)
    mod = cell.module
    stream = mod.make_stream(5, cell.cfg, cell.traffic)
    assert 0 < stream["bid_span"] < 5_000       # events between two bids
    monkeypatch.setattr(mod, "DIST_BITS", 8)
    with pytest.raises(ValueError, match="events apart"):
        mod.make_stream(5, cell.cfg, cell.traffic)
    with pytest.raises(ValueError, match="whole number"):
        mod.make_stream(5, cell.cfg, dict(cell.traffic, pool_blocks=7))


def test_a_program_without_with_key_capacity_is_refused(monkeypatch):
    from windflow_tpu.tpu import builders_tpu
    cell = Cell("q19.saturated", rehearse=True)
    stream = cell.module.make_stream(3, cell.cfg, cell.traffic)
    monkeypatch.delattr(builders_tpu._KeyCapacityMixin, "with_key_capacity")
    with pytest.raises(SystemExit, match="no with_key_capacity"):
        cell.module.build_graph(lambda s: None, lambda c, t: None,
                                cell.cfg, stream)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "configs", "q19.py")
    src = open(path).read()
    tree = ast.parse(src)
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = {a.name if isinstance(n, ast.Import) else n.module
             for n in top for a in n.names}
    assert names == {"__future__", "os", "numpy", "harness.cell"}
    build = next(f for f in tree.body if isinstance(f, ast.FunctionDef)
                 and f.name == "build_graph")
    inside = {n.module.split(".")[0] for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) and n not in top}
    assert inside == {"windflow_tpu"} == {
        n.module.split(".")[0] for n in ast.walk(build)
        if isinstance(n, ast.ImportFrom)}


def test_the_graph_is_the_public_builders_alone():
    cell = Cell("q19.saturated", rehearse=True)
    stream = cell.module.make_stream(3, cell.cfg, cell.traffic)
    graph, roles = cell.module.build_graph(lambda s: None, lambda c, t: None,
                                           cell.cfg, stream)
    assert roles["device"] == list(DEVICE)
    assert (roles["first"], roles["window"], roles["exit"]) == (
        "bids", "top10", "pack")
    from windflow_tpu.tpu import Map_TPU
    top10 = next(op for op in graph._ops if op.name == "top10")
    assert isinstance(top10, Map_TPU) and top10.key_field == "auction_lo"
    assert {k: (v.shape, v.dtype) for k, v in top10.state_init.items()} \
        == {"price": ((10,), np.int32), "seq": ((10,), np.int32)}
    assert top10.key_capacity == cell.cfg["key_capacity"]


def test_the_files_state_the_deployment():
    cell = Cell("q19.saturated")
    cfg, t = cell.cfg, cell.traffic
    assert cfg["generator"] == Cell("q5.saturated").cfg["generator"]
    assert (cfg["top"], cfg["key_capacity"]) == (10, 8_388_608)
    assert cfg["reduced"] == ["strings"]
    assert {"generator", "key_capacity", "batch_rows"} <= set(
        cfg["assumed"])
    assert "recalled, not read" in cfg["source_note"]
    assert "q19.sql" in cfg["source"] and "q19.sql" in cfg["origin"]
    assert "ROW_NUMBER()" in cfg["statement"]
    assert set(cfg["limits"].values()) == {0} and len(cfg["limits"]) == 3
    assert len(cfg["departures"]) == 6
    assert (cfg["batch_rows"], cfg["channel_capacity"],
            cfg["parallelism"]) == (16_384, 16, 1)
    assert cfg["result"] == {"key": "row", "wid": "block",
                             "value": "ranked", "valid": "valid"}
    assert t["pool_blocks"] * cfg["batch_rows"] == 2_048_000
    assert t["warmup"] == {"blocks": 16, "block_gap_us": 0}
    assert cell.module.windows_per_event(cfg) == 1 and cell.chips == 1
    names = {m["name"] for m, _ in cell.metrics("per_layer")}
    mine = {n for n in names if n.endswith(".q19")}
    assert mine == {
        "grid_fill_share.q19", "grid_depth_per_program.q19",
        "grid_host_us_per_batch.q19", "key_capacity_growths.q19",
        "key_slots_live.q19", "ranked_share.q19",
        "grid_scan_device_share.q19", "grid_scan_roofline.q19",
        "batch_admit_share.q19"}
    assert all(n.endswith(".sat") for n in names - mine)
    assert [m["name"] for m, _ in cell.metrics("end_to_end")] == [
        "events_per_s", "setup_s"]


def test_the_least_bytes_of_a_grid_scan_are_counted_from_the_rows():
    roof = load_module(os.path.join(BENCH, "metrics",
                                    "grid_scan_roofline.py"))
    # 15,000 rows of 4 columns in and 6 out, 1,000 keys' 80-byte state
    # read and written once
    assert roof.grid_scan_bytes(15_000, 1_000, 4, 6, 80) == (
        15_000 * 10 * 4 + 1_000 * 160)
