"""q7: NEXmark Query 7, "highest bid" (github.com/nexmark/nexmark, q7.sql:
the bids whose price is the highest of a 10 s window, each against the
window's end) on the device plane: a tumbling max over the bids
(``Ffat_Windows_TPU``) feeding a keyed interval join of the bids with it
(``Interval_Join_TPU``), every bid sent to both by one multicast split.
The stream is ``q5.py``'s generator (the Beam NEXmark generator as
recalled) with the price drawn anew for every block, so that no price
draw recurs inside the join's span. Sizes, every recalled constant and
the departures are in ``q7.json``. ``reference`` is the query in plain
numpy, by blocks: it imports nothing of the program."""

from __future__ import annotations

import os

import numpy as np

from harness.cell import load_module

q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "q5.py"))
PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
# the fields of ``top``: the price (at most 10^8 < 2^27 by the generator's
# formula) | (the window's id + its bids) & 15
LOW_BITS = 4
# the split's mask: every bid to the window (bit 0) and to the join (bit 1)
BOTH = 3


def top(wid, price, n):
    """The compared value: one non-negative int32 that only the right
    pair gives, from the bid's price (the window's maximum), the window's
    id and its number of bids ``n`` (so that a bid lost anywhere in the
    window shows: a maximum forgets the bids under it). The same
    expression runs in the device's ``pack`` and in the reference."""
    return (price << LOW_BITS) | ((wid + n) & ((1 << LOW_BITS) - 1))


def cell_of(seq, wid, rows: int):
    """``(row, block)`` of a delivered pair in the harness's table: the
    bid's place in its block, twice over, the window's parity choosing
    (a bid at exactly a window's end meets that window and the next one,
    whose ids differ by one)."""
    return 2 * (seq % rows) + (wid & 1), seq // rows


def prices(block: int, rows: int, seed: int, cycle: int) -> np.ndarray:
    """The price of each of block ``block``'s events, ``round(10^(6u) *
    100)`` of a draw ``u`` from a counter-based generator keyed by the
    seed and counted from ``block % cycle``: the draws recur every
    ``cycle`` blocks and nowhere else, whatever the run's length."""
    bits = np.random.Philox(key=seed % (1 << 64),
                            counter=[0, block % cycle, 0, 0])
    u = np.random.Generator(bits).random(rows)
    # 10 ** (6 u) as an exponential: a third of the power's time in the
    # source's thread
    return np.rint(np.exp(u * (6.0 * np.log(10.0))) * 100.0).astype(np.int64)


class Blocks:
    """``q5``'s pool (block ``i`` is the generator at events ``[rows * i,
    rows * (i + 1))``, its draws other than the price those of block ``i
    % pool_blocks``) with every event's price drawn for the block itself
    (``prices``), and ``seq``, the event's number."""

    def __init__(self, draws: list, rows: int, cfg: dict, seed: int,
                 price_cycle: int):
        self.inner = q5.Blocks(draws, rows, cfg["generator"])
        self.rows, self.seed, self.cycle = rows, seed, price_cycle

    def __len__(self) -> int:
        return len(self.inner)

    def __getitem__(self, i: int) -> dict:
        cols = dict(self.inner[i])
        price = np.where(cols["event_type"] == PERSON, 0,
                         prices(i, self.rows, self.seed, self.cycle))
        cols["price_lo"], cols["price_hi"] = q5.words(price)
        cols["seq"] = (i * self.rows + np.arange(self.rows)).astype(np.int32)
        return cols


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """The draws of one cycle of ``q5``'s pool (``traffic["pool_blocks"]``
    blocks) from the seed, the prices of every block from the seed and the
    block's number, and the stream over them. Refuses a stream whose
    price draws recur within two windows (``price_pool_blocks`` too few
    for the span the join holds: a window's maximum would recur in it)."""
    rng = np.random.default_rng(seed)
    rows, g = cfg["batch_rows"], cfg["generator"]
    cycle = int(traffic["pool_blocks"])
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    if (cycle * rows) % denom:
        raise ValueError(f"q7: a cycle of {cycle} blocks of {rows} rows is "
                         f"no whole number of {denom}-event epochs")
    if int(traffic["nominal_rate"]) != cfg["event_rate"]:
        raise ValueError("q7: the cell's nominal_rate is not the "
                         "deployment's event_rate")
    span = 2 * cfg["window"]["win_us"] * cfg["event_rate"] // 10**6
    price_cycle = int(traffic["price_pool_blocks"])
    if price_cycle * rows < span:
        raise ValueError(
            f"q7: the price draws recur every {price_cycle * rows} events, "
            f"within two windows ({span} events): a window's maximum would "
            "recur inside the span the join holds")
    draws = [{k: rng.random(rows) for k in
              ("hot_a", "auction", "hot_p", "person", "price")}
             for _ in range(cycle)]
    return {"pool": Blocks(draws, rows, cfg, seed, price_cycle),
            "price_draws_every": price_cycle * rows}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Filter_TPU ``bids`` + Map_TPU ``route`` (chained: the
    split's mask, both branches) -> split by mask -> {Ffat_Windows_TPU
    ``max``: tumbling max of the price over the constant key} / {Map_TPU
    ``bid``: the Bid's seven words} -> merge (the window's rows first:
    input A) -> Interval_Join_TPU ``join`` by the price's low word, the
    bids' archive given its capacity -> Map_TPU ``pack`` (``top``, the
    bid's cell) -> columnar sink."""
    import inspect

    from windflow_tpu.topology.multipipe import MultiPipe
    from windflow_tpu.tpu import Interval_Join_TPU_Builder
    if "mask" not in inspect.signature(MultiPipe.split).parameters \
            or not hasattr(Interval_Join_TPU_Builder,
                           "with_archive_capacity"):
        raise SystemExit(
            "q7: this program has no multicast split on the device plane "
            "(no split(field, n, mask=True)) or no archive capacity for "
            "its device join (no Interval_Join_TPU_Builder."
            "with_archive_capacity): it cannot serve Q7")
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    rows, w, j = cfg["batch_rows"], cfg["window"], cfg["join"]
    bids = (Filter_TPU_Builder(lambda f: f["event_type"] == BID)
            .with_name("bids").build())
    route = (Map_TPU_Builder(
                 lambda f: {**f, "to": jnp.full(f["seq"].shape, BOTH,
                                                jnp.int32)})
             .with_name("route").build())
    # an empty window's row carries valid False: the join takes such a
    # row as not there (it neither probes nor is archived)
    top_price = (Ffat_Windows_TPU_Builder(
                     lambda f: {"price_lo": f["price_lo"],
                                "n": jnp.ones(f["price_lo"].shape,
                                              jnp.int32)},
                     lambda a, b: {"price_lo": jnp.maximum(a["price_lo"],
                                                           b["price_lo"]),
                                   "n": a["n"] + b["n"]})
                 .with_key_by("event_type")
                 .with_tb_windows(w["win_us"], w["win_us"])
                 .with_key_capacity(1)
                 .with_parallelism(cfg["parallelism"]).with_name("max")
                 .build())
    bid = (Map_TPU_Builder(
               lambda f: {k: f[k] for k in (
                   "auction_lo", "auction_hi", "bidder_lo", "bidder_hi",
                   "price_lo", "price_hi", "seq")})
           .with_name("bid").build())
    join = (Interval_Join_TPU_Builder(
                lambda a, b: {"wid": a["wid"], "n": a["n"], **b})
            .with_key_by("price_lo")
            .with_boundaries(j["lower_us"], j["upper_us"]).with_kp_mode()
            .with_archive_capacity(None, cfg["archive"]["b_rows"])
            .with_parallelism(cfg["parallelism"]).with_name("join").build())

    def packed(f):
        row, block = cell_of(f["seq"], f["wid"], rows)
        return {**f, "row": row, "block": block,
                "top": top(f["wid"], f["price_lo"], f["n"]),
                "valid": jnp.ones(f["seq"].shape, bool)}

    pack = Map_TPU_Builder(packed).with_name("pack").build()
    g = PipeGraph("q7", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    pipe = g.add_source(Source_Builder(source_fn).with_name("src")
                        .with_output_batch_size(rows).build()) \
        .add(bids).chain(route)
    pipe.split("to", 2, mask=True)
    windows = pipe.select(0).add(top_price)
    every_bid = pipe.select(1).add(bid)
    windows.merge(every_bid).add(join).add(pack).add_sink(
        Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "bids", "window": "join",
               "exit": "pack",
               "device": ["bids", "route", "max", "bid", "join", "pack"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block whose loss the results show: the bids (each is
    counted in its window's ``n``, which every pair of the window
    carries in ``top``)."""
    return cols["event_type"] == BID


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Q7 over every offered block, one block at a time: per 10 s window
    of event time the highest price of its bids, and every bid of that
    price whose time lies in ``[T - 10 s, T]``, ``T`` the window's end,
    both ends included (a bid at exactly ``T`` meets the closing window
    too). Tables of shape (2 x rows of a block, blocks): cell
    ``cell_of(seq, wid)``, ``count`` 1 where the pair is delivered,
    ``value`` then ``top``. ``windows`` lists ``(wid, its end, pairs,
    bids)`` (the harness does not read it: ``results_due`` does)."""
    rows, win = cfg["batch_rows"], cfg["window"]["win_us"]
    best = {}               # window -> its highest price so far
    bids = {}               # window -> its number of bids
    held = {}               # window -> event numbers of the bids at it
    edge = []               # (window ending at the bid, price, seq)
    n_blocks = 0
    for cols, ts in blocks:
        if len(ts):
            n_blocks = max(n_blocks, int(cols["seq"][-1]) // rows + 1)
        keep = cols["event_type"] == BID
        price = cols["price_lo"][keep].astype(np.int64)
        t, seq = ts[keep], cols["seq"][keep].astype(np.int64)
        wid = t // win
        at_end = t % win == 0
        edge += zip((wid[at_end] - 1).tolist(), price[at_end].tolist(),
                    seq[at_end].tolist())
        for w in np.unique(wid).tolist():
            mine = wid == w
            p = price[mine]
            m = int(p.max())
            bids[w] = bids.get(w, 0) + len(p)
            if m > best.get(w, -1):
                best[w], held[w] = m, []
            if m == best[w]:
                held[w] += seq[mine][p == m].tolist()
    pairs = [(w, s) for w in best for s in held[w]]
    # a bid at exactly T of the window before its own, at that window's
    # highest price (every bid of which came before it)
    pairs += [(w, s) for w, p, s in edge if w in best and p == best[w]]
    out = {"count": np.zeros((2 * rows, n_blocks), np.int8),
           "value": np.zeros((2 * rows, n_blocks), np.int32)}
    if pairs:
        w, s = (np.array(x, np.int64) for x in zip(*pairs))
        r, b = cell_of(s, w, rows)
        out["count"][r, b] = 1
        out["value"][r, b] = top(
            w, np.array([best[x] for x in w.tolist()], np.int64),
            np.array([bids[x] for x in w.tolist()], np.int64))
    out["windows"] = np.array(
        [(x, (x + 1) * win, sum(1 for y, _ in pairs if y == x), bids[x])
         for x in sorted(best)], np.int64).reshape(-1, 4)
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open: the pairs of every
    window that ends at or before the watermark (it has fired, and the
    bids up to its end have reached the join)."""
    w = table["windows"]
    return int(w[w[:, 1] <= wm_us, 2].sum())


def windows_per_event(cfg: dict) -> int:
    return 1
