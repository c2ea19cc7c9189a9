"""q5: NEXmark Query 5, "hot items" (github.com/nexmark/nexmark, q5.sql:
bids per auction over a 10 s window sliding by 2 s, then the auction with
the most bids of each window) on the device plane: two keyed window
operators, the second over the rows the first fires. The stream is the
Beam NEXmark generator as recalled (a function of the event number and
the seed), all three event kinds; auction ids never recur, so the first
operator's key slots turn over for the whole run. Sizes, every recalled
constant and the departures are in ``q5.json``. ``reference`` imports
nothing of the program."""

from __future__ import annotations

import numpy as np

PERSON, AUCTION, BID = 0, 1, 2
# the fields of ``hot_item``: bids & 127 | count | auction & 8191
COUNT_BITS, AUCTION_BITS = 11, 13
ALL = "all"              # the constant key column of the second stage


def hot_item(bids, count, auction):
    """The compared value: one int32 every bid of the window shows in.
    The same expression runs in the device's ``pack`` and in the
    reference."""
    return (((bids & 127) << (COUNT_BITS + AUCTION_BITS))
            | (count << AUCTION_BITS)
            | (auction & ((1 << AUCTION_BITS) - 1)))


def words(values: np.ndarray):
    """``(lo, hi)``: the two 32-bit words of non-negative int64 values,
    as the int32 columns an 8-byte field crosses in."""
    v = np.ascontiguousarray(values, np.int64).view(np.uint64)
    return ((v & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
            (v >> 32).astype(np.uint32).view(np.int32))


def generate(first: int, draws: dict, g: dict, inner: dict = None) -> dict:
    """Events ``[first, first + rows)`` of the generator, with the random
    draws ``draws`` (uniform in [0, 1), one set an event: ``hot_a``,
    ``auction``, ``hot_p``, ``person``, ``price``). Event ``n`` is of
    epoch ``n // 50`` at offset ``n % 50``: offset 0 a Person, 1-3 an
    Auction, 4-49 a Bid. A bid's auction is the hot one of the current
    hundred with probability ``1 - 1 / hot_auction_ratio``, else uniform
    over the auctions in flight and ``auction_id_lead`` ahead; its
    bidder, and an auction's seller, likewise among the people.
    ``inner``, where given, takes what ``Blocks`` keeps of a block."""
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    rows = len(draws["price"])
    n = first + np.arange(rows, dtype=np.int64)
    epoch, offset = n // denom, n % denom
    is_person = offset < g["person_proportion"]
    is_bid = offset >= g["person_proportion"] + g["auction_proportion"]
    kind = np.where(is_person, PERSON, np.where(is_bid, BID, AUCTION))
    # lastBase0AuctionId / lastBase0PersonId of the event number
    a_off = np.where(is_person | is_bid, g["auction_proportion"] - 1,
                     offset - g["person_proportion"])
    last_a = (epoch - is_person) * g["auction_proportion"] + a_off
    last_p = epoch * g["person_proportion"] + np.minimum(
        offset, g["person_proportion"] - 1)
    unit = g["hot_ratio_unit"]

    def among(last, in_flight, lead, u):
        lo = np.maximum(last - in_flight, 0)
        return lo + (u * (last - lo + 1 + lead)).astype(np.int64)

    someone = among(last_p, g["active_people"], g["person_id_lead"],
                    draws["person"])
    hot_a = draws["hot_a"] * g["hot_auction_ratio"] >= 1
    bid_auction = np.where(
        hot_a, last_a // unit * unit,
        among(last_a, g["in_flight_auctions"], g["auction_id_lead"],
              draws["auction"]))
    hot_b = draws["hot_p"] * g["hot_bidders_ratio"] >= 1
    bidder = np.where(hot_b, last_p // unit * unit + 1, someone)
    hot_s = draws["hot_p"] * g["hot_sellers_ratio"] >= 1
    seller = np.where(hot_s, last_p // unit * unit, someone)
    price = np.rint(10.0 ** (draws["price"] * 6.0) * 100.0).astype(np.int64)
    auction = np.where(is_person, 0, g["first_auction_id"] + np.where(
        is_bid, bid_auction, last_a))
    person = g["first_person_id"] + np.where(
        is_bid, bidder, np.where(is_person, last_p, seller))
    a_lo, a_hi = words(auction)
    b_lo, b_hi = words(person)
    p_lo, p_hi = words(np.where(is_person, 0, price))
    if inner is not None:
        hot = {"bids": (is_bid & hot_a, last_a),
               "bidders": (is_bid & hot_b, last_p),
               "sellers": (~is_bid & ~is_person & hot_s, last_p)}
        inner.update(auction=auction, person=person,
                     persons=np.nonzero(is_person)[0],
                     **{k: (np.nonzero(m)[0], last[m])     # rows, their last
                        for k, (m, last) in hot.items()})
    return {"event_type": kind.astype(np.int32),
            "auction_lo": a_lo, "auction_hi": a_hi,
            "bidder_lo": b_lo, "bidder_hi": b_hi,
            "price_lo": p_lo, "price_hi": p_hi}


class Blocks:
    """The stream as the harness's pool: block ``i`` is the generator at
    events ``[rows * i, rows * (i + 1))``, its random draws those of
    block ``i % cycle`` (the draws repeat every ``cycle`` blocks, a whole
    number of epochs; the ids do not: they follow the event number).
    ``len`` is far past any run, so the harness's ``pool[seq %
    len(pool)]`` is ``pool[seq]``.

    The source's thread asks for a block inside the measured window, so
    a block must cost it little (the other configurations' pools are
    lists). Of every block of one cycle (``ref``, the first past the
    stream's head, where the ranges of auctions in flight and of active
    people are still clipped at 0) the id columns are kept with the rows
    of the hot ids: block ``i`` is those columns moved on by whole
    cycles' auctions and persons, the hot ids floored to their hundred
    AFTER the move. A tier-1 test holds it to ``generate`` at the
    block's own events; blocks of the head are ``generate``'s."""

    def __init__(self, draws: list, rows: int, gen: dict):
        self.draws, self.rows, self.gen = draws, rows, gen
        denom = gen["person_proportion"] + gen["auction_proportion"] \
            + gen["bid_proportion"]
        epochs = len(draws) * rows // denom      # of a cycle
        self.per_cycle = (epochs * gen["auction_proportion"],
                          epochs * gen["person_proportion"])
        head = 1 + max(
            -(-gen["in_flight_auctions"] // gen["auction_proportion"]),
            -(-gen["active_people"] // gen["person_proportion"]))
        self.head = -(-head * denom // rows)     # blocks with a clipped range
        self.ref = -(-self.head // len(draws))
        self.kept = []
        for j in range(len(draws)):
            inner = {}
            cols = generate((self.ref * len(draws) + j) * rows, draws[j],
                            gen, inner)
            self.kept.append((cols, inner))

    def __len__(self) -> int:
        return 1 << 40

    def __getitem__(self, i: int) -> dict:
        cycle, j = divmod(i, len(self.draws))
        if i < self.head:
            return generate(i * self.rows, self.draws[j], self.gen)
        cols, k = self.kept[j]
        g, unit = self.gen, self.gen["hot_ratio_unit"]
        on_a, on_p = ((cycle - self.ref) * n for n in self.per_cycle)
        auction = k["auction"] + on_a
        auction[k["persons"]] = 0
        rows, last = k["bids"]
        auction[rows] = (last + on_a) // unit * unit + g["first_auction_id"]
        person = k["person"] + on_p
        for (rows, last), plus in ((k["bidders"], 1), (k["sellers"], 0)):
            person[rows] = (last + on_p) // unit * unit + plus \
                + g["first_person_id"]
        a_lo, a_hi = words(auction)
        b_lo, b_hi = words(person)
        return {**cols, "auction_lo": a_lo, "auction_hi": a_hi,
                "bidder_lo": b_lo, "bidder_hi": b_hi}


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """The draws of one cycle (``traffic["pool_blocks"]`` blocks) from
    the seed, and the stream over them. Refuses a stream that would
    leave ``hot_item``'s fields: an auction with ``2 ** 11`` bids or
    more in all, or a window whose auction ids span ``2 ** 13`` or more
    (two of its auctions would then share their low 13 bits)."""
    rng = np.random.default_rng(seed)
    rows, g, w = cfg["batch_rows"], cfg["generator"], cfg["window"]
    cycle = int(traffic["pool_blocks"])
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    if (cycle * rows) % denom:
        raise ValueError(f"q5: a cycle of {cycle} blocks of {rows} rows is "
                         f"no whole number of {denom}-event epochs")
    draws = [{k: rng.random(rows) for k in
              ("hot_a", "auction", "hot_p", "person", "price")}
             for _ in range(cycle)]
    stream = {"pool": Blocks(draws, rows, g)}
    # ids a window spans: the auctions made while it lasts, those in
    # flight at its start (the hot one is at most a hundred back) and
    # the lead at its end
    per_window = (int(traffic["nominal_rate"]) * w["win_us"]) // 10**6
    span = (-(-per_window // denom) + 1) * g["auction_proportion"] + max(
        g["in_flight_auctions"], g["hot_ratio_unit"]) \
        + g["auction_id_lead"] + 1
    if span >= 1 << AUCTION_BITS:
        raise ValueError(
            f"q5: a window's auction ids span up to {span} >= 2**"
            f"{AUCTION_BITS}: hot_item's auction field would not tell "
            "them apart")
    bids = np.concatenate([c["auction_lo"][c["event_type"] == BID]
                           for c in map(stream["pool"].__getitem__,
                                        range(cycle))])
    most = int(np.bincount(bids - bids.min()).max())   # of the first cycle
    if most >= 1 << COUNT_BITS:
        raise ValueError(
            f"q5: an auction draws {most} bids >= 2**{COUNT_BITS}: "
            "hot_item's count field would not hold them")
    stream.update(most_bids=most, window_id_span=span)
    return stream


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Filter_TPU ``bids`` -> keyed re-shard by the auction ->
    Ffat_Windows_TPU ``win`` (bids per auction, sliding) -> Map_TPU
    ``one`` (the constant key column) -> Ffat_Windows_TPU ``hot`` keyed
    by it (tumbling by the slide over the fired rows' event time: the
    largest count, ties to the lowest id, and the window's bids) ->
    Map_TPU ``pack`` (``hot_item``) -> columnar sink."""
    from windflow_tpu.monitoring.tracing import STAGES
    if "keys" not in STAGES:
        raise SystemExit(
            "q5: this program keeps a window key's slot for ever and keys "
            "a stage after a window by the window's key (no stage 'keys' "
            "in monitoring/tracing.py STAGES): it cannot answer Q5")
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    w = cfg["window"]
    bids = (Filter_TPU_Builder(lambda f: f["event_type"] == BID)
            .with_name("bids").build())
    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"count": jnp.ones(f["auction_lo"].shape,
                                            jnp.int32)},
               lambda a, b: {"count": a["count"] + b["count"]})
           .with_key_by("auction_lo")
           .with_tb_windows(w["win_us"], w["slide_us"])
           .with_key_capacity(cfg["key_capacity"])
           .with_parallelism(cfg["parallelism"]).with_name("win"))
    if cfg.get("num_win_per_batch"):     # else the operator's own sizing
        win = win.with_num_win_per_batch(cfg["num_win_per_batch"])
    one = (Map_TPU_Builder(
               lambda f: {**f, ALL: jnp.zeros(f["count"].shape, jnp.int32)})
           .with_name("one").build())

    def lift(f):
        # a window that fired empty carries whatever the walk left
        count = jnp.where(f["valid"], f["count"], 0)
        return {"count": count, "auction": f["auction_lo"], "bids": count}

    def larger(a, b):
        take = (a["count"] > b["count"]) | (
            (a["count"] == b["count"]) & (a["auction"] < b["auction"]))
        return {"count": jnp.where(take, a["count"], b["count"]),
                "auction": jnp.where(take, a["auction"], b["auction"]),
                "bids": a["bids"] + b["bids"]}

    hot = (Ffat_Windows_TPU_Builder(lift, larger)
           .with_key_by(ALL)
           .with_tb_windows(w["slide_us"], w["slide_us"])
           .with_key_capacity(1)
           .with_parallelism(cfg["parallelism"]).with_name("hot").build())
    pack = (Map_TPU_Builder(
                lambda f: {**f, "hot_item": hot_item(f["bids"], f["count"],
                                                     f["auction"]),
                           "valid": f["valid"] & (f["bids"] > 0)})
            .with_name("pack").build())
    g = PipeGraph("q5", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(cfg["batch_rows"]).build()) \
     .add(bids).add(win.build()).add(one).add(hot).add(pack) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "bids", "window": "win",
               "hot": "hot", "exit": "pack",
               "device": ["bids", "win", "one", "hot", "pack"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block that reach a window: the bids."""
    return cols["event_type"] == BID


def stage_two_wid(w, cfg: dict):
    """The tumbling window of the second stage that holds the row of
    first-stage window ``w``: the row carries the last instant of ``[w *
    slide, w * slide + win)``."""
    win = cfg["window"]
    return w + win["win_us"] // win["slide_us"] - 1


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Per slide-long pane a count of bids by auction id (``np.bincount``
    over the pane's own id range); a window is the sum of its panes,
    aligned by id; the winner the largest count, ties to the lowest id.
    Tables of shape (1, windows of the second stage): ``value`` the
    packed ``hot_item``, ``count`` the window's bids (0: nothing is
    delivered), and ``auction``, ``hot_count``, ``bids`` column by column
    (the harness does not read those: tier-1 does)."""
    w = cfg["window"]
    slide, per = w["slide_us"], w["win_us"] // w["slide_us"]
    panes = {}              # pane -> [lowest id, counts from it on]
    for c, ts in blocks:
        keep = c["event_type"] == BID
        ids, p = c["auction_lo"][keep].astype(np.int64), ts[keep] // slide
        for pane in np.unique(p).tolist():
            mine = ids[p == pane]
            lo = int(mine.min())
            counts = np.bincount(mine - lo)
            if pane in panes:
                lo0, c0 = panes[pane]
                base = min(lo, lo0)
                both = np.zeros(max(lo + len(counts), lo0 + len(c0)) - base,
                                np.int64)
                both[lo - base:lo - base + len(counts)] += counts
                both[lo0 - base:lo0 - base + len(c0)] += c0
                lo, counts = base, both
            panes[pane] = [lo, counts]
    n_win = stage_two_wid(int(last_ts) // slide, cfg) + 1
    out = {k: np.zeros((1, n_win), np.int64)
           for k in ("value", "count", "auction", "hot_count", "bids")}
    for first in range(int(last_ts) // slide + 1):
        held = [panes[p] for p in range(first, first + per) if p in panes]
        if not held:
            continue
        base = min(lo for lo, _ in held)
        total = np.zeros(max(lo + len(c) for lo, c in held) - base, np.int64)
        for lo, c in held:
            total[lo - base:lo - base + len(c)] += c
        best = int(np.argmax(total))         # the first maximum: lowest id
        v = stage_two_wid(first, cfg)
        out["auction"][0, v] = base + best
        out["hot_count"][0, v] = total[best]
        out["bids"][0, v] = out["count"][0, v] = total.sum()
        out["value"][0, v] = hot_item(int(total.sum()), int(total[best]),
                                      base + best)
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open. The first stage has
    fired every window that ends at or before ``wm_us``; a fired batch is
    stamped below the earliest window end among its rows, so what the
    second stage has closed follows from the lowest window of the LAST
    batch the first stage fired: the windows that ended after the
    watermark of the block before it."""
    w = cfg["window"]

    def closed(wm):          # first-stage windows that end at or before wm
        return (wm - w["win_us"]) // w["slide_us"] + 1 if wm >= w["win_us"] \
            else 0

    marks = [int(ts[0]) - 1 for _, ts in blocks if int(ts[0]) - 1 <= wm_us]
    lowest = None
    for before, wm in zip([-1] + marks, marks):
        if closed(wm) > closed(before):
            lowest = closed(before)     # the lowest window of that batch
    if lowest is None:
        return 0
    # stamped ``end(lowest) - 1``: the second stage closes what ends at or
    # before that, the windows before ``lowest``'s own
    return int((table["count"][0, :stage_two_wid(lowest, cfg)] > 0).sum())


def windows_per_event(cfg: dict) -> int:
    w = cfg["window"]
    return max(1, w["win_us"] // w["slide_us"])
