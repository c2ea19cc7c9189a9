"""q20: NEXmark Query 20, "expand bid with auction" (github.com/nexmark/
nexmark, q20.sql: every bid joined with its auction where the auction's
category is 10) on the device plane: a split by event kind, the bids on
one branch, the auctions of category 10 on the other, a merge, and a
keyed interval join (``Interval_Join_TPU``) over both. The stream is
``q5.py``'s generator (the Beam NEXmark generator as recalled) with the
Auction's own fields drawn here. Sizes, every recalled constant and the
departures are in ``q20.json``. ``reference`` is the REGULAR join in plain
numpy: it imports nothing of the program and never reads the join's
bounds."""

from __future__ import annotations

import os

import numpy as np

from harness.cell import load_module

q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "q5.py"))
PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
FIRST_CATEGORY = 10
# the fields of ``joined``: price & 4095 | seller & 255 | reserve & 255 |
# category - 10
SELLER_BITS, RESERVE_BITS, CATEGORY_BITS = 8, 8, 3
OWN_DRAWS = ("category", "reserve", "expires")


def joined(price, seller, reserve, category):
    """The compared value: one non-negative int32 that only the right
    pair gives, from the low words of the bid's price and of its
    auction's seller, reserve and category. The same expression runs in
    the device's ``pack`` and in the reference."""
    return (((price & 4095) << (SELLER_BITS + RESERVE_BITS + CATEGORY_BITS))
            | ((seller & 255) << (RESERVE_BITS + CATEGORY_BITS))
            | ((reserve & 255) << CATEGORY_BITS)
            | ((category - FIRST_CATEGORY) & 7))


def auction_fields(draws: dict, cols: dict, cfg: dict) -> dict:
    """The Auction's own fields of a block's events, zero on the rows
    that are no Auction: ``category`` (10 + a draw of 5),
    ``reserve`` (the initial bid, ``q5``'s price words, plus another
    price) and ``length_ms`` (``expires`` less the event's time: 1 + a
    draw of twice the time the auctions in flight take to be made)."""
    g, a = cfg["generator"], cfg["auction"]
    is_a = cols["event_type"] == AUCTION
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    initial = (cols["price_hi"].astype(np.int64) << 32) \
        | cols["price_lo"].view(np.uint32)
    price = np.rint(10.0 ** (draws["reserve"] * 6.0) * 100.0).astype(np.int64)
    horizon_ms = (g["in_flight_auctions"] * denom // g["auction_proportion"]
                  * 1000) // g["first_event_rate"]
    length = 1 + (draws["expires"] * max(2 * horizon_ms, 1)).astype(np.int64)
    return {"category": np.where(is_a, a["first_category"] + (
                draws["category"] * a["categories"]).astype(np.int64), 0),
            "reserve": np.where(is_a, initial + price, 0),
            "length_ms": np.where(is_a, length, 0)}


def columns(cols: dict, own: dict, first: int, cfg: dict) -> dict:
    """One block as it crosses: ``q5``'s seven words, the Auction's own
    six (``expires`` is the event's time in ms plus the drawn length) and
    ``seq``, the event's number."""
    rows = len(cols["event_type"])
    n = first + np.arange(rows, dtype=np.int64)
    ms = n * 1000 // cfg["generator"]["first_event_rate"]
    out = dict(cols)
    for name, v in (("category", own["category"]),
                    ("reserve", own["reserve"]),
                    ("expires", np.where(own["length_ms"] > 0,
                                         ms + own["length_ms"], 0))):
        out[name + "_lo"], out[name + "_hi"] = q5.words(v)
    out["seq"] = n.astype(np.int32)
    return out


class Blocks:
    """``q5``'s pool (block ``i`` is the generator at events ``[rows * i,
    rows * (i + 1))``, its draws those of block ``i % cycle``) with the
    Auction's own fields: of every block of a cycle they are kept
    (category, reserve and the drawn length follow the draws alone);
    ``expires`` and ``seq`` follow the event number."""

    def __init__(self, draws: list, rows: int, cfg: dict):
        self.inner = q5.Blocks(draws, rows, cfg["generator"])
        self.rows, self.cfg = rows, cfg
        self.own = [auction_fields(d, self.inner[j], cfg)
                    for j, d in enumerate(draws)]

    def __len__(self) -> int:
        return len(self.inner)

    def __getitem__(self, i: int) -> dict:
        return columns(self.inner[i], self.own[i % len(self.own)],
                       i * self.rows, self.cfg)


def pairs_of(blocks, cfg: dict):
    """``(bid's event number, its auction's event number, the value)`` of
    every pair of the regular join ``B.auction = A.id AND A.category =
    10`` over ``blocks`` (an iterable of column blocks), by a sorted
    array of the category-10 auction ids of the whole run."""
    a_cols = ("auction_lo", "bidder_lo", "reserve_lo", "seq")
    b_cols = ("auction_lo", "price_lo", "seq")
    a_parts, b_parts = [], []
    for c in blocks:
        a = (c["event_type"] == AUCTION) \
            & (c["category_lo"] == FIRST_CATEGORY) & (c["category_hi"] == 0)
        b = c["event_type"] == BID
        a_parts.append([c[k][a] for k in a_cols])
        b_parts.append([c[k][b] for k in b_cols])
    if not a_parts:
        z = np.zeros(0, np.int64)
        return z, z, z
    ids, seller, reserve, a_seq = map(np.concatenate, zip(*a_parts))
    b_auction, b_price, b_seq = map(np.concatenate, zip(*b_parts))
    order = np.argsort(ids, kind="stable")
    ids = ids[order]
    at = np.searchsorted(ids, b_auction)
    hit = at < len(ids)
    hit[hit] = ids[at[hit]] == b_auction[hit]
    of = order[at[hit]]                 # the bid's auction, as collected
    return (b_seq[hit].astype(np.int64), a_seq[of].astype(np.int64),
            joined(b_price[hit], seller[of], reserve[of],
                   np.int32(FIRST_CATEGORY)))


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """The draws of one cycle (``traffic["pool_blocks"]`` blocks) from
    the seed, ``q5``'s five an event and the Auction's own three, and the
    stream over them. Refuses a stream in which a pair of the regular
    join lies outside the interval join's bounds (reckoned over the
    stream's head and one cycle past it: the generator's pairs repeat
    with the cycle)."""
    rng = np.random.default_rng(seed)
    rows, g = cfg["batch_rows"], cfg["generator"]
    cycle = int(traffic["pool_blocks"])
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    if (cycle * rows) % denom:
        raise ValueError(f"q20: a cycle of {cycle} blocks of {rows} rows is "
                         f"no whole number of {denom}-event epochs")
    if int(traffic["nominal_rate"]) != g["first_event_rate"]:
        raise ValueError("q20: the cell's nominal_rate is not the "
                         "generator's first_event_rate: expires would not "
                         "follow the event's time")
    draws = [{k: rng.random(rows) for k in
              ("hot_a", "auction", "hot_p", "person", "price") + OWN_DRAWS}
             for _ in range(cycle)]
    pool = Blocks(draws, rows, cfg)
    n = pool.inner.head + cycle
    b_seq, a_seq, _ = pairs_of((pool[i] for i in range(n)), cfg)
    us = 10**6 // g["first_event_rate"]
    dt = (b_seq - a_seq) * us          # ts_b - ts_a of every pair
    j = cfg["join"]
    if len(dt) and (dt.min() < -j["lower_us"] or dt.max() > j["upper_us"]):
        raise ValueError(
            f"q20: a bid lies {dt.min()}..{dt.max()} us from its auction, "
            f"outside [-{j['lower_us']}, {j['upper_us']}]: the interval "
            "join would not deliver q20.sql's rows")
    return {"pool": pool, "pair_dt_us": (int(dt.min()), int(dt.max()))
            if len(dt) else (0, 0), "head_pairs": len(dt), "head_blocks": n}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Map_TPU ``kind`` (the branch: bid 0, auction or person
    1) -> split -> {Map_TPU ``bid``: the Bid's own columns} / {Filter_TPU
    ``cat10``: the WHERE, pushed below the join -> Map_TPU ``auction``:
    the Auction's own columns} -> merge (auctions first: input A) ->
    Interval_Join_TPU ``join`` by the auction id's low word -> Map_TPU
    ``pack`` (``joined``, the bid's row and block) -> columnar sink."""
    try:
        from windflow_tpu.tpu import Interval_Join_TPU_Builder
    except ImportError:
        raise SystemExit(
            "q20: this program has no keyed two-input operator on the "
            "device plane (no Interval_Join_TPU_Builder in windflow_tpu."
            "tpu): it can answer Q20 through the per-tuple Interval_Join "
            "only, and cannot serve it")
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder

    rows, j = cfg["batch_rows"], cfg["join"]
    kind = (Map_TPU_Builder(
                lambda f: {**f, "branch": (f["event_type"] != BID)
                           .astype(jnp.int32)})
            .with_name("kind").build())
    bid = (Map_TPU_Builder(
               lambda f: {k: f[k] for k in (
                   "auction_lo", "auction_hi", "bidder_lo", "bidder_hi",
                   "price_lo", "price_hi", "seq")})
           .with_name("bid").build())
    cat10 = (Filter_TPU_Builder(
                 lambda f: (f["event_type"] == AUCTION)
                 & (f["category_lo"] == FIRST_CATEGORY)
                 & (f["category_hi"] == 0))
             .with_name("cat10").build())
    auction = (Map_TPU_Builder(
                   lambda f: {"auction_lo": f["auction_lo"],
                              "id_hi": f["auction_hi"],
                              "seller_lo": f["bidder_lo"],
                              "seller_hi": f["bidder_hi"],
                              "initial_lo": f["price_lo"],
                              "initial_hi": f["price_hi"],
                              **{k: f[k] for k in (
                                  "reserve_lo", "reserve_hi", "expires_lo",
                                  "expires_hi", "category_lo",
                                  "category_hi")}})
               .with_name("auction").build())
    join = (Interval_Join_TPU_Builder(
                lambda a, b: {**{k: v for k, v in a.items()
                                 if k != "auction_lo"}, **b})
            .with_key_by("auction_lo")
            .with_boundaries(j["lower_us"], j["upper_us"]).with_kp_mode()
            .with_parallelism(cfg["parallelism"]).with_name("join").build())
    pack = (Map_TPU_Builder(
                lambda f: {**f, "row": f["seq"] % rows,
                           "block": f["seq"] // rows,
                           "joined": joined(f["price_lo"], f["seller_lo"],
                                            f["reserve_lo"],
                                            f["category_lo"]),
                           "valid": jnp.ones(f["seq"].shape, bool)})
            .with_name("pack").build())
    g = PipeGraph("q20", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    pipe = g.add_source(Source_Builder(source_fn).with_name("src")
                        .with_output_batch_size(rows).build()).add(kind)
    pipe.split("branch", 2)
    bids = pipe.select(0).add(bid)
    auctions = pipe.select(1).add(cat10).add(auction)
    auctions.merge(bids).add(join).add(pack).add_sink(
        Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "kind", "window": "join",
               "exit": "pack",
               "device": ["kind", "bid", "cat10", "auction", "join", "pack"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block whose loss a block's own results show: the bids
    whose auction is of category 10 and in the same block (most pairs;
    which other bids find an auction only the whole stream says)."""
    b_seq, _, _ = pairs_of([cols], cfg)
    mask = np.zeros(len(cols["seq"]), bool)
    mask[np.searchsorted(cols["seq"], b_seq)] = True
    return mask


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """The regular join over every offered block. Tables of shape (rows
    of a block, blocks): cell (r, b) is the bid at row ``r`` of block
    ``b`` (its ``seq``), ``count`` 1 where its auction is of category 10,
    ``value`` then ``joined``."""
    rows = cfg["batch_rows"]
    cols = [c for c, _ in blocks]
    b_seq, _, value = pairs_of(cols, cfg)
    n_blocks = max([int(c["seq"][-1]) // rows + 1 for c in cols if
                    len(c["seq"])], default=0)
    out = {"count": np.zeros((rows, n_blocks), np.int8),
           "value": np.zeros((rows, n_blocks), np.int32)}
    out["count"][b_seq % rows, b_seq // rows] = 1
    out["value"][b_seq % rows, b_seq // rows] = value
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open: a join delivers a pair
    with its later member, whatever the watermark, so every pair of the
    blocks pushed so far."""
    return int(table["count"].sum())


def windows_per_event(cfg: dict) -> int:
    return 1
