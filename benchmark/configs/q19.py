"""q19: NEXmark Query 19, "auction TOP-10 price" (github.com/nexmark/
nexmark, q19.sql: every bid with its ROW_NUMBER over its auction's bids
by price, descending, kept where that is at most 10) on the keyed
device-state plane: a stateful ``Map_TPU`` holds each auction's ten
highest bids, a ``(10,)`` price vector and a ``(10,)`` sequence vector,
for every auction of the stream. The stream is ``q5.py``'s generator
(the Beam NEXmark generator as recalled) and ``seq``, the event's
number. Sizes, every recalled constant and the departures are in
``q19.json``. ``reference`` is plain numpy: it imports nothing of the
program."""

from __future__ import annotations

import os

import numpy as np

from harness.cell import load_module

q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "q5.py"))
AUCTION, BID = q5.AUCTION, q5.BID
TOP = 10
# the fields of ``ranked``: rank | (seq - evicted) & (2**27 - 1)
DIST_BITS = 27
NONE = -1                # an empty place of the top ten, no bid evicted
CHUNK = 512              # blocks the reference ranks at once


def ranked(rank, seq, evicted):
    """The compared value: one non-negative int32 that only the right
    rank and the right evicted bid give, the distance 0 where nothing was
    evicted. The same expression runs in the device's ``pack`` and in
    the reference."""
    dist = (seq - evicted) * (evicted >= 0)
    return (rank << DIST_BITS) | (dist & ((1 << DIST_BITS) - 1))


class Blocks:
    """``q5``'s pool (block ``i`` is the generator at events ``[rows * i,
    rows * (i + 1))``, its draws those of block ``i % cycle``) with
    ``seq``, the event's number."""

    def __init__(self, draws: list, rows: int, cfg: dict):
        self.inner = q5.Blocks(draws, rows, cfg["generator"])
        self.rows = rows
        self._offsets = np.arange(rows, dtype=np.int64)

    def __len__(self) -> int:
        return len(self.inner)

    def __getitem__(self, i: int) -> dict:
        return {**self.inner[i],
                "seq": (i * self.rows + self._offsets).astype(np.int32)}


def bids_of(cols: dict):
    """``(auction, price, seq)`` of a block's bids, in arrival order."""
    b = cols["event_type"] == BID
    return cols["auction_lo"][b], cols["price_lo"][b], cols["seq"][b]


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """The draws of one cycle (``traffic["pool_blocks"]`` blocks) from
    the seed, and the stream over them. Refuses a stream whose prices
    leave their low word, or in which two bids of one auction lie
    ``2 ** 27`` events apart or more (``ranked``'s distance field),
    reckoned over the stream's head and one cycle past it: an auction
    takes bids only while it is among the last hundred, and that repeats
    with the cycle."""
    rng = np.random.default_rng(seed)
    rows, g = cfg["batch_rows"], cfg["generator"]
    cycle = int(traffic["pool_blocks"])
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    if (cycle * rows) % denom:
        raise ValueError(f"q19: a cycle of {cycle} blocks of {rows} rows is "
                         f"no whole number of {denom}-event epochs")
    draws = [{k: rng.random(rows) for k in
              ("hot_a", "auction", "hot_p", "person", "price")}
             for _ in range(cycle)]
    pool = Blocks(draws, rows, cfg)
    n = pool.inner.head + cycle
    blocks = [pool[i] for i in range(n)]
    if any(((c["price_hi"] != 0) | (c["price_lo"] < 0))[
            c["event_type"] == BID].any() for c in blocks):
        raise ValueError("q19: a price leaves its low word: the key and the "
                         "ranking read price_lo alone")
    auction, _, seq = (np.concatenate(x) for x in zip(*map(bids_of, blocks)))
    order = np.argsort(auction, kind="stable")
    a, s = auction[order], seq[order].astype(np.int64)
    first = np.r_[True, a[1:] != a[:-1]]
    last = np.r_[a[1:] != a[:-1], True]
    span = int((s[last] - s[first]).max()) if len(s) else 0
    if span >= 1 << DIST_BITS:
        raise ValueError(
            f"q19: two bids of one auction lie {span} events apart >= 2**"
            f"{DIST_BITS}: ranked's distance field would not hold them")
    return {"pool": pool, "bid_span": span, "head_blocks": n}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Filter_TPU ``bids`` chained with Map_TPU ``narrow`` (the
    four columns the ranking reads and forwards) -> keyed re-shard by
    the auction -> stateful Map_TPU ``top10`` (per auction the ten
    highest bids; a bid's rank at arrival and the bid it evicted) ->
    Filter_TPU ``ranked`` (rank > 0) chained with Map_TPU ``pack``
    (``ranked``, the bid's row and block) -> columnar sink."""
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder
    if not hasattr(Map_TPU_Builder, "with_key_capacity"):
        raise SystemExit(
            "q19: this program's stateful Map_TPU has no with_key_capacity "
            "(its state table starts at 64 slots and doubles, a scalar a "
            "leaf): it cannot hold ten bids an auction for every auction "
            "of the stream")

    rows = cfg["batch_rows"]
    top = cfg["top"]
    bids = (Filter_TPU_Builder(lambda f: f["event_type"] == BID)
            .with_name("bids").build())
    narrow = (Map_TPU_Builder(
                  lambda f: {k: f[k] for k in (
                      "auction_lo", "price_lo", "bidder_lo", "seq")})
              .with_name("narrow").build())

    def insert(row, held):
        """One bid against its auction's top ten (held price-descending,
        ties seq-ascending, empty places -1): its rank, the bid pushed out
        of the tenth place, the bid in its place."""
        p, s = row["price_lo"], row["seq"]
        price, seq = held["price"], held["seq"]
        rank = 1 + jnp.sum((price >= p) & (seq >= 0)).astype(jnp.int32)
        enters = rank <= top
        at = jnp.arange(top, dtype=jnp.int32)

        def place(old, new):
            down = jnp.concatenate([old[:1], old[:-1]])
            return jnp.where(at < rank - 1, old,
                             jnp.where(at == rank - 1, new, down))

        evicted = jnp.where(enters, seq[top - 1], NONE)
        out = {**row, "rank": jnp.where(enters, rank, 0),
               "evicted": evicted}
        return out, {"price": jnp.where(enters, place(price, p), price),
                     "seq": jnp.where(enters, place(seq, s), seq)}

    top10 = (Map_TPU_Builder(insert)
             .with_key_by("auction_lo")
             .with_state({"price": np.full(top, NONE, np.int32),
                          "seq": np.full(top, NONE, np.int32)})
             .with_key_capacity(cfg["key_capacity"])
             .with_parallelism(cfg["parallelism"]).with_name("top10")
             .build())
    keep = (Filter_TPU_Builder(lambda f: f["rank"] > 0)
            .with_name("ranked").build())
    pack = (Map_TPU_Builder(
                lambda f: {**f, "row": f["seq"] % rows,
                           "block": f["seq"] // rows,
                           "ranked": ranked(f["rank"], f["seq"],
                                            f["evicted"]),
                           "valid": jnp.ones(f["seq"].shape, bool)})
            .with_name("pack").build())
    g = PipeGraph("q19", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(rows).build()) \
     .add(bids).chain(narrow).add(top10).add(keep).chain(pack) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "bids", "window": "top10",
               "exit": "pack",
               "device": ["bids", "narrow", "top10", "ranked", "pack"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Bids of a block whose loss its own results show: the first ten
    bids of an auction made in the block, far enough in that no bid on it
    lies in an earlier block (a bid names an auction at most
    ``auction_id_lead`` ahead of the newest), so each of them ranks."""
    g = cfg["generator"]
    denom = g["person_proportion"] + g["auction_proportion"] \
        + g["bid_proportion"]
    lead_rows = ((g["auction_id_lead"] + 1) * denom
                 // g["auction_proportion"] + denom)
    kind, auction = cols["event_type"], cols["auction_lo"]
    made = np.unique(auction[(kind == AUCTION)
                             & (np.arange(len(kind)) >= lead_rows)])
    mask = np.zeros(len(kind), bool)
    rows = np.nonzero((kind == BID) & np.isin(auction, made))[0]
    order = np.argsort(auction[rows], kind="stable")
    a = auction[rows][order]
    start = np.r_[True, a[1:] != a[:-1]] if len(a) else np.zeros(0, bool)
    depth = np.arange(len(a)) - np.nonzero(start)[0][np.cumsum(start) - 1]
    mask[rows[order][depth < TOP]] = True
    return mask


class TopBook:
    """Every auction's ten highest bids so far (price descending, ties to
    the earlier bid), as arrays indexed by the auction id."""

    def __init__(self, top: int):
        self.top = top
        self.price = np.full((0, top), NONE, np.int32)
        self.seq = np.full((0, top), NONE, np.int32)

    def _fit(self, n: int) -> None:
        have = len(self.price)
        if n > have:
            pad = np.full((max(n, 2 * have) - have, self.top), NONE,
                          np.int32)
            self.price = np.concatenate([self.price, pad])
            self.seq = np.concatenate([self.seq, pad])

    def offer(self, auction, price, seq):
        """``(rank, evicted)`` of bids given in arrival order, each
        against the bids of its auction before it: the rank is 1 + the
        earlier bids priced at least as high, 0 where that passes ten;
        what it evicts, the tenth of the earlier bids where there were
        ten. Walks the bids' depth in their auction, every auction at
        once."""
        n, top = len(auction), self.top
        rank = np.zeros(n, np.int32)
        evicted = np.full(n, NONE, np.int32)
        if not n:
            return rank, evicted
        self._fit(int(auction.max()) + 1)
        order = np.argsort(auction, kind="stable")
        a = auction[order]
        first = np.nonzero(np.r_[True, a[1:] != a[:-1]])[0]
        size = np.diff(np.r_[first, n])
        deep = np.argsort(-size, kind="stable")
        first, size = first[deep], size[deep]
        # the chunk's auctions, deepest first: those with a bid at depth
        # d are a prefix
        ids = a[first]
        held_p, held_s = self.price[ids], self.seq[ids]
        at = np.arange(top)
        for d in range(int(size[0])):
            live = np.searchsorted(-size, -d)
            i = order[first[:live] + d]
            p, s = price[i], seq[i]
            hp, hs = held_p[:live], held_s[:live]
            r = 1 + ((hp >= p[:, None]) & (hs >= 0)).sum(axis=1)
            go = r <= top
            rank[i] = np.where(go, r, 0)
            evicted[i] = np.where(go, hs[:, top - 1], NONE)
            # a bid that does not enter leaves every place as it is
            stay, put = at < (r - 1)[:, None], at == (r - 1)[:, None]
            for held, new in ((hp, p), (hs, s)):
                down = np.concatenate([held[:, :1], held[:, :-1]], axis=1)
                held[...] = np.where(stay, held,
                                     np.where(put, new[:, None], down))
        self.price[ids], self.seq[ids] = held_p, held_s
        return rank, evicted


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Every bid's rank at arrival among its auction's bids, and the bid
    it evicted, by ``TopBook`` over the offered blocks (``CHUNK`` at a
    time). Tables of shape (rows of a block, blocks): cell (r, b) is the
    bid at row ``r`` of block ``b`` (its ``seq``), ``count`` 1 where it
    ranks, ``value`` then ``ranked``."""
    rows = cfg["batch_rows"]
    book = TopBook(cfg["top"])
    parts, bids = [], []
    n_blocks = 0

    def rank(bids):
        auction, price, seq = (np.concatenate(x) for x in zip(*bids))
        r, e = book.offer(auction, price, seq)
        hit = r > 0
        parts.append((seq[hit], ranked(r[hit], seq[hit], e[hit])))

    for c, _ in blocks:
        if len(c["seq"]):
            n_blocks = max(n_blocks, int(c["seq"][-1]) // rows + 1)
        bids.append(bids_of(c))
        if len(bids) == CHUNK:
            rank(bids)
            bids = []
    if bids:
        rank(bids)
    out = {"count": np.zeros((rows, n_blocks), np.int8),
           "value": np.zeros((rows, n_blocks), np.int32)}
    for seq, value in parts:
        out["count"][seq % rows, seq // rows] = 1
        out["value"][seq % rows, seq // rows] = value
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open: a bid is answered at
    its own arrival, whatever the watermark, so every ranked bid of the
    blocks pushed so far."""
    return int(table["count"].sum())


def windows_per_event(cfg: dict) -> int:
    return 1
