"""ysb: the Yahoo Streaming Benchmark query on the device plane, with the
numeric record of the upstream project's own benchmark suite. Sizes and
departures are in ``ysb.json``. ``reference`` imports nothing of the
program."""

from __future__ import annotations

import numpy as np

from harness.traffic import draw_ids
from harness.windows import dense_window_fold


WIDE = ("user_id", "page_id", "ad_id")     # the record's 64-bit fields


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """``traffic["pool_blocks"]`` blocks of ad events from the seed, every
    field of the record at its width (a 64-bit field as ``_lo`` and
    ``_hi`` int32 halves), and the static ad -> campaign table: a seeded
    permutation, so every campaign owns exactly ``ads_per_campaign`` ads.
    Ads are drawn by ``traffic["ads"]`` where the cell's file has it, else
    by the configuration's."""
    rng = np.random.default_rng(seed)
    rows = cfg["batch_rows"]
    n_ads = cfg["campaigns"] * cfg["ads_per_campaign"]
    table = (rng.permutation(n_ads) // cfg["ads_per_campaign"]).astype(
        np.int32)
    ads = traffic.get("ads", cfg["ads"])
    high = np.zeros(rows, np.int32)

    def draw(n):
        return rng.integers(0, n, rows).astype(np.int32)

    pool = []
    for _ in range(int(traffic["pool_blocks"])):
        low = {"user_id": draw(cfg["users"]), "page_id": draw(cfg["pages"]),
               "ad_id": draw_ids(rng, n_ads, rows, ads).astype(np.int32)}
        cols = {}
        for f in WIDE:
            cols[f + "_lo"], cols[f + "_hi"] = low[f], high
        cols.update(ad_type=draw(cfg["ad_types"]),
                    event_type=draw(cfg["event_types"]),
                    ip=np.full(rows, 1, np.int32))
        pool.append(cols)
    return {"pool": pool, "campaign_of_ad": table}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Filter_TPU chained with Map_TPU (the join as a gather;
    one fused program) -> keyed re-shard -> Ffat_Windows_TPU -> columnar
    sink. The window operator is added, not chained: a fused window chain
    may not compute its key in its prefix."""
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    view = cfg["view_type"]
    table = jnp.asarray(stream["campaign_of_ad"])
    w = cfg["window"]
    views = (Filter_TPU_Builder(lambda f: f["event_type"] == view)
             .with_name("views").build())
    join = (Map_TPU_Builder(lambda f: {"campaign": table[f["ad_id_lo"]],
                                       "one": f["event_type"] * 0 + 1})
            .with_name("join").build())
    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"count": f["one"]},
               lambda a, b: {"count": a["count"] + b["count"]})
           .with_key_by("campaign")
           .with_tb_windows(w["win_us"], w["slide_us"])
           .with_num_win_per_batch(cfg["num_win_per_batch"])
           .with_key_capacity(cfg["key_capacity"])
           .with_parallelism(cfg["parallelism"]).with_name("win").build())
    g = PipeGraph("ysb", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(cfg["batch_rows"]).build()) \
     .add(views).chain(join).add(win) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "views", "window": "win",
               "device": ["views", "win"], "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block that reach a window: the views."""
    return cols["event_type"] == cfg["view_type"]


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Views per (campaign, 10 s window), as dense tables."""
    w = cfg["window"]
    table, view = stream["campaign_of_ad"], cfg["view_type"]

    def views():
        for c, ts in blocks:
            keep = c["event_type"] == view
            camp = table[c["ad_id_lo"][keep]]
            yield camp, np.ones(len(camp), np.int64), ts[keep]

    return dense_window_fold(views(), cfg["campaigns"], w["win_us"],
                             w["slide_us"], last_ts)
