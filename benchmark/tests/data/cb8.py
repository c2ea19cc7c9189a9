"""cb8: a test-only deployment (``benchmark/tests``; never measured) in
the shape of SpikeDetection: per device a count-based sliding window over
its last ``win_rows`` readings, aggregate ``{sum, count, last}`` with
``last`` the right operand's, then a filter on the fired row. It says
``results_due`` and ``windows_per_event`` itself, and its file has no
``window`` key. ``reference`` imports nothing of the program."""

from __future__ import annotations

import numpy as np

from harness.traffic import draw_ids


def spike(last, total, count, quarters):
    """The filter, in integers: the last reading is more than
    ``quarters`` quarters of the window's mean away from it. The same
    expression runs on the device's columns and in the reference."""
    return abs(last * count - total) * 4 > quarters * total


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    rng = np.random.default_rng(seed)
    rows, keys, v = cfg["batch_rows"], cfg["keys"], cfg["value"]
    pool = [{"device": draw_ids(rng, keys["count"], rows,
                                keys).astype(np.int32),
             "value": rng.integers(v["low"], v["high"], rows).astype(
                 np.int32)}
            for _ in range(int(traffic["pool_blocks"]))]
    return {"pool": pool}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> keyed re-shard -> Ffat_Windows_TPU (count-based) ->
    Filter_TPU -> columnar sink."""
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder, Filter_TPU_Builder

    w, q = cfg["count_window"], cfg["spike_quarters"]
    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"sum": f["value"],
                          "count": jnp.ones(f["value"].shape, jnp.int32),
                          "last": f["value"]},
               lambda a, b: {"sum": a["sum"] + b["sum"],
                             "count": a["count"] + b["count"],
                             "last": b["last"]})
           .with_key_by("device")
           .with_cb_windows(w["win_rows"], w["slide_rows"])
           .with_key_capacity(cfg["key_capacity"])
           .with_parallelism(cfg["parallelism"]).with_name("win"))
    if cfg.get("num_win_per_batch"):
        win = win.with_num_win_per_batch(cfg["num_win_per_batch"])
    spikes = (Filter_TPU_Builder(
                  lambda f: spike(f["last"], f["sum"], f["count"], q))
              .with_name("spikes").build())
    g = PipeGraph("cb8", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(cfg["batch_rows"]).build()) \
     .add(win.build()).add(spikes) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "win", "window": "win",
               "exit": "spikes", "device": ["win", "spikes"], "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    return np.ones(len(cols["value"]), bool)


def _arrivals(blocks, n_keys: int):
    """Every device's values in arrival order."""
    blocks = list(blocks)
    dev = np.concatenate([c["device"] for c, _ in blocks])
    val = np.concatenate([c["value"] for c, _ in blocks]).astype(np.int64)
    return [val[dev == k] for k in range(n_keys)]


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Per device, window ``w`` holds its arrivals ``[w * slide, w * slide
    + win)``; one exists for every arrival index a slide starts at (the
    last ones partial: the end-of-stream flush fires them). ``count`` is
    0 where the filter drops the window: nothing is delivered for it."""
    w, q = cfg["count_window"], cfg["spike_quarters"]
    win, slide = w["win_rows"], w["slide_rows"]
    per_key = _arrivals(blocks, cfg["keys"]["count"])
    n_win = max(-(-len(v) // slide) for v in per_key)
    out = {"value": np.zeros((len(per_key), n_win), np.int64),
           "count": np.zeros((len(per_key), n_win), np.int64)}
    for k, v in enumerate(per_key):
        lo = np.arange(0, len(v), slide)
        hi = np.minimum(lo + win, len(v))
        c = np.concatenate([[0], np.cumsum(v)])
        total, count, last = c[hi] - c[lo], hi - lo, v[hi - 1]
        keep = spike(last, total, count, q)
        out["value"][k, :len(lo)] = np.where(keep, total, 0)
        out["count"][k, :len(lo)] = np.where(keep, count, 0)
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open: the complete windows
    (a device's window ``w`` fires with its arrival ``w * slide + win -
    1``, whatever the watermark) that pass the filter."""
    w = cfg["count_window"]
    arrived = np.array([len(v) for v in
                        _arrivals(blocks, cfg["keys"]["count"])])
    k, wid = np.nonzero(table["count"])
    return int((wid * w["slide_rows"] + w["win_rows"] <= arrived[k]).sum())


def windows_per_event(cfg: dict) -> int:
    w = cfg["count_window"]
    return max(1, w["win_rows"] // w["slide_rows"])
