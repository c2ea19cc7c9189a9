"""sd: SpikeDetection (DSPBench; ParaGroup/StreamBenchmarks, WindFlow
version) on the device plane: per mote a moving average over a
count-based window of its last ``win_rows`` readings, slide 1, then a
threshold filter on the fired row. Sizes, what was recalled and not
checked, and the departures are in ``sd.json``. ``reference`` imports
nothing of the program."""

from __future__ import annotations

import numpy as np

from harness.traffic import draw_ids

EXACT_F32 = 1 << 24      # whole numbers below this are exact in float32


def spike(last, total, count, inverse):
    """The filter, in integers: ``|last - avg| > avg / inverse`` with
    ``avg = total / count`` (``inverse`` 40 is the source's threshold
    0.025), multiplied through by ``count * inverse``. The same
    expression runs on the device's columns and in the reference."""
    return abs(last * count - total) * inverse > total


def double_words(values: np.ndarray):
    """``(lo, hi)``: the two 32-bit words of the IEEE-754 doubles, as the
    int32 columns the 8-byte field crosses in."""
    bits = np.ascontiguousarray(values, np.float64).view(np.uint64)
    return ((bits & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
            (bits >> 32).astype(np.uint32).view(np.int32))


def words_double(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """The doubles of ``double_words``'s two columns."""
    return ((hi.view(np.uint32).astype(np.uint64) << 32)
            | lo.view(np.uint32)).view(np.float64)


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """``traffic["pool_blocks"]`` blocks of mote readings from the seed,
    every field of the 32-byte ``tuple_t`` but the timestamp (the
    program's own event-time column) at its width: an 8-byte field is
    two int32 words. Per mote a whole-number baseline; a reading is the
    baseline plus jitter too small to pass the filter, and with
    probability ``spike.probability`` a spike of ``low_share`` to
    ``high_share`` of the baseline, either sign alike so the mean stays
    put. Motes are drawn by ``traffic["motes"]`` where the cell's file
    has it, else by the configuration's. Refuses a range with which a
    window's sum could leave the integers float32 holds exactly: the
    comparison is exact."""
    rng = np.random.default_rng(seed)
    rows, n = cfg["batch_rows"], cfg["keys"]["count"]
    v, sp, w = cfg["value"], cfg["spike"], cfg["count_window"]
    base = rng.integers(v["baseline_low"], v["baseline_high"], n)
    top = v["baseline_high"] - 1
    largest = top + max(v["jitter"], int(top * sp["high_share"]))
    bound = largest * w["win_rows"]
    if bound >= EXACT_F32:
        raise ValueError(
            f"sd: a window's sum can reach {bound} >= 2**24 with readings "
            f"up to {largest}: float32 sums would no longer be exact")
    dist = traffic.get("motes", cfg["motes"])
    zeros = np.zeros(rows, np.int32)
    pool = []
    for _ in range(int(traffic["pool_blocks"])):
        mote = draw_ids(rng, n, rows, dist).astype(np.int32)
        b = base[mote]
        size = rng.integers(np.ceil(b * sp["low_share"]).astype(np.int64),
                            (b * sp["high_share"]).astype(np.int64) + 1)
        jitter = rng.integers(-v["jitter"], v["jitter"] + 1, rows)
        off = np.where(rng.random(rows) < sp["probability"],
                       size * rng.choice((-1, 1), rows), jitter)
        lo, hi = double_words(b + off)
        pool.append({"value_lo": lo, "value_hi": hi,
                     "avg_lo": zeros, "avg_hi": zeros,
                     "device_lo": mote, "device_hi": zeros})
    return {"pool": pool, "baseline": base, "window_sum_bound": bound}


def narrow_double(lo, hi):
    """A double's two int32 words -> float32, by bit operations (the
    device plane has no 64-bit floats): sign, exponent rebiased 1023 ->
    127, the top 23 mantissa bits (truncated: exact for every double a
    float32 holds, so for the whole numbers the stream draws, else within
    one ulp), zero for a zero exponent. Exponents outside float32's
    range are not the stream's and are not handled."""
    import jax
    import jax.numpy as jnp

    lo = jax.lax.bitcast_convert_type(lo, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(hi, jnp.uint32)
    sign = hi & jnp.uint32(0x80000000)
    exp = (hi >> 20) & jnp.uint32(0x7FF)
    mant = ((hi & jnp.uint32(0xFFFFF)) << 3) | (lo >> 29)
    bits = sign | ((exp - jnp.uint32(1023 - 127)) << 23) | mant
    return jax.lax.bitcast_convert_type(
        jnp.where(exp == 0, sign, bits), jnp.float32)


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Map_TPU ``narrow`` (the mote id as the key, the double
    narrowed to float32) -> keyed re-shard -> Ffat_Windows_TPU
    (count-based; sum, count and the last reading) -> Map_TPU ``avg``
    chained with Filter_TPU ``spikes`` (one fused program) -> columnar
    sink."""
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    w, inverse = cfg["count_window"], cfg["threshold_inverse"]
    narrow = (Map_TPU_Builder(
                  lambda f: {"device": f["device_lo"],
                             "value": narrow_double(f["value_lo"],
                                                    f["value_hi"])})
              .with_name("narrow").build())
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
    avg = (Map_TPU_Builder(
               lambda f: {**f, "incremental_average": f["sum"] / jnp.maximum(
                   f["count"], 1).astype(jnp.float32)})
           .with_name("avg").build())
    spikes = (Filter_TPU_Builder(
                  lambda f: spike(f["last"].astype(jnp.int32),
                                  f["sum"].astype(jnp.int32), f["count"],
                                  inverse))
              .with_name("spikes").build())
    g = PipeGraph("sd", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(cfg["batch_rows"]).build()) \
     .add(narrow).add(win.build()).add(avg).chain(spikes) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "narrow", "window": "win",
               "exit": "spikes", "device": ["narrow", "win", "spikes"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block that reach a window: all."""
    return np.ones(len(cols["device_lo"]), bool)


def _arrivals(blocks, n_keys: int):
    """Every mote's readings in arrival order, as whole numbers."""
    blocks = list(blocks)
    mote = np.concatenate([c["device_lo"] for c, _ in blocks])
    val = np.concatenate([words_double(c["value_lo"], c["value_hi"])
                          for c, _ in blocks]).astype(np.int64)
    order = np.argsort(mote, kind="stable")
    return np.split(val[order], np.cumsum(
        np.bincount(mote, minlength=n_keys))[:-1])


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Per mote, window ``w`` holds its arrivals ``[w * slide, w * slide
    + win)``; one exists for every arrival index a slide starts at (the
    last ones partial: the end-of-stream flush fires them). ``value`` is
    the window's sum and ``last`` its last reading (a table the harness
    does not read: tier-1 does); ``count`` is 0 where the filter drops
    the window: nothing is delivered for it."""
    w, inverse = cfg["count_window"], cfg["threshold_inverse"]
    win, slide = w["win_rows"], w["slide_rows"]
    per_key = _arrivals(blocks, cfg["keys"]["count"])
    n_win = max(-(-len(v) // slide) for v in per_key)
    out = {name: np.zeros((len(per_key), n_win), np.int64)
           for name in ("value", "count", "last")}
    for k, v in enumerate(per_key):
        lo = np.arange(0, len(v), slide)
        hi = np.minimum(lo + win, len(v))
        c = np.concatenate([[0], np.cumsum(v)])
        total, count, last = c[hi] - c[lo], hi - lo, v[hi - 1]
        keep = spike(last, total, count, inverse)
        out["value"][k, :len(lo)] = np.where(keep, total, 0)
        out["count"][k, :len(lo)] = np.where(keep, count, 0)
        out["last"][k, :len(lo)] = np.where(keep, last, 0)
    return out


def results_due(table, blocks, cfg: dict, stream: dict, wm_us: int) -> int:
    """Rows delivered with the stream still open: the complete windows
    (a mote's window ``w`` fires with its arrival ``w * slide + win -
    1``, whatever the watermark) that pass the filter."""
    w = cfg["count_window"]
    arrived = np.bincount(
        np.concatenate([c["device_lo"] for c, _ in blocks]),
        minlength=cfg["keys"]["count"])
    k, wid = np.nonzero(table["count"])
    return int((wid * w["slide_rows"] + w["win_rows"] <= arrived[k]).sum())


def windows_per_event(cfg: dict) -> int:
    w = cfg["count_window"]
    return max(1, w["win_rows"] // w["slide_rows"])
