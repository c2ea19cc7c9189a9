"""sg2: the smart-grid per-plug sliding average of the SABER and
LightSaber evaluations (query SG2 over the DEBS 2014 smart-plug trace) on
the device plane. Sizes, what was assumed and how the triple is keyed are
in ``sg2.json``. ``reference`` imports nothing of the program."""

from __future__ import annotations

import math

import numpy as np

from harness.traffic import draw_ids
from harness.windows import dense_window_fold

EXACT_F32 = 1 << 24      # whole numbers below this are exact in float32


def _aranges(counts) -> np.ndarray:
    """[0..c0), [0..c1), ... end to end."""
    return np.concatenate([np.arange(c) for c in counts])


def make_registry(rng, cfg: dict) -> dict:
    """The (house, household, plug) triples of the deployment, from the
    seed: every house gets 1..``households_per_house_max`` households,
    every household one plug and the rest dealt at random. Sorted by
    (house, household, plug), so a triple's row in the registry is
    ``base[house * households_per_house_max + household] + plug``."""
    houses, plugs = cfg["houses"], cfg["keys"]["count"]
    hh_max = cfg["households_per_house_max"]
    per_house = rng.integers(1, hh_max + 1, houses)
    n_hh = int(per_house.sum())
    if n_hh > plugs:
        raise ValueError(f"sg2: {n_hh} households for {plugs} plugs")
    hh_house = np.repeat(np.arange(houses), per_house)
    hh_local = _aranges(per_house)
    size = 1 + np.bincount(rng.integers(0, n_hh, plugs - n_hh),
                           minlength=n_hh)
    start = np.cumsum(size) - size
    base = np.zeros(houses * hh_max, np.int32)
    base[hh_house * hh_max + hh_local] = start
    return {"house": np.repeat(hh_house, size).astype(np.int32),
            "household": np.repeat(hh_local, size).astype(np.int32),
            "plug": _aranges(size).astype(np.int32),
            "base": base}


def plug_index(cols: dict, registry: dict, cfg: dict) -> np.ndarray:
    """Registry row of every event's triple (plain numpy: the reference's
    side of what the ``pack`` operator does on the device)."""
    hh_max = cfg["households_per_house_max"]
    return (registry["base"][cols["house"] * hh_max + cols["household"]]
            + cols["plug"])


def make_stream(seed: int, cfg: dict, traffic: dict) -> dict:
    """``traffic["pool_blocks"]`` blocks of smart-plug readings from the
    seed, every field of the 32-byte record but the timestamp (the
    program's own event-time column) at its width. Plugs are drawn by
    ``traffic["plugs"]`` where the cell's file has it, else by the
    configuration's; values are whole numbers carried as float32.
    Refuses a value range with which a window's sum could leave the
    integers float32 holds exactly: the comparison is exact."""
    rng = np.random.default_rng(seed)
    rows, n = cfg["batch_rows"], cfg["keys"]["count"]
    reg = make_registry(rng, cfg)
    lo, hi = cfg["value"]["low"], cfg["value"]["high"]
    dist = traffic.get("plugs", cfg["plugs"])
    pool, per_plug = [], np.zeros(n, np.int64)
    for _ in range(int(traffic["pool_blocks"])):
        idx = draw_ids(rng, n, rows, dist)
        value = rng.integers(lo, hi, rows)
        per_plug += np.bincount(idx, weights=value, minlength=n).astype(
            np.int64)
        pool.append({
            "value": value.astype(np.float32),
            "property": rng.integers(0, 2, rows).astype(np.int32),
            "plug": reg["plug"][idx], "household": reg["household"][idx],
            "house": reg["house"][idx],
            "padding": np.zeros(rows, np.int32)})
    # a window holds at most this many passes of the cycled pool (blocks
    # are never closer in event time than one block's span)
    span_us = (rows * 10**6) // int(traffic["nominal_rate"])
    in_window = math.ceil(cfg["window"]["win_us"] / max(span_us, 1)) + 1
    cycles = math.ceil(in_window / len(pool)) + 1
    bound = int(per_plug.max()) * cycles
    if bound >= EXACT_F32:
        raise ValueError(
            f"sg2: a window's sum can reach {bound} >= 2**24 with values "
            f"in [{lo}, {hi}): float32 sums would no longer be exact")
    return {"pool": pool, "registry": reg, "window_sum_bound": bound}


def build_graph(source_fn, sink, cfg: dict, stream: dict):
    """Source -> Map_TPU ``pack`` (the triple to its registry row, one
    gather) -> keyed re-shard -> Ffat_Windows_TPU (sum and count) ->
    Map_TPU ``avg`` (sum / count, the triple back from the registry) ->
    columnar sink."""
    import jax.numpy as jnp

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder, Map_TPU_Builder

    reg = {k: jnp.asarray(v) for k, v in stream["registry"].items()}
    hh_max = cfg["households_per_house_max"]
    w = cfg["window"]

    def pack(f):
        return {"key": reg["base"][f["house"] * hh_max + f["household"]]
                + f["plug"], "value": f["value"]}

    def avg(f):
        k = f["key"]
        return {**f, "avg": f["sum"] / jnp.maximum(f["count"], 1).astype(
                    jnp.float32),
                "plug": reg["plug"][k], "household": reg["household"][k],
                "house": reg["house"][k]}

    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"sum": f["value"],
                          "count": jnp.ones(f["value"].shape, jnp.int32)},
               lambda a, b: {"sum": a["sum"] + b["sum"],
                             "count": a["count"] + b["count"]})
           .with_key_by("key")
           .with_tb_windows(w["win_us"], w["slide_us"])
           .with_key_capacity(cfg["key_capacity"])
           .with_parallelism(cfg["parallelism"]).with_name("win"))
    if cfg.get("num_win_per_batch"):     # else the operator's own sizing
        win = win.with_num_win_per_batch(cfg["num_win_per_batch"])
    win = win.build()
    g = PipeGraph("sg2", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=cfg["channel_capacity"])
    g.add_source(Source_Builder(source_fn).with_name("src")
                 .with_output_batch_size(cfg["batch_rows"]).build()) \
     .add(Map_TPU_Builder(pack).with_name("pack").build()) \
     .add(win) \
     .add(Map_TPU_Builder(avg).with_name("avg").build()) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g, {"source": "src", "first": "pack", "window": "win",
               "exit": "avg", "device": ["pack", "win", "avg"],
               "sink": "snk"}


def counted_mask(cols: dict, cfg: dict) -> np.ndarray:
    """Events of a block that reach a window: all (SG2 has no where)."""
    return np.ones(len(cols["value"]), bool)


def reference(blocks, cfg: dict, stream: dict, last_ts: int):
    """Sum and count of ``value`` per (plug, 1 s slide of a 3600 s
    window), as dense tables over the registry's rows."""
    w, reg = cfg["window"], stream["registry"]

    def readings():
        for c, ts in blocks:
            yield (plug_index(c, reg, cfg), c["value"].astype(np.int64), ts)

    return dense_window_fold(readings(), cfg["keys"]["count"], w["win_us"],
                             w["slide_us"], last_ts)
