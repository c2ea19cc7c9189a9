#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that windflow_tpu still starts on the chip.

One process, no child that touches JAX. It drives the served path through
the public builders on a TPU and checks every result against a plain
reference of the same semantics:

  A. the main path at real size: columnar source -> keyed staging ->
     ``Ffat_Windows_TPU`` -> columnar sink, the repo's high-cardinality
     configuration (bench.py: 10,240 int32 keys, 65,536-row batches,
     100 ms windows sliding by 25 ms), 24 batches. Every fired
     (key, wid, value) equals a vectorised numpy fold, and a sample of 64
     keys equals the per-tuple CPU-plane ``Ffat_Windows``;
  B. every other device program family once, each in a ``PipeGraph`` and
     equal to its CPU-plane sibling on the same stream: the fused
     map∘filter∘map chain, the keyed stateful map (grid scan), the keyed
     reduce (segmented scan), a device split edge with a TPU->TPU keyed
     re-shard, and the fused filter -> project -> tumbling-count chain of
     examples/ysb.py;
  D. with four or more chips: stage A's stream through ``.with_mesh(
     n_devices=4)`` and a mesh-sharded stateful map. On fewer chips the
     stage prints ``skipped`` and is not a pass.

It exits non-zero when JAX finds no TPU, when any stage raises and when
any comparison differs; there is no CPU mode. The stage functions take
their sizes as arguments so that tests can call them small on the CPU
backend. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A fuller summary is appended to ``chiprun_out/chip_smoke.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

import bench  # the repo's own configuration constants
from windflow_tpu import (ExecutionMode, Ffat_Windows_Builder, Filter_Builder,
                          Map_Builder, PipeGraph, Reduce_Builder,
                          Sink_Builder, Source_Builder, TimePolicy)

B_BATCH = 16_384      # stage B batch rows
B_BATCHES = 16
B_KEYS = 1_024
SAMPLE_KEYS = 64
# examples/ysb.py's shape
YSB_CAMPAIGNS = 100
YSB_ADS_PER_CAMPAIGN = 10
YSB_WIN_US = 10_000_000
YSB_TS_STEP_US = 100


class SmokeError(Exception):
    """A comparison differed or an expected device path did not run."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# streams (made from the seed) and graph plumbing
# ---------------------------------------------------------------------------
def ffat_stream(seed: int, n_keys: int, batch: int, n_batches: int):
    """bench.py's stream: uniform int32 keys, values in [0, 100), event
    time advancing TS_STEP/AGG_RATE_KEYS µs per tuple whatever the key
    count. Returns [({"key", "value"}, ts)] blocks."""
    rng = np.random.default_rng(seed)
    blocks, ts0 = [], 0
    for _ in range(n_batches):
        keys = rng.integers(0, n_keys, batch).astype(np.int32)
        vals = rng.integers(0, 100, batch).astype(np.int32)
        ts = (ts0 + np.arange(batch, dtype=np.int64)
              * bench.TS_STEP // bench.AGG_RATE_KEYS)
        ts0 = int(ts[-1]) + bench.TS_STEP
        blocks.append(({"key": keys, "value": vals}, ts))
    return blocks


def ysb_stream(seed: int, batch: int, n_batches: int):
    """examples/ysb.py's events. The ad -> campaign join key is resolved
    at the source: a fused window chain may not compute its key column
    in its prefix (tpu/fused_ops.py legality)."""
    rng = np.random.default_rng(seed)
    n_ads = YSB_CAMPAIGNS * YSB_ADS_PER_CAMPAIGN
    blocks = []
    for b in range(n_batches):
        ts = (b * batch + np.arange(batch, dtype=np.int64)) * YSB_TS_STEP_US
        ad = rng.integers(0, n_ads, batch).astype(np.int32)
        blocks.append(({
            "ad_id": ad, "campaign": ad // YSB_ADS_PER_CAMPAIGN,
            "event_type": rng.integers(0, 3, batch).astype(np.int32)}, ts))
    return blocks


def block_source(blocks):
    """Event-time source functor over column blocks; each block rides
    the watermark just below its first timestamp, so no row is late. On
    a device edge a block is one staged batch, on a CPU edge its rows
    materialise as dicts — one functor feeds both planes."""
    def src(shipper, ctx):
        for cols, ts in blocks:
            shipper.set_next_watermark(max(0, int(ts[0]) - 1))
            shipper.push_columns(cols, ts=ts)
            shipper.set_next_watermark(int(ts[-1]))
    return src


class ColumnSink:
    """``with_columns`` sink: keeps the named columns of every batch."""

    def __init__(self, names, valid_field=None):
        self.names, self.valid_field = list(names), valid_field
        self._parts, self._lock = [], threading.Lock()

    def __call__(self, cols, ts):
        if cols is None:
            return
        keep = (cols[self.valid_field].astype(bool)
                if self.valid_field else slice(None))
        part = [np.array(cols[n][keep]) for n in self.names]
        with self._lock:
            self._parts.append(part)

    def columns(self):
        with self._lock:
            parts = list(self._parts)
        if not parts:
            return [np.zeros(0, np.int64) for _ in self.names]
        return [np.concatenate([p[i] for p in parts]).astype(np.int64)
                for i in range(len(self.names))]


class RowSink:
    """Row sink for CPU-plane siblings: ``pick(row)`` -> tuple of ints."""

    def __init__(self, pick):
        self.pick, self.rows, self._lock = pick, [], threading.Lock()

    def __call__(self, row):
        if row is None:
            return
        t = self.pick(row)
        if t is not None:
            with self._lock:
                self.rows.append(t)

    def columns(self, width: int):
        if not self.rows:
            return [np.zeros(0, np.int64) for _ in range(width)]
        a = np.asarray(self.rows, dtype=np.int64).reshape(-1, width)
        return [a[:, i] for i in range(width)]


def same_rows(what: str, got, want) -> int:
    """Multiset equality of two column tuples (order-insensitive: DEFAULT
    mode promises no cross-replica order). Returns the row count."""
    g = np.stack(got, axis=1) if len(got[0]) else np.zeros((0, len(got)))
    w = np.stack(want, axis=1) if len(want[0]) else np.zeros((0, len(want)))
    check(len(g) == len(w), f"{what}: {len(g)} rows, reference {len(w)}")
    g = g[np.lexsort(g.T[::-1])]
    w = w[np.lexsort(w.T[::-1])]
    bad = (g != w).any(axis=1)
    check(not bad.any(), f"{what}: {int(bad.sum())} of {len(w)} rows differ "
                         f"from the reference (first: got {g[bad][:1]}, "
                         f"want {w[bad][:1]})")
    return len(w)


def replica_stats(graph, name=None, kind=None):
    reps = []
    for o in graph.get_stats()["Operators"]:
        if (name is None or o["name"] == name) \
                and (kind is None or o["kind"] == kind):
            reps.extend(o["replicas"])
    return reps


def stat_sum(reps, field: str):
    return sum(r.get(field, 0) for r in reps)


def device_set(tree) -> set:
    import jax
    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs |= set(leaf.devices())
    return devs


def event_graph(name: str) -> PipeGraph:
    return PipeGraph(name, ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)


def timed_run(graph) -> float:
    """``graph.run()``; seconds of wall clock, compilation included."""
    t0 = time.perf_counter()
    graph.run()
    return round(time.perf_counter() - t0, 3)


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------
def numpy_window_fold(blocks, win_us: int, slide_us: int):
    """Sum per (key, window) by a vectorised fold. Window ``w`` of every
    key is ``[w*slide, w*slide + win)`` from absolute time 0, and at end
    of stream every window that holds a tuple has fired. Returns sorted
    (key, wid, value) int64 columns."""
    keys = np.concatenate([c["key"] for c, _ in blocks]).astype(np.int64)
    vals = np.concatenate([c["value"] for c, _ in blocks]).astype(np.int64)
    ts = np.concatenate([t for _, t in blocks])
    last = ts // slide_us                       # newest window holding ts
    n_win = int(last.max()) + 1
    comp, wsum = [], []
    for back in range(-(-win_us // slide_us)):
        w = last - back
        ok = (w >= 0) & (w * slide_us + win_us > ts)
        comp.append(keys[ok] * n_win + w[ok])
        wsum.append(vals[ok])
    comp, inv = np.unique(np.concatenate(comp), return_inverse=True)
    total = np.bincount(inv, weights=np.concatenate(wsum).astype(np.float64))
    return [comp // n_win, comp % n_win, total.astype(np.int64)]


def cpu_ffat_windows(blocks, win_us: int, slide_us: int, key_field="key",
                     value_field="value", prefix=()):
    """The per-tuple CPU plane over the same blocks: optional CPU-plane
    ``prefix`` operators, then ``Ffat_Windows`` (lift = the value, combine
    = +). Returns (key, wid, value) columns of the non-empty windows."""
    sink = RowSink(lambda r: None if r.value is None
                   else (r.key, r.wid, r.value))
    g = event_graph("smoke_cpu_ffat")
    mp = g.add_source(Source_Builder(block_source(blocks)).build())
    for op in prefix:
        mp = mp.add(op)
    mp.add(Ffat_Windows_Builder(lambda t: t[value_field],
                                lambda a, b: a + b)
           .with_key_by(lambda t: t[key_field])
           .with_tb_windows(win_us, slide_us).build()) \
      .add_sink(Sink_Builder(sink).build())
    g.run()
    return sink.columns(3)


# ---------------------------------------------------------------------------
# stage A: the main path
# ---------------------------------------------------------------------------
def run_ffat_graph(blocks, n_keys: int, batch: int, mesh_devices=None):
    """source -> keyed staging -> FFAT (one chip, or the mesh plane) ->
    columnar sink. Returns (windows, graph, operator, run seconds)."""
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

    sink = ColumnSink(["key", "wid", "value"], valid_field="valid")
    b = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda x, y: {"value": x["value"] + y["value"]})
         .with_tb_windows(bench.WIN_US, bench.SLIDE_US)
         .with_key_by("key").with_key_capacity(n_keys).with_name("ffat"))
    if mesh_devices:
        b = b.with_mesh(n_devices=mesh_devices)
    op = b.build()
    g = event_graph("smoke_ffat_mesh" if mesh_devices else "smoke_ffat")
    g.add_source(Source_Builder(block_source(blocks)).with_name("src")
                 .with_output_batch_size(batch).build()) \
     .add(op).add_sink(Sink_Builder(sink).with_name("snk")
                       .with_columns().build())
    wall = timed_run(g)
    return sink.columns(), g, op, wall


def check_ffat_windows(what: str, got, blocks, n_keys: int,
                       sample_keys: int, seed: int) -> int:
    """Every fired window against the numpy fold; a fixed key sample
    against the per-tuple CPU plane on the same arrays."""
    n = same_rows(f"{what} vs numpy fold", got,
                  numpy_window_fold(blocks, bench.WIN_US, bench.SLIDE_US))
    sample = np.random.default_rng(seed + 1).choice(
        n_keys, size=min(sample_keys, n_keys), replace=False)
    sub = []
    for cols, ts in blocks:
        m = np.isin(cols["key"], sample)
        sub.append(({k: v[m] for k, v in cols.items()}, ts[m]))
    m = np.isin(got[0], sample)
    same_rows(f"{what} vs CPU-plane Ffat_Windows ({len(sample)} keys)",
              [c[m] for c in got],
              cpu_ffat_windows(sub, bench.WIN_US, bench.SLIDE_US))
    return n


def stage_a(seed: int, n_keys: int, batch: int, n_batches: int,
            sample_keys: int = SAMPLE_KEYS) -> dict:
    blocks = ffat_stream(seed, n_keys, batch, n_batches)
    n_events = batch * n_batches
    got, g, op, wall = run_ffat_graph(blocks, n_keys, batch)
    n_win = check_ffat_windows("stage A", got, blocks, n_keys, sample_keys,
                               seed)
    win = replica_stats(g, name="ffat")
    received, late, admitted, dropped = (
        stat_sum(win, f) for f in ("Inputs_received", "Late_records",
                                   "Late_admitted", "Late_dropped"))
    # inputs == on_time + admitted + dropped, and this stream is in order
    check((received - late) + admitted + dropped == n_events and late == 0,
          f"stage A: late-record conservation broken: received={received} "
          f"of {n_events}, late={late} admitted={admitted} "
          f"dropped={dropped} on an in-order stream")
    rep = op.replicas[0]
    out = {
        "events": n_events, "windows": n_win, "run_s": wall,
        "K_cap": rep.K_cap, "F": rep.F, "W_cap": rep.W_cap,
        "W_wide": rep.W_wide,
        "Compile_count": stat_sum(win, "Compile_count"),
        "Device_programs_run": stat_sum(win, "Device_programs_run"),
        "Programs_per_batch": win[0]["Programs_per_batch"],
        "Staging_pool_hits": stat_sum(replica_stats(g), "Staging_pool_hits"),
        "forest_devices": sorted(str(d) for d in device_set(
            (rep.trees, rep.tvalid))),
    }
    say(f"stage A: {n_events} events -> {n_win} windows exact; "
        f"K_cap={rep.K_cap} F={rep.F} W_cap={rep.W_cap} "
        f"W_wide={rep.W_wide} "
        f"Compile_count={out['Compile_count']} "
        f"Device_programs_run={out['Device_programs_run']} "
        f"Programs_per_batch={out['Programs_per_batch']} "
        f"Staging_pool_hits={out['Staging_pool_hits']} "
        f"forest on {out['forest_devices']} run={wall}s")
    return out


# ---------------------------------------------------------------------------
# stage B: the other device program families, each against its CPU sibling
# ---------------------------------------------------------------------------
def cpu_rows(blocks, build, sinks: int = 1):
    """Run a CPU-plane sibling graph; ``build(mp, sinks)`` wires the
    operators between the source and the row sink(s). Returns the
    (key, value) columns each sink saw."""
    rs = [RowSink(lambda t: (t["key"], t["value"])) for _ in range(sinks)]
    g = event_graph("smoke_cpu")
    mp = g.add_source(Source_Builder(block_source(blocks)).build())
    build(mp, [Sink_Builder(r).build() for r in rs])
    g.run()
    return [r.columns(2) for r in rs]


def cpu_running_sum():
    """CPU-plane keyed Reduce: the running sum per key, one per input."""
    return (Reduce_Builder(lambda t, st: {"key": t["key"],
                                          "value": st["value"] + t["value"]})
            .with_key_by(lambda t: t["key"])
            .with_initial_state({"key": 0, "value": 0}))


def dev_source(g, blocks, batch):
    return g.add_source(Source_Builder(block_source(blocks))
                        .with_name("src").with_output_batch_size(batch)
                        .build())


def per_key_sum(k, v):
    uk, inv = np.unique(k, return_inverse=True)
    return [uk, np.bincount(inv, weights=v.astype(np.float64))
            .astype(np.int64)]


def per_key_last(k, v):
    """Final running sum per key (values are >= 0, so the largest)."""
    uk, inv = np.unique(k, return_inverse=True)
    out = np.zeros(len(uk), np.int64)
    np.maximum.at(out, inv, v)
    return [uk, out]


def family_fused_chain(blocks, batch):
    """Map_TPU ∘ Filter_TPU ∘ Map_TPU as one program per batch."""
    from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder

    m1 = lambda f: {**f, "value": f["value"] * 3 + f["key"]}
    keep = lambda f: (f["value"] % 2) == 0
    m2 = lambda f: {**f, "value": f["value"] + 1}
    sink = ColumnSink(["key", "value"])
    g = event_graph("smoke_fused")
    dev_source(g, blocks, batch) \
        .add(Map_TPU_Builder(m1).with_name("m1").build()) \
        .chain(Filter_TPU_Builder(keep).with_name("f1").build()) \
        .chain(Map_TPU_Builder(m2).with_name("m2").build()) \
        .add_sink(Sink_Builder(sink).with_columns().build())
    wall = timed_run(g)
    fused = replica_stats(g, kind="Fused_TPU_Chain")
    check(len(fused) == 1 and fused[0]["Fused_ops"] == 3,
          "fused chain: the three operators did not fuse into one stage")
    check(fused[0]["Device_programs_run"] == fused[0]["Device_batches_in"],
          "fused chain: more than one program per batch")
    want, = cpu_rows(
        blocks, lambda mp, s: mp.add(Map_Builder(m1).build())
        .add(Filter_Builder(keep).build()).add(Map_Builder(m2).build())
        .add_sink(s[0]))
    return same_rows("fused map∘filter∘map", sink.columns(), want), \
        fused, wall


def running_sum_step(row, state):
    total = state["total"] + row["value"]
    return {**row, "value": total}, {"total": total}


def family_stateful_map(blocks, batch, running, n_keys=0, mesh_devices=None):
    """Keyed stateful Map_TPU (grid scan): a running sum per key, one
    output per input, against the CPU plane's keyed Reduce (``running``).
    Returns the operator too: stage D reads its sharded table."""
    import jax.numpy as jnp

    from windflow_tpu.tpu import Map_TPU_Builder

    b = (Map_TPU_Builder(running_sum_step)
         .with_state({"total": jnp.int32(0)}).with_key_by("key")
         .with_name("scan"))
    if mesh_devices:
        b = b.with_mesh(n_devices=mesh_devices, key_capacity=n_keys)
    op = b.build()
    sink = ColumnSink(["key", "value"])
    g = event_graph("smoke_scan_mesh" if mesh_devices else "smoke_scan")
    dev_source(g, blocks, batch).add(op) \
        .add_sink(Sink_Builder(sink).with_columns().build())
    wall = timed_run(g)
    n = same_rows("stateful map (running sum per key)", sink.columns(),
                  running)
    return n, replica_stats(g, name="scan"), wall, op


def family_keyed_reduce(blocks, batch, running):
    """Keyed Reduce_TPU (segmented scan) emits one partial per key per
    batch; a key's partials add up to the CPU Reduce's final state."""
    from windflow_tpu.tpu import Reduce_TPU_Builder

    sink = ColumnSink(["key", "value"])
    g = event_graph("smoke_reduce")
    dev_source(g, blocks, batch) \
        .add(Reduce_TPU_Builder(
            lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
            .with_key_by("key").with_name("red").build()) \
        .add_sink(Sink_Builder(sink).with_columns().build())
    wall = timed_run(g)
    k, v = sink.columns()
    check(len(k) < sum(len(t) for _, t in blocks),
          "keyed reduce: no per-batch reduction happened")
    return same_rows("keyed reduce totals", per_key_sum(k, v),
                     per_key_last(*running)), \
        replica_stats(g, name="red"), wall


def family_split_reshard(blocks, batch):
    """A device split edge (routing by a device-computed column) whose
    first branch re-shards keyed, TPU -> TPU, into a two-replica keyed
    reduce (``TPUKeyByEmitter``); the second branch maps on."""
    from windflow_tpu.tpu import Map_TPU_Builder, Reduce_TPU_Builder

    tag = lambda f: {**f, "branch": f["value"] % 2}
    red = lambda a, b: {"key": b["key"], "value": a["value"] + b["value"],
                        "branch": b["branch"]}
    seven = lambda f: {**f, "value": f["value"] * 7}
    s0, s1 = ColumnSink(["key", "value"]), ColumnSink(["key", "value"])
    g = event_graph("smoke_split")
    mp = dev_source(g, blocks, batch) \
        .add(Map_TPU_Builder(tag).with_name("tag").build())
    mp.split("branch", 2)
    mp.select(0).add(Reduce_TPU_Builder(red).with_key_by("key")
                     .with_parallelism(2).with_name("red2").build()) \
      .add_sink(Sink_Builder(s0).with_columns().build())
    mp.select(1).add(Map_TPU_Builder(seven).with_name("x7").build()) \
      .add_sink(Sink_Builder(s1).with_columns().build())
    wall = timed_run(g)

    def cpu(mp, sinks):
        mp = mp.add(Map_Builder(tag).build())
        mp.split(lambda t: t["branch"], 2)
        mp.select(0).add(cpu_running_sum().with_parallelism(2).build()) \
          .add_sink(sinks[0])
        mp.select(1).add(Map_Builder(seven).build()).add_sink(sinks[1])

    c0, c1 = cpu_rows(blocks, cpu, sinks=2)
    red2 = replica_stats(g, name="red2")
    check(len(red2) == 2 and all(r["Inputs_received"] > 0 for r in red2),
          "split/re-shard: the keyed re-shard did not feed both replicas")
    n0 = same_rows("split branch 0 (keyed re-shard -> reduce totals)",
                   per_key_sum(*s0.columns()), per_key_last(*c0))
    n1 = same_rows("split branch 1 (map)", s1.columns(), c1)
    return n0 + n1, red2, wall


def family_ysb_chain(blocks, batch):
    """examples/ysb.py with YSB_DEVICE_CHAIN=1: view filter -> projection
    -> 10 s tumbling count per campaign, chained so the window program
    absorbs the prefix."""
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    views = lambda f: f["event_type"] == 0
    project = lambda f: {"campaign": f["campaign"],
                         "one": f["event_type"] * 0 + 1}
    sink = ColumnSink(["campaign", "wid", "count"], valid_field="valid")
    g = event_graph("smoke_ysb")
    dev_source(g, blocks, batch) \
        .add(Filter_TPU_Builder(views).with_name("views").build()) \
        .chain(Map_TPU_Builder(project).with_name("project").build()) \
        .chain(Ffat_Windows_TPU_Builder(
                   lambda f: {"count": f["one"]},
                   lambda a, b: {"count": a["count"] + b["count"]})
               .with_key_by("campaign")
               .with_tb_windows(YSB_WIN_US, YSB_WIN_US)
               .with_num_win_per_batch(32)
               .with_key_capacity(YSB_CAMPAIGNS).with_name("win").build()) \
        .add_sink(Sink_Builder(sink).with_columns().build())
    wall = timed_run(g)
    fused = replica_stats(g, kind="Fused_TPU_Chain")
    check(len(fused) == 1 and fused[0]["Fused_ops"] == 3,
          "ysb chain: filter, project and window did not fuse")
    want = cpu_ffat_windows(
        blocks, YSB_WIN_US, YSB_WIN_US, key_field="campaign",
        value_field="one",
        prefix=(Filter_Builder(views).build(), Map_Builder(project).build()))
    return same_rows("ysb fused filter->project->count", sink.columns(),
                     want), fused, wall


def stage_b(seed: int, n_keys: int, batch: int, n_batches: int) -> dict:
    blocks = ffat_stream(seed + 10, n_keys, batch, n_batches)
    running, = cpu_rows(blocks, lambda mp, s: mp.add(
        cpu_running_sum().build()).add_sink(s[0]))
    out = {}
    for name, run in (
            ("fused_chain", lambda: family_fused_chain(blocks, batch)),
            ("stateful_map",
             lambda: family_stateful_map(blocks, batch, running)[:3]),
            ("keyed_reduce",
             lambda: family_keyed_reduce(blocks, batch, running)),
            ("split_reshard", lambda: family_split_reshard(blocks, batch)),
            ("ysb_chain", lambda: family_ysb_chain(
                ysb_stream(seed + 11, batch, n_batches), batch))):
        rows, reps, wall = run()
        out[name] = {
            "rows_equal": rows, "run_s": wall,
            "Compile_count": stat_sum(reps, "Compile_count"),
            "Device_programs_run": stat_sum(reps, "Device_programs_run")}
        say(f"stage B {name}: {rows} rows equal to the CPU plane; "
            f"Compile_count={out[name]['Compile_count']} "
            f"Device_programs_run={out[name]['Device_programs_run']} "
            f"run={wall}s")
    return out


# ---------------------------------------------------------------------------
# stage D: four chips
# ---------------------------------------------------------------------------
def stage_d(seed: int, n_keys: int, batch: int, n_batches: int,
            b_keys: int, b_batch: int, b_batches: int,
            n_devices: int = 4, sample_keys: int = SAMPLE_KEYS) -> dict:
    blocks = ffat_stream(seed, n_keys, batch, n_batches)
    got, g, op, _ = run_ffat_graph(blocks, n_keys, batch,
                                   mesh_devices=n_devices)
    n_win = check_ffat_windows("stage D mesh FFAT", got, blocks, n_keys,
                               sample_keys, seed)
    out = {"ffat": _mesh_facts("mesh FFAT", replica_stats(g, name="ffat"),
                               op.replicas[0]._state, n_devices)}
    out["ffat"]["windows"] = n_win
    say(f"stage D mesh FFAT: {n_win} windows equal to stage A's fold; "
        f"{out['ffat']}")
    sblocks = ffat_stream(seed + 10, b_keys, b_batch, b_batches)
    running, = cpu_rows(sblocks, lambda mp, s: mp.add(
        cpu_running_sum().build()).add_sink(s[0]))
    rows, reps, _, mop = family_stateful_map(
        sblocks, b_batch, running, n_keys=b_keys, mesh_devices=n_devices)
    out["stateful_map"] = _mesh_facts("mesh stateful map", reps,
                                      mop.replicas[0]._table, n_devices)
    out["stateful_map"]["rows_equal"] = rows
    say(f"stage D mesh stateful map: {rows} rows equal to the CPU plane; "
        f"{out['stateful_map']}")
    return out


def _mesh_facts(what: str, reps, state, n_devices: int) -> dict:
    devs = device_set(state)
    facts = {"Mesh_devices": stat_sum(reps, "Mesh_devices"),
             "Mesh_shuffle_bytes": stat_sum(reps, "Mesh_shuffle_bytes"),
             "state_devices": len(devs)}
    check(facts["Mesh_devices"] == n_devices,
          f"{what}: Mesh_devices={facts['Mesh_devices']}, "
          f"want {n_devices}")
    check(len(devs) == n_devices,
          f"{what}: sharded state lives on {len(devs)} devices, "
          f"want {n_devices}")
    check(facts["Mesh_shuffle_bytes"] > 0, f"{what}: no shuffle bytes")
    return facts


# ---------------------------------------------------------------------------
class CompileMeter:
    """Counts JAX's persistent-cache hits/misses and backend compile time
    through the public ``jax.monitoring`` listeners."""

    def __init__(self):
        import jax.monitoring as m
        self.hits = self.misses = 0
        self.compile_s = 0.0
        m.register_event_listener(self._event)
        m.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out"))
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    from windflow_tpu.native import native_available, native_build_error
    from windflow_tpu.runtime.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    meter = CompileMeter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    if jax.default_backend() != "tpu":
        print("chip_smoke: no TPU (jax.default_backend() is "
              f"{jax.default_backend()!r}); this script has no CPU mode",
              file=sys.stderr)
        return 1
    say(f"native_available={native_available()} "
        f"native_build_error={native_build_error()!r}")

    summary = {"device": device, "seed": args.seed, "jax": jax.__version__,
               "compile_cache_dir": cache_dir, "stages": {}}
    stages = summary["stages"]

    stages["A"] = a = stage_a(args.seed, bench.HC_KEYS, bench.BATCH,
                              bench.N_BATCHES)
    check(a["Staging_pool_hits"] > 0,
          "stage A: the staging-buffer recycler never hit its pool")
    check(a["forest_devices"] == [str(devs[0])],
          f"stage A: forest on {a['forest_devices']}, want [{devs[0]}]")
    stages["B"] = stage_b(args.seed, B_KEYS, B_BATCH, B_BATCHES)
    if len(devs) >= 4:
        stages["D"] = stage_d(args.seed, bench.HC_KEYS, bench.BATCH,
                              bench.N_BATCHES, B_KEYS, B_BATCH, B_BATCHES)
    else:
        stages["D"] = {"skipped": f"{len(devs)} device"}
        say(f"stage D skipped: {len(devs)} device (not a pass)")

    wall = time.perf_counter() - t_start
    summary.update({
        "ok": True, "wall_s": round(wall, 2),
        "backend_compile_s": round(meter.compile_s, 2),
        "compile_share": round(meter.compile_s / wall, 3),
        "persistent_cache_hits": meter.hits,
        "persistent_cache_misses": meter.misses})
    say(f"wall={wall:.1f}s backend_compile={meter.compile_s:.1f}s "
        f"({100 * meter.compile_s / wall:.0f}% of wall) "
        f"persistent_cache hits={meter.hits} misses={meter.misses}")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke.jsonl"), "a") as f:
        f.write(json.dumps(summary) + "\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
