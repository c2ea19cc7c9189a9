"""Device-chain fusion (tpu/fused_ops.py): one XLA program per batch
across chained TPU operators.

Acceptance coverage:
- a fused ``Map_TPU -> Filter_TPU -> Map_TPU`` chain runs exactly ONE
  device program and ONE dispatch-queue commit per batch (asserted via
  ``Device_programs_run`` / ``Dispatch_batches``) with zero mid-chain
  host readbacks;
- the fused-vs-unfused (``WF_TPU_FUSION=0``) randomized differential
  delivers identical multisets, including stateful sub-ops, empty
  batches (a filter dropping whole batches mid-chain), punctuation
  interleavings, and EOS with in-flight commits (deep dispatch queue);
- fusion legality: keyed entries fuse only key-compatible keyed sub-ops,
  a global Reduce_TPU terminates the chain, and every refusal is
  recorded on the fallback stage and surfaced by ``describe()`` and the
  dataflow diagram.
"""

import random
import threading

import jax.numpy as jnp
import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.tpu import (Filter_TPU_Builder, Map_TPU_Builder,
                              Reduce_TPU_Builder)

from common import (GlobalSum, make_event_time_source, make_ingress_source,
                    make_sum_sink, rand_degree)

N_KEYS = 5
STREAM_LEN = 60


class RowCollector:
    """Thread-safe (key, value) multiset sink."""

    def __init__(self):
        self.rows = []
        self._lock = threading.Lock()

    def sink(self, t):
        if t is not None:
            with self._lock:
                self.rows.append((int(t.key), int(t.value)))

    @property
    def multiset(self):
        with self._lock:
            return sorted(self.rows)


def _three_op_chain(p, batch, collector, stateful=False,
                    drop_all_pred=False, event_time=False):
    """src -> [map -> filter -> map] -> sink; the device trio is built
    via chain() so it fuses when WF_TPU_FUSION allows."""
    g = PipeGraph("fusion", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME if event_time
                  else TimePolicy.INGRESS_TIME)
    src_fn = (make_event_time_source(N_KEYS, STREAM_LEN, seed=3)
              if event_time else make_ingress_source(N_KEYS, STREAM_LEN))
    src = (Source_Builder(src_fn).with_parallelism(2)
           .with_output_batch_size(batch).build())
    if stateful:
        def step(row, state):
            s2 = {"total": state["total"] + row["value"]}
            return {**row, "value": s2["total"]}, s2

        m1 = (Map_TPU_Builder(step).with_key_by("key")
              .with_state({"total": jnp.int32(0)})
              .with_name("m1").with_parallelism(p).build())
    else:
        m1 = (Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 3})
              .with_name("m1").with_parallelism(p).build())
    if drop_all_pred:
        # whole batches die mid-chain: the empty-batch path must stay
        # equivalent (unfused compacts to zero and drops the batch)
        flt = (Filter_TPU_Builder(lambda f: f["value"] < 0)
               .with_name("f1").with_parallelism(p).build())
    else:
        flt = (Filter_TPU_Builder(lambda f: f["value"] % 2 == 0)
               .with_name("f1").with_parallelism(p).build())
    m2 = (Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 7})
          .with_name("m2").with_parallelism(p).build())
    snk = Sink_Builder(collector.sink).build()
    g.add_source(src).add(m1).chain(flt).chain(m2).add_sink(snk)
    return g


def _fused_stage_stats(g):
    ops = [o for o in g.get_stats()["Operators"]
           if o["kind"] == "Fused_TPU_Chain"]
    assert len(ops) == 1, "expected exactly one fused device stage"
    return ops[0]


# ---------------------------------------------------------------------------
# one program / one commit per batch
# ---------------------------------------------------------------------------
def test_fused_chain_one_program_one_commit_per_batch(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    col = RowCollector()
    g = _three_op_chain(2, 16, col)
    g.run()
    # one stage for the whole device trio: threads = src + fused + sink
    assert g.get_num_threads() == 2 + 2 + 1
    op = _fused_stage_stats(g)
    assert op["name"] == "m1∘f1∘m2"
    total_batches = 0
    for r in op["replicas"]:
        assert r["Fused_ops"] == 3
        assert r["Device_batches_in"] > 0
        # exactly ONE XLA program and ONE dispatch commit per batch —
        # no mid-chain programs, no mid-chain readback commits
        assert r["Device_programs_run"] == r["Device_batches_in"]
        assert r["Dispatch_batches"] == r["Device_batches_in"]
        total_batches += r["Device_batches_in"]
    assert total_batches > 0
    expected = sorted(
        (k, 3 * v + 7) for k in range(N_KEYS)
        for v in range(1, STREAM_LEN + 1) if (3 * v) % 2 == 0)
    assert col.multiset == expected


def test_fusion_optout_restores_per_stage_wiring(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "0")
    col = RowCollector()
    g = _three_op_chain(2, 16, col)
    g.run()
    # three separate device stages again
    assert g.get_num_threads() == 2 + 3 * 2 + 1
    assert not any(o["kind"] == "Fused_TPU_Chain"
                   for o in g.get_stats()["Operators"])
    # and the fallback reason is visible on the unchained stages
    refused = [s for s in g._stages if s.chain_refused]
    assert refused and all("WF_TPU_FUSION" in s.chain_refused
                           for s in refused)


# ---------------------------------------------------------------------------
# fused-vs-unfused randomized differential
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [5, 19, 83])
def test_fused_vs_unfused_differential(seed, monkeypatch):
    rng = random.Random(seed)
    p = rand_degree(rng)
    batch = rng.choice([8, 16, 32])
    stateful = rng.random() < 0.5
    results = {}
    for fusion in ("1", "0"):
        monkeypatch.setenv("WF_TPU_FUSION", fusion)
        col = RowCollector()
        _three_op_chain(p, batch, col, stateful=stateful).run()
        results[fusion] = col.multiset
    assert results["1"] == results["0"]
    assert results["1"], "differential is vacuous on an empty stream"


def test_differential_empty_batches_and_punctuation(monkeypatch):
    """A filter dropping EVERY tuple mid-chain + event-time watermark
    punctuation interleavings: delivered multisets stay identical (here:
    empty) and the fused stage still ran its programs."""
    results = {}
    for fusion in ("1", "0"):
        monkeypatch.setenv("WF_TPU_FUSION", fusion)
        col = RowCollector()
        g = _three_op_chain(2, 8, col, drop_all_pred=True, event_time=True)
        g.run()
        results[fusion] = col.multiset
        if fusion == "1":
            op = _fused_stage_stats(g)
            assert sum(r["Device_programs_run"]
                       for r in op["replicas"]) > 0
    assert results["1"] == results["0"] == []


def test_differential_eos_with_inflight_commits(monkeypatch):
    """Deep dispatch queue: commits stay parked until the EOS drain, so
    result delivery rides the terminate path — multisets must still
    match the synchronous run exactly."""
    results = {}
    for fusion, depth in (("1", "64"), ("0", "64"), ("1", "0")):
        monkeypatch.setenv("WF_TPU_FUSION", fusion)
        monkeypatch.setenv("WF_DISPATCH_DEPTH", depth)
        col = RowCollector()
        _three_op_chain(1, 16, col, stateful=True).run()
        results[(fusion, depth)] = col.multiset
    assert results[("1", "64")] == results[("0", "64")] == results[("1", "0")]
    assert results[("1", "64")]


def test_differential_reduce_terminator(monkeypatch):
    """Global Reduce_TPU as the chain terminator: the fold consumes the
    in-program keep mask (no pre-reduce compaction) and must equal the
    unfused map->filter->reduce pipeline."""
    sums = {}
    for fusion in ("1", "0"):
        monkeypatch.setenv("WF_TPU_FUSION", fusion)
        acc = GlobalSum()
        g = PipeGraph("fusion_red", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)
        src = (Source_Builder(make_ingress_source(N_KEYS, STREAM_LEN))
               .with_parallelism(1).with_output_batch_size(16).build())
        m = (Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 2})
             .with_name("m").build())
        flt = (Filter_TPU_Builder(lambda f: f["value"] > 40)
               .with_name("f").build())
        red = (Reduce_TPU_Builder(
            lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
            .with_name("r").build())
        g.add_source(src).add(m).chain(flt).chain(red).add_sink(
            Sink_Builder(make_sum_sink(acc)).build())
        g.run()
        if fusion == "1":
            assert g.get_num_threads() == 1 + 1 + 1
        sums[fusion] = (acc.value, acc.count)
    assert sums["1"][0] == sums["0"][0]
    # per-batch fold: one output tuple per non-empty batch either way
    assert sums["1"][1] == sums["0"][1]


@pytest.mark.parametrize("terminator", ["filter", "reduce", "keyed_reduce"])
def test_differential_depth0_vs_depth2_ordered(terminator, monkeypatch):
    """The chains whose emit reads their program's fresh outputs hand
    that read back to the dispatch queue as a finish, run one launch
    later: at parallelism 1 the sink sees the SAME rows in the SAME
    order at WF_DISPATCH_DEPTH 0 (both halves inside submit) and 2."""
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    rows, deferred = {}, {}
    for depth in ("0", "2"):
        monkeypatch.setenv("WF_DISPATCH_DEPTH", depth)
        col = RowCollector()
        g = PipeGraph("fusion_depth", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)
        src = (Source_Builder(make_ingress_source(N_KEYS, STREAM_LEN))
               .with_parallelism(1).with_output_batch_size(16).build())
        m = (Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 3})
             .with_name("m").build())
        flt = (Filter_TPU_Builder(lambda f: f["value"] % 2 == 0)
               .with_name("f").build())
        pipe = g.add_source(src).add(m).chain(flt)
        if terminator != "filter":
            red = Reduce_TPU_Builder(
                lambda a, b: {"key": b["key"],
                              "value": a["value"] + b["value"]})
            if terminator == "keyed_reduce":
                red = red.with_key_by("key")
            pipe = pipe.chain(red.with_name("r").build())
        pipe.add_sink(Sink_Builder(col.sink).build())
        g.run()
        rows[depth] = list(col.rows)
        (rep,) = _fused_stage_stats(g)["replicas"]
        assert rep["Dispatch_readbacks"] == rep["Dispatch_batches"] > 2
        deferred[depth] = rep["Dispatch_readbacks_deferred"]
    assert rows["0"] == rows["2"] and rows["0"]
    assert deferred["0"] == 0 and deferred["2"] > 0


# ---------------------------------------------------------------------------
# legality + fallback diagnostics
# ---------------------------------------------------------------------------
def _mk_graph():
    g = PipeGraph("legal", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    src = (Source_Builder(make_ingress_source(2, 8))
           .with_output_batch_size(8).build())
    return g, g.add_source(src)


def test_keyed_subop_requires_compatible_entry(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    # forward entry + keyed stateful candidate: refuse (needs a shuffle)
    g, mp = _mk_graph()
    m = Map_TPU_Builder(lambda f: f).with_name("m").build()
    sm = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
          .with_state({"x": jnp.int32(0)}).with_name("sm").build())
    mp.add(m).chain(sm)
    stage = g._stages[-1]
    assert stage.describe() == "sm"
    assert "keyed" in stage.chain_refused
    assert "unchained" in stage.describe(diagnostics=True)

    # keyed entry + keyed candidate on a DIFFERENT key: refuse
    g2 = PipeGraph("legal2", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    src2 = (Source_Builder(make_ingress_source(2, 8))
            .with_output_batch_size(8).build())
    sm1 = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
           .with_state({"x": jnp.int32(0)}).with_name("sm1").build())
    sm2 = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("value")
           .with_state({"x": jnp.int32(0)}).with_name("sm2").build())
    g2.add_source(src2).add(sm1).chain(sm2)
    stage = g2._stages[-1]
    assert stage.describe() == "sm2"
    assert "keys differ" in stage.chain_refused

    # keyed entry + SAME key: fuses
    g3 = PipeGraph("legal3", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    src3 = (Source_Builder(make_ingress_source(2, 8))
            .with_output_batch_size(8).build())
    sma = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
           .with_state({"x": jnp.int32(0)}).with_name("sma").build())
    smb = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
           .with_state({"x": jnp.int32(0)}).with_name("smb").build())
    g3.add_source(src3).add(sma).chain(smb)
    assert g3._stages[-1].describe() == "sma∘smb"


def test_refusal_reason_reaches_dot_and_svg(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    g, mp = _mk_graph()
    m = Map_TPU_Builder(lambda f: f).with_name("m").build()
    red = (Reduce_TPU_Builder(
        lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
        .with_name("r").build())
    m2 = Map_TPU_Builder(lambda f: f).with_name("m2").build()
    col = RowCollector()
    mp.add(m).chain(red).chain(m2).add_sink(Sink_Builder(col.sink).build())
    assert g._stages[-2].chain_refused  # m2 refused onto the terminator
    assert "unchained" in g.to_dot()
    assert "unchained" in g.to_svg()
    # fused stages render as one ∘-joined node
    assert "m∘r" in g.to_dot()
