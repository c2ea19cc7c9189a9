"""Device-ahead dispatch pipeline (runtime/dispatch.py +
TPUReplicaBase.prep_device_batch): the host-prep / device-commit split
must never change RESULTS, only when work happens. These tests pin the
ordering contract — commits land before punctuations/EOS, in-flight
batches survive a flush, a failing commit discards the rest of the
pipeline and unwinds the graph — and the differential acceptance
criterion: ``WF_DISPATCH_DEPTH=0`` (synchronous) and depth >= 2 produce
identical window results on randomized window configs."""

import random

import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy, WindFlowError)
from windflow_tpu.runtime.dispatch import DeviceDispatchQueue, dispatch_depth

from common import DictWinCollector, TupleT, expected_windows


# ---------------------------------------------------------------------------
# queue unit semantics
# ---------------------------------------------------------------------------
def test_queue_defers_up_to_depth():
    q = DeviceDispatchQueue(depth=2)
    ran = []
    for i in range(5):
        q.submit(lambda i=i: ran.append(i))
    # depth 2: the three oldest overflowed and committed, two in flight
    assert ran == [0, 1, 2]
    assert len(q) == 2
    q.drain()
    assert ran == [0, 1, 2, 3, 4]
    assert len(q) == 0


def test_queue_depth_zero_is_synchronous():
    q = DeviceDispatchQueue(depth=0)
    ran = []
    q.submit(lambda: ran.append(1))
    assert ran == [1] and len(q) == 0


def test_queue_on_idle_reports_work():
    q = DeviceDispatchQueue(depth=4)
    assert q.on_idle() is False
    q.submit(lambda: None)
    assert q.on_idle() is True
    assert q.on_idle() is False


def test_queue_failing_commit_discards_rest():
    """A commit that raises aborts the pipeline: later entries were
    prepped against control-plane state the failed batch advanced, so
    they must NOT run afterwards."""
    q = DeviceDispatchQueue(depth=8)
    ran = []

    def boom():
        raise RuntimeError("synthetic commit failure")

    q.submit(boom)
    q.submit(lambda: ran.append("late"))
    with pytest.raises(RuntimeError, match="synthetic commit failure"):
        q.drain()
    assert len(q) == 0  # discarded, not pending
    q.drain()  # and a later drain is a clean no-op
    assert ran == []


def test_dispatch_depth_env(monkeypatch):
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "5")
    assert dispatch_depth() == 5
    assert DeviceDispatchQueue().depth == 5
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "not-a-number")
    assert dispatch_depth() == 2  # malformed knob falls back to default
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "-3")
    assert dispatch_depth() == 0  # clamped: negatives mean synchronous


def test_queue_stall_and_stage_counters():
    from windflow_tpu.monitoring.stats import StatsRecord

    st = StatsRecord("op", 0)
    q = DeviceDispatchQueue(stats=st, depth=2)
    for b in (1, 2):
        with q.prep(b):  # the wf:prep stage counts the batch
            pass
        q.submit(lambda: None, b)
    assert st.dispatch_batches == 2
    assert st.dispatch_host_prep_total_us > 0.0
    # CPU beside wall on prep (the thread's CPU clock, read on the first
    # of every CPU_EVERY spans and scaled: an estimate)
    assert st.stage_cpu_usec("prep") >= 0.0
    # the latency hook is bound only under sampling: none here
    assert q._st_prep._note is None
    assert st.dispatch_depth_max == 2
    assert st.dispatch_stalls == 0
    q.drain(forced=True)  # ordering-point drain with entries = a stall
    assert st.dispatch_stalls == 1
    assert st.dispatch_commit_total_us > 0.0
    q.drain(forced=True)  # empty forced drain is NOT a stall
    assert st.dispatch_stalls == 1
    d = st.to_dict()
    for field in ("Dispatch_host_prep_cpu_total_usec",
                  "Dispatch_commit_cpu_total_usec",
                  "Dispatch_readback_stalls", "Dispatch_queue_depth_max",
                  "Dispatch_batches", "Dispatch_host_prep_total_usec",
                  "Dispatch_commit_total_usec",
                  "Dispatch_queue_wait_total_usec"):
        assert field in d
    # the per-batch EWMAs went in PR 36: nothing read them
    assert "Dispatch_host_prep_usec" not in d
    assert "Dispatch_commit_usec" not in d
    assert d["Dispatch_batches"] == 2
    # both batches sat in the queue from submit until the drain
    assert d["Dispatch_queue_wait_total_usec"] > 0.0


# ---------------------------------------------------------------------------
# graph-level: EOS flush and error unwind with batches in flight
# ---------------------------------------------------------------------------
N_KEYS = 4
STREAM_LEN = 90
TS_STEP = 131
WIN_US, SLIDE_US = 1200, 400


def _make_src(n_keys, stream_len):
    def src(shipper, ctx):
        for i in range(stream_len):
            ts = i * TS_STEP
            for k in range(ctx.get_replica_index(), n_keys,
                           ctx.get_parallelism()):
                shipper.push_with_timestamp(TupleT(k, i + 1 + k, ts), ts)
            shipper.set_next_watermark(ts)
    return src


def _model(n_keys, stream_len):
    return {k: [(i + 1 + k, i * TS_STEP) for i in range(stream_len)]
            for k in range(n_keys)}


def _sum_or_none(vals):
    return sum(vals) if vals else None


def _run_ffat_graph(obs=32):
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

    coll = DictWinCollector()
    graph = PipeGraph("dispatch_eos", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    src = (Source_Builder(_make_src(N_KEYS, STREAM_LEN))
           .with_output_batch_size(obs).build())
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_num_win_per_batch(8).build())
    graph.add_source(src).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    return coll


def test_eos_flush_with_in_flight_batches(monkeypatch):
    """A depth far above the batch count keeps EVERY batch in flight
    until EOS: the terminate-time drain must commit them all (in order)
    before the partial-window flush, so the results still match the
    window model exactly."""
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "64")
    expected = expected_windows(_model(N_KEYS, STREAM_LEN), WIN_US,
                                SLIDE_US, False, _sum_or_none)
    coll = _run_ffat_graph()
    assert coll.dups == 0
    assert coll.results == expected


def test_error_unwind_mid_pipeline(monkeypatch):
    """A device commit that fails with batches queued behind it must
    unwind the graph (wait_end re-raises) instead of hanging — and the
    queued commits after the failure must not run (the queue aborts)."""
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "4")
    from windflow_tpu.tpu import Map_TPU_Builder

    graph = PipeGraph("dispatch_boom")
    src = (Source_Builder(
        lambda shipper, ctx: [shipper.push(TupleT(k % 3, k))
                              for k in range(200)])
        .with_output_batch_size(16).build())
    op = Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1}).build()

    orig_build = op.build_replicas
    committed = []

    def build_then_sabotage():
        orig_build()
        rep = op.replicas[0]
        orig_prep = rep.prep_device_batch
        seen = [0]

        def prep(batch):
            commit = orig_prep(batch)
            seen[0] += 1
            my = seen[0]

            def failing_commit():
                if my == 3:
                    raise WindFlowError("synthetic commit failure")
                commit()
                committed.append(my)

            return failing_commit

        rep.prep_device_batch = prep

    op.build_replicas = build_then_sabotage
    graph.add_source(src).add(op).add_sink(
        Sink_Builder(lambda t: None).build())
    with pytest.raises(WindFlowError, match="synthetic commit failure"):
        graph.run()
    # nothing past the failing batch committed (abort-on-error), and the
    # batches before it did
    assert committed and all(c < 3 for c in committed)


# ---------------------------------------------------------------------------
# differential: depth 0 == depth >= 2 on randomized window configs
# ---------------------------------------------------------------------------
def _drive_replica(depth, cfg, monkeypatch):
    """Feed one FfatTPUReplica a randomized keyed batch stream directly
    (no graph: the pipeline's deferral is the thing under test, so the
    driver controls exactly when drains happen) and return every emitted
    window row."""
    import jax

    from windflow_tpu.basic import WinType
    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU
    from windflow_tpu.tpu.schema import TupleSchema

    monkeypatch.setenv("WF_DISPATCH_DEPTH", str(depth))
    (n_keys, win, slide, lateness, n_batches, batch_size, seed) = cfg
    op = Ffat_Windows_TPU(
        lift=lambda f: {"value": f["value"]},
        combine=lambda a, b: {"value": a["value"] + b["value"]},
        key_extractor="key", win_len=win, slide_len=slide,
        win_type=WinType.TB, lateness=lateness, num_win_per_batch=8,
        key_capacity=4, name=f"diff_d{depth}")
    op.build_replicas()
    rep = op.replicas[0]

    rows = []

    class Sink:
        def emit_device_batch(self, b):
            n = b.size
            cols = {f: np.asarray(b.fields[f])[:n] for f in b.fields}
            for i in range(n):
                rows.append((int(cols["key"][i]), int(cols["wid"][i]),
                             int(cols["value"][i]), bool(cols["valid"][i])))

        def set_stats(self, s):
            pass

        def propagate_punctuation(self, wm):
            pass

        def flush(self):
            pass

    rep.emitter = Sink()
    schema = TupleSchema({"key": np.int32, "value": np.int32})
    rng = np.random.default_rng(seed)
    ts0 = 0
    for i in range(n_batches):
        keys = rng.integers(0, n_keys, batch_size).astype(np.int64)
        vals = rng.integers(0, 50, batch_size).astype(np.int32)
        ts = ts0 + np.cumsum(rng.integers(0, 7, batch_size)).astype(np.int64)
        ts0 = int(ts[-1]) + 1
        b = BatchTPU({"key": jax.device_put(keys.astype(np.int32)),
                      "value": jax.device_put(vals)}, ts, batch_size,
                     schema, wm=max(0, int(ts[-1]) - lateness),
                     host_keys=keys)
        rep.handle_msg(0, b)
        if i == n_batches // 2:
            # mid-stream punctuation: the drain-before-punct ordering
            # point fires with batches (possibly) in flight
            from windflow_tpu.message import make_punctuation
            rep.handle_msg(0, make_punctuation(b.wm))
    rep.terminate()
    return sorted(rows), rep.stats


@pytest.mark.parametrize("seed", [11, 23, 47])
def test_depth0_equals_depth2_randomized(seed, monkeypatch):
    """Acceptance differential: identical window results (keys, wids,
    values, validity) at WF_DISPATCH_DEPTH=0 and depth >= 2 over
    randomized window configs, including mid-stream punctuation and the
    EOS flush."""
    rng = random.Random(seed)
    slide = rng.choice([13, 40, 64])
    win = slide * rng.randint(1, 5)
    cfg = (rng.randint(2, 5), win, slide, rng.choice([0, 25]),
           rng.randint(6, 12), rng.choice([32, 64]), seed)
    r0, _ = _drive_replica(0, cfg, monkeypatch)
    r2, st2 = _drive_replica(2, cfg, monkeypatch)
    r8, st8 = _drive_replica(8, cfg, monkeypatch)
    assert r0, "config produced no windows — differential is vacuous"
    assert r0 == r2 == r8
    # depth >= 2 actually pipelined (otherwise this test proves nothing)
    assert st2.dispatch_depth_max >= 1
    assert st2.dispatch_batches == cfg[4]


def test_worker_idle_tick_commits_in_flight(monkeypatch):
    """A quiet stream must not park prepared batches: the worker's idle
    tick drains replica dispatch queues like the emitter FIFOs (the
    windows arrive without any further input, well before EOS)."""
    import threading
    import time as _time

    monkeypatch.setenv("WF_DISPATCH_DEPTH", "64")
    monkeypatch.setenv("WF_IDLE_DRAIN_MS", "20")
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

    coll = DictWinCollector()
    arrived = threading.Event()

    def sink(r):
        coll.sink(r)
        if coll.results:
            arrived.set()

    hold = threading.Event()

    def src(shipper, ctx):
        # enough stream time to make several windows fireable, then park
        # (no EOS until the main thread saw results via the idle tick)
        for i in range(60):
            ts = i * TS_STEP
            for k in range(2):
                shipper.push_with_timestamp(TupleT(k, 1, ts), ts)
            shipper.set_next_watermark(ts)
        hold.wait(timeout=30.0)

    graph = PipeGraph("dispatch_idle", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_num_win_per_batch(8).build())
    graph.add_source(Source_Builder(src).with_output_batch_size(16).build()) \
         .add(op).add_sink(Sink_Builder(sink).build())
    t = threading.Thread(target=graph.run, daemon=True)
    t.start()
    try:
        assert arrived.wait(timeout=20.0), (
            "no windows delivered while the source idled — the idle tick "
            "did not drain the dispatch queue")
    finally:
        hold.set()
        t.join(timeout=30.0)
