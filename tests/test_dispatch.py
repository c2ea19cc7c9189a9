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
from windflow_tpu.runtime.dispatch import (DeviceDispatchQueue,
                                           dispatch_depth, split_commit)

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
# the two-half contract: a commit may hand back its readback-and-emit as a
# finish, which the queue runs one launch later
# ---------------------------------------------------------------------------
def _split(log, i, boom=False):
    @split_commit
    def launch():
        log.append(("launch", i))

        def finish():
            if boom:
                raise RuntimeError("synthetic finish failure")
            log.append(("finish", i))

        return finish
    return launch


def _whole(log, i):
    return lambda: log.append(("whole", i))


def _play(q, log, script):
    """``s``: submit a split commit, ``w``: a whole one (numbered in
    submission order), ``d``: drain(forced), ``i``: idle tick."""
    n = 0
    for step in script:
        if step == "s":
            q.submit(_split(log, n), n)
            n += 1
        elif step == "w":
            q.submit(_whole(log, n), n)
            n += 1
        elif step == "d":
            q.drain(forced=True)
        else:
            q.on_idle()


L, F, W = "launch", "finish", "whole"
TWO_HALF_CASES = {
    # launch n+1 runs before finish n; two launches queued, one finish
    # pending
    "launch_ahead_depth2": (2, "ssss", [(L, 0), (L, 1), (F, 0)], 3),
    "launch_ahead_depth1": (1, "sss", [(L, 0), (L, 1), (F, 0)], 2),
    # the lag is one launch whatever the depth
    "launch_ahead_depth8": (8, "s" * 11, [(L, 0), (L, 1), (F, 0),
                                          (L, 2), (F, 1)], 9),
    # finishes leave in submission order, a drain runs every launch and
    # every finish
    "drain_runs_both_halves": (2, "ssssd",
                               [(L, 0), (L, 1), (F, 0), (L, 2), (F, 1),
                                (L, 3), (F, 2), (F, 3)], 0),
    "idle_tick_runs_both_halves": (4, "ssi",
                                   [(L, 0), (L, 1), (F, 0), (F, 1)], 0),
    # depth 0: both halves inside submit
    "depth0_both_inside_submit": (0, "ss",
                                  [(L, 0), (F, 0), (L, 1), (F, 1)], 0),
    # a commit that returns None emits inside its call: the pending
    # finish runs first
    "whole_never_overtakes": (1, "sww", [(L, 0), (F, 0), (W, 1)], 1),
    "mixed_kinds_keep_order": (2, "swsswd",
                               [(L, 0), (F, 0), (W, 1), (L, 2), (L, 3),
                                (F, 2), (F, 3), (W, 4)], 0),
    "whole_commits_unchanged": (2, "wwwd", [(W, 0), (W, 1), (W, 2)], 0),
    # one split commit, then quiet: nothing is parked past a drain
    "lone_split_then_drain": (2, "sd", [(L, 0), (F, 0)], 0),
}


@pytest.mark.parametrize("case", sorted(TWO_HALF_CASES))
def test_two_half_order(case):
    depth, script, expected, left = TWO_HALF_CASES[case]
    q = DeviceDispatchQueue(depth=depth)
    log = []
    _play(q, log, script)
    assert log == expected
    assert len(q) == left


READBACK_COUNT_CASES = {
    # (depth, script) -> (Dispatch_readbacks, Dispatch_readbacks_deferred)
    "steady_stream": (2, "s" * 12, (9, 9)),
    "stream_then_drain": (2, "sssssd", (5, 4)),
    "depth0_never_deferred": (0, "sssss", (5, 0)),
    "idle_tick_not_deferred": (2, "si", (1, 0)),
    "ahead_of_a_whole_commit_not_deferred": (1, "swsd", (2, 0)),
    "no_split_commit_no_readback": (2, "wwwwd", (0, 0)),
}


@pytest.mark.parametrize("case", sorted(READBACK_COUNT_CASES))
def test_readback_counters(case):
    from windflow_tpu.monitoring.stats import StatsRecord

    depth, script, expected = READBACK_COUNT_CASES[case]
    st = StatsRecord("op", 0)
    q = DeviceDispatchQueue(stats=st, depth=depth)
    _play(q, [], script)
    d = st.to_dict()
    assert (d["Dispatch_readbacks"],
            d["Dispatch_readbacks_deferred"]) == expected


@pytest.mark.parametrize("depth", [0, 2])
def test_finish_that_raises_aborts_the_rest(depth):
    q = DeviceDispatchQueue(depth=depth)
    log = []
    with pytest.raises(RuntimeError, match="synthetic finish failure"):
        q.submit(_split(log, 0, boom=True), 0)
        for i in (1, 2, 3):
            q.submit(_split(log, i), i)
        q.drain()
    assert len(q) == 0  # queued launches and the pending finish: gone
    q.drain()
    # depth 2: launch 1 ran, then finish 0 raised; depth 0: inside submit
    assert log == ([(L, 0)] if depth == 0 else [(L, 0), (L, 1)])


def test_abort_drops_the_pending_finish():
    q = DeviceDispatchQueue(depth=1)
    log = []
    _play(q, log, "ss")
    assert log == [(L, 0)] and len(q) == 2
    q.abort()
    assert len(q) == 0
    q.drain(forced=True)
    assert log == [(L, 0)]


def test_forced_drain_of_a_pending_finish_is_a_stall():
    from windflow_tpu.monitoring.stats import StatsRecord

    st = StatsRecord("op", 0)
    q = DeviceDispatchQueue(stats=st, depth=1)
    _play(q, [], "ss")
    q.drain(forced=True)
    assert st.dispatch_stalls == 1
    q.drain(forced=True)  # nothing left: not a stall
    assert st.dispatch_stalls == 1


def test_split_finish_spans_carry_their_own_batch():
    """A finish runs under ``wf:commit`` with ITS batch's id, not the
    id of the launch it follows; commit time is both halves' sum."""
    from windflow_tpu.monitoring.stats import StatsRecord

    st = StatsRecord("op", 0)
    q = DeviceDispatchQueue(stats=st, depth=1)
    _play(q, [], "sssd")
    # three launches and three finishes, a span each
    assert st.stage_count("commit") == 6


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


# ---------------------------------------------------------------------------
# the commits that compact: launch and finish one launch apart, results
# what they were
# ---------------------------------------------------------------------------
COMPACTING = ("filter", "stateful_filter", "map_filter_chain",
              "chain_reduce", "chain_keyed_reduce")


def _compacting_replica(kind, name):
    import jax.numpy as jnp

    from windflow_tpu.tpu.fused_ops import make_fused_replica
    from windflow_tpu.tpu.ops_tpu import Filter_TPU, Map_TPU, Reduce_TPU

    def keep(f):
        return f["value"] % 3 != 0

    def triple(f):
        return {**f, "value": f["value"] * 3 + 1}

    def add(a, b):
        return {"key": b["key"], "value": a["value"] + b["value"]}

    if kind == "filter":
        op = Filter_TPU(keep, name=name)
        op.build_replicas()
        return op.replicas[0]
    if kind == "stateful_filter":
        def every_other(row, state):
            n = state["n"] + 1
            return n % 2 == 0, {"n": n}

        op = Filter_TPU(every_other, name=name, key_extractor="key",
                        state_init={"n": jnp.int32(0)})
        op.build_replicas()
        return op.replicas[0]
    ops = [Map_TPU(triple, name=name + "_m"), Filter_TPU(keep,
                                                         name=name + "_f")]
    if kind == "chain_reduce":
        ops.append(Reduce_TPU(add, name=name + "_r"))
    elif kind == "chain_keyed_reduce":
        ops.append(Reduce_TPU(add, key_extractor="key", name=name + "_r"))
    return make_fused_replica(ops, 0)


class _Recorder:
    """Stands in for the emitter: every batch (columns, timestamps,
    watermark, host keys) and every punctuation, in the order they
    leave the replica."""

    def __init__(self):
        self.out = []

    def emit_device_batch(self, b):
        n = b.size
        keys = b.host_keys
        self.out.append((
            "batch", n,
            {f: np.asarray(v)[:n].tolist() for f, v in b.fields.items()},
            np.asarray(b.ts_host)[:n].tolist(), int(b.wm),
            None if keys is None else [int(k) for k in keys][:n]))

    def propagate_punctuation(self, wm):
        self.out.append(("punct", int(wm)))

    def set_stats(self, s):
        pass

    def flush(self):
        pass


def _compacting_batches(seed, n_batches, size=32):
    import jax

    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.schema import TupleSchema

    schema = TupleSchema({"key": np.int32, "value": np.int32})
    rng = np.random.default_rng(seed)
    ts0 = 0
    for i in range(n_batches):
        n = size if i % 3 else size - 5  # partial batches too
        keys = rng.integers(0, 4, size).astype(np.int64)
        vals = rng.integers(0, 60, size).astype(np.int32)
        ts = ts0 + np.cumsum(rng.integers(0, 7, size)).astype(np.int64)
        ts0 = int(ts[-1]) + 1
        yield BatchTPU({"key": jax.device_put(keys.astype(np.int32)),
                        "value": jax.device_put(vals)}, ts, n, schema,
                       wm=int(ts[n - 1]), host_keys=keys[:n])


def _drive_compacting(kind, depth, seed, monkeypatch):
    from windflow_tpu.message import make_punctuation

    monkeypatch.setenv("WF_DISPATCH_DEPTH", str(depth))
    rep = _compacting_replica(kind, f"{kind}_d{depth}")
    rec = rep.emitter = _Recorder()
    n_batches = 9
    for i, b in enumerate(_compacting_batches(seed, n_batches)):
        rep.handle_msg(0, b)
        if i == 4:
            rep.handle_msg(0, make_punctuation(b.wm))
    rep.terminate()
    return rec.out, rep.stats


@pytest.mark.parametrize("kind", COMPACTING)
@pytest.mark.parametrize("seed", [11, 23, 47])
def test_depth0_equals_depth2_compacting(kind, seed, monkeypatch):
    """The differential for the commits that split: identical batches
    (rows, order, timestamps, watermarks, keys) and punctuations, in the
    same order, at WF_DISPATCH_DEPTH 0, 2 and 8 — and at depth >= 1 the
    finishes did run one launch late."""
    o0, st0 = _drive_compacting(kind, 0, seed, monkeypatch)
    o2, st2 = _drive_compacting(kind, 2, seed, monkeypatch)
    o8, st8 = _drive_compacting(kind, 8, seed, monkeypatch)
    assert sum(1 for o in o0 if o[0] == "batch") >= 5, "vacuous"
    assert [o for o in o0 if o[0] == "punct"], "no punctuation went out"
    assert o0 == o2 == o8
    assert st0.dispatch_readbacks == st2.dispatch_readbacks == 9
    assert st0.dispatch_readbacks_deferred == 0
    # nine batches, a punctuation after the fifth, EOS: every finish but
    # the two the drains ran followed a later launch
    assert st2.dispatch_readbacks_deferred == 7
    assert st8.dispatch_readbacks_deferred == 7


def _ordering_point(rep, name):
    from windflow_tpu.message import make_punctuation

    if name == "drain_forced":
        rep.dispatch.drain(forced=True)
    elif name == "on_idle":
        assert rep.on_idle() is True
    elif name == "punctuation":
        rep.handle_msg(0, make_punctuation(rep.cur_wm))
    elif name == "snapshot_state":
        rep.snapshot_state()
    else:
        rep.terminate()


@pytest.mark.parametrize("point", ["drain_forced", "on_idle", "punctuation",
                                   "snapshot_state", "eos"])
@pytest.mark.parametrize("kind", ["filter", "map_filter_chain"])
def test_ordering_point_leaves_nothing_pending(kind, point, monkeypatch):
    """Three batches at depth 2: one launched with its finish pending,
    two queued. Every ordering point runs all three, launch and finish,
    in order, before anything else leaves (a punctuation last)."""
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "2")
    rep = _compacting_replica(kind, f"{kind}_{point}")
    rec = rep.emitter = _Recorder()
    sent = list(_compacting_batches(5, 3))
    for b in sent:
        rep.handle_msg(0, b)
    assert len(rep.dispatch) == 3 and rec.out == []
    _ordering_point(rep, point)
    assert len(rep.dispatch) == 0
    batches = [o for o in rec.out if o[0] == "batch"]
    assert [o[4] for o in batches] == [int(b.wm) for b in sent]
    if point == "punctuation":
        assert rec.out[-1] == ("punct", int(sent[-1].wm))
        assert rec.out[:-1] == batches


@pytest.mark.parametrize("kind", ["filter", "map"])
def test_readback_counters_in_get_stats(kind, monkeypatch):
    """A graph with a filter reports how many finishes ran and how many
    of them one launch late; a graph without one reports none."""
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "2")
    from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder

    graph = PipeGraph("dispatch_readbacks")
    src = (Source_Builder(
        lambda shipper, ctx: [shipper.push(TupleT(k % 3, k))
                              for k in range(320)])
        .with_output_batch_size(16).build())
    if kind == "filter":
        op = (Filter_TPU_Builder(lambda f: f["value"] % 2 == 0)
              .with_name("dev").build())
    else:
        op = (Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1})
              .with_name("dev").build())
    seen = []
    graph.add_source(src).add(op).add_sink(
        Sink_Builder(lambda t: seen.append(t)).build())
    graph.run()
    (dev,) = [o for o in graph.get_stats()["Operators"]
              if o["name"] == "dev"]
    (rep,) = dev["replicas"]
    assert rep["Dispatch_batches"] == 20
    if kind == "filter":
        assert rep["Dispatch_readbacks"] == 20
        # idle ticks and punctuations may drain a few early
        assert 0 < rep["Dispatch_readbacks_deferred"] <= 19
        assert len([t for t in seen if t is not None]) == 160
    else:
        assert rep["Dispatch_readbacks"] == 0
        assert rep["Dispatch_readbacks_deferred"] == 0


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
