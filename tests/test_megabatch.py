"""Megabatch device-resident loop + chain terminators (PR 12).

Covers the three tentpole rungs end-to-end:

- FFAT-in-chain: ``map [-> filter] -> Ffat_Windows_TPU`` fuses into ONE
  composed program per batch (the prefix's per-batch programs vanish —
  asserted against the unfused run's per-stage ``Device_programs_run``),
  with randomized/late-event differentials exactly equal to the unfused
  pipeline;
- single-chip KEYBY fusion: a keyed ``Reduce_TPU`` terminates the chain
  at parallelism 1 (in-program sort/segment, no host keyby emitter hop)
  with exact differentials including whole-batch filter kills;
- megabatch scan loop: ``WF_MEGABATCH=K`` coalesces same-signature
  queued commits into one ``lax.scan`` dispatch — differentials stay
  exact across K in {0, 1, 4, 8}, EOS/checkpoint/supervision ordering
  points drain to K=1, and the ``Megabatch_*`` / ``Programs_per_batch``
  stats report the amortization.

Queue-grouping units run against fake commits (no device work).
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, RestartPolicy,
                          Sink_Builder, Source_Builder, TimePolicy)
from windflow_tpu.runtime.dispatch import DeviceDispatchQueue, megabatch_k
from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder, Filter_TPU_Builder,
                              Map_TPU_Builder, Reduce_TPU_Builder)

from common import TupleT

N_KEYS = 5
TS_STEP = 137
WIN_US, SLIDE_US = 1000, 400


# ---------------------------------------------------------------------------
# queue grouping units (fake commits, no device)
# ---------------------------------------------------------------------------
class _FakeCommit:
    """Commit thunk carrying the scan attributes fused_ops attaches."""

    def __init__(self, log, tag, sig):
        self._log, self._tag = log, tag
        if sig is not None:
            self.scan_sig = sig
            self.scan_runner = self._runner

    def __call__(self):
        self._log.append(("single", self._tag))

    def _runner(self, commits):
        self._log.append(("group", [c._tag for c in commits]))


def test_megabatch_env_knob(monkeypatch):
    monkeypatch.delenv("WF_MEGABATCH", raising=False)
    assert megabatch_k() == 1
    monkeypatch.setenv("WF_MEGABATCH", "0")
    assert megabatch_k() == 1  # 0 and 1 both mean off
    monkeypatch.setenv("WF_MEGABATCH", "16")
    assert megabatch_k() == 16
    monkeypatch.setenv("WF_MEGABATCH", "not-a-number")
    assert megabatch_k() == 1  # malformed knob must not kill the graph


def test_queue_depth_rides_to_megabatch():
    # a K-wide group needs K commits in the queue
    assert DeviceDispatchQueue(depth=2, megabatch=8).depth == 8
    assert DeviceDispatchQueue(depth=16, megabatch=4).depth == 16
    # synchronous mode wins: commits never queue at all
    assert DeviceDispatchQueue(depth=0, megabatch=8).depth == 0


def test_queue_pow2_front_runs():
    """Overflow pops the largest power-of-two same-signature FRONT run
    as one group; drain() always runs singles (ordering points force
    K=1); order is preserved throughout."""
    log = []
    q = DeviceDispatchQueue(depth=4, megabatch=4)
    for i in range(11):
        q.submit(_FakeCommit(log, i, sig="A"))
    q.drain(forced=True)
    tags = []
    for kind, payload in log:
        tags.extend(payload if kind == "group" else [payload])
    assert tags == list(range(11))  # submission order, no reordering
    assert ("group", [0, 1, 2, 3]) in log
    # everything still queued at the EOS drain ran as singles
    drained = log[log.index(("group", [0, 1, 2, 3])) + 1:]
    assert all(k == "single" or len(p) in (2, 4)
               for k, p in drained)
    assert log[-1][0] == "single"


def test_queue_mixed_signatures_run_single():
    log = []
    q = DeviceDispatchQueue(depth=2, megabatch=4)
    sigs = ["A", "B", "A", "B", "A", "B"]
    for i, s in enumerate(sigs):
        q.submit(_FakeCommit(log, i, sig=s))
    q.drain()
    assert all(kind == "single" for kind, _ in log)
    assert [t for _, t in log] == list(range(6))


def test_queue_unfused_commits_run_single():
    log = []
    q = DeviceDispatchQueue(depth=2, megabatch=8)
    for i in range(6):
        q.submit(_FakeCommit(log, i, sig=None))  # no scan attrs
    q.drain()
    assert all(kind == "single" for kind, _ in log)


def test_queue_megabatch_off_runs_single():
    log = []
    q = DeviceDispatchQueue(depth=4, megabatch=1)
    for i in range(9):
        q.submit(_FakeCommit(log, i, sig="A"))
    q.drain()
    assert all(kind == "single" for kind, _ in log)
    assert [t for _, t in log] == list(range(9))


def test_queue_partial_run_truncates_to_pow2():
    """A front run of 3 same-sig commits groups as 2 + 1 single."""
    log = []
    q = DeviceDispatchQueue(depth=3, megabatch=4)  # depth rides to 4
    for i, s in enumerate(["A", "A", "A", "B", "B"]):
        q.submit(_FakeCommit(log, i, sig=s))  # 5th submit overflows
    q.drain()
    assert log[0] == ("group", [0, 1])
    assert all(kind == "single" for kind, _ in log[1:])
    assert [t for _, t in log[1:]] == [2, 3, 4]


# ---------------------------------------------------------------------------
# FFAT window terminator: map [-> filter] -> Ffat_Windows_TPU as ONE
# program per batch, differential vs the unfused pipeline
# ---------------------------------------------------------------------------
class DictWinCollector:
    def __init__(self):
        self._lock = threading.Lock()
        self.results = {}

    def sink(self, r):
        if r is None:
            return
        with self._lock:
            self.results[(r["key"], r["wid"])] = (
                r["value"] if r["valid"] else None)


def _ffat_src(stream_len, disorder=0, seed=7):
    import random
    rng = random.Random(seed)

    def src(shipper, ctx):
        for i in range(stream_len):
            ts = i * TS_STEP
            if disorder:
                ts = max(0, ts - rng.randint(0, disorder))
            for k in range(N_KEYS):
                shipper.push_with_timestamp(TupleT(k, i + 1 + k, ts), ts)
            shipper.set_next_watermark(max(0, i * TS_STEP - disorder))
    return src


def _run_ffat_chain(monkeypatch, fusion, with_filter, stream_len=90,
                    disorder=0, megabatch="0"):
    monkeypatch.setenv("WF_TPU_FUSION", fusion)
    monkeypatch.setenv("WF_MEGABATCH", megabatch)
    # the callers compare program counts of two runs: a batch's programs
    # follow the windows it fires, so no partial
    # batch may ship by wall-clock age and move the batch boundaries
    monkeypatch.setenv("WF_MAX_STAGING_MS", "0")
    coll = DictWinCollector()
    g = PipeGraph("ffat_chain", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    src = (Source_Builder(_ffat_src(stream_len, disorder))
           .with_output_batch_size(32).build())
    mp = g.add_source(src).add(
        Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 2})
        .with_name("m").build())
    if with_filter:
        mp = mp.chain(Filter_TPU_Builder(lambda f: f["value"] % 4 == 0)
                      .with_name("flt").build())
    w = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
         .with_key_by("key").with_num_win_per_batch(8)
         .with_tb_windows(WIN_US, SLIDE_US).with_name("ffat").build())
    mp.chain(w).add_sink(Sink_Builder(coll.sink).build())
    g.run()
    ops = g.get_stats()["Operators"]
    return coll.results, {o["name"]: o for o in ops}


@pytest.mark.parametrize("with_filter", [False, True])
def test_ffat_chain_differential(monkeypatch, with_filter):
    fused_res, fstats = _run_ffat_chain(monkeypatch, "1", with_filter)
    plain_res, pstats = _run_ffat_chain(monkeypatch, "0", with_filter)
    assert fused_res == plain_res
    assert len(fused_res) > 50  # real windows fired, not a vacuous pass

    chain_name = "m∘flt∘ffat" if with_filter else "m∘ffat"
    assert chain_name in fstats
    frep = fstats[chain_name]["replicas"][0]
    assert frep["Fused_ops"] == (3 if with_filter else 2)
    # ACCEPTANCE: the chain runs ONE composed program per batch — the
    # prefix's own per-batch programs vanish, so the fused chain's
    # program count matches the bare unfused FFAT stage (plus, with a
    # filter, one prep-time mask program per batch for exact liveness).
    unfused_ffat = pstats["ffat"]["replicas"][0]["Device_programs_run"]
    unfused_map = pstats["m"]["replicas"][0]["Device_programs_run"]
    assert unfused_map > 0
    if not with_filter:
        assert frep["Device_programs_run"] == unfused_ffat
    else:
        assert frep["Device_programs_run"] < (
            unfused_ffat + unfused_map
            + pstats["flt"]["replicas"][0]["Device_programs_run"])


def test_ffat_chain_late_events_differential(monkeypatch):
    fused_res, _ = _run_ffat_chain(monkeypatch, "1", True, disorder=300)
    plain_res, _ = _run_ffat_chain(monkeypatch, "0", True, disorder=300)
    assert fused_res == plain_res
    assert len(fused_res) > 50


# ---------------------------------------------------------------------------
# single-chip KEYBY fusion: keyed Reduce_TPU terminates the chain
# ---------------------------------------------------------------------------
def _run_kreduce(monkeypatch, fusion, with_filter, drop_all=False,
                 megabatch="0", stream_len=60):
    monkeypatch.setenv("WF_TPU_FUSION", fusion)
    monkeypatch.setenv("WF_MEGABATCH", megabatch)
    acc, lock = {}, threading.Lock()

    def sink(t):
        if t is not None:
            with lock:
                acc[t.key] = acc.get(t.key, 0) + t.value

    g = PipeGraph("kred_chain", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)

    def src(shipper, ctx):
        for i in range(stream_len):
            for k in range(N_KEYS):
                shipper.push(TupleT(k, i + 1 + k))

    mp = g.add_source(Source_Builder(src).with_output_batch_size(16)
                      .build()) \
          .add(Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1})
               .with_name("m").build())
    if with_filter:
        pred = ((lambda f: f["value"] < 0) if drop_all
                else (lambda f: f["value"] % 3 != 0))
        mp = mp.chain(Filter_TPU_Builder(pred).with_name("kf").build())
    red = (Reduce_TPU_Builder(
        lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
        .with_key_by("key").with_name("kr").build())
    mp.chain(red).add_sink(Sink_Builder(sink).build())
    g.run()
    ops = g.get_stats()["Operators"]
    fused = [o for o in ops if o["kind"] == "Fused_TPU_Chain"]
    return acc, fused


@pytest.mark.parametrize("with_filter", [False, True])
def test_kreduce_chain_differential(monkeypatch, with_filter):
    fused_acc, fused = _run_kreduce(monkeypatch, "1", with_filter)
    plain_acc, plain = _run_kreduce(monkeypatch, "0", with_filter)
    assert fused_acc == plain_acc and len(fused_acc) == N_KEYS
    assert len(fused) == 1 and not plain
    r = fused[0]["replicas"][0]
    # ACCEPTANCE: one program per batch — the keyed shuffle degenerated
    # to an in-program sort/segment, no host keyby emitter hop
    assert r["Device_programs_run"] == r["Dispatch_batches"]


def test_kreduce_chain_drop_all_batches(monkeypatch):
    """A filter killing every row mid-chain: the fused kreduce must emit
    nothing, exactly like the unfused pipeline."""
    fused_acc, fused = _run_kreduce(monkeypatch, "1", True, drop_all=True)
    plain_acc, _ = _run_kreduce(monkeypatch, "0", True, drop_all=True)
    assert fused_acc == plain_acc == {}
    assert len(fused) == 1


# ---------------------------------------------------------------------------
# megabatch scan loop: differential + stats across K
# ---------------------------------------------------------------------------
def _run_three_op(monkeypatch, megabatch, stream_len=240):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    monkeypatch.setenv("WF_MEGABATCH", megabatch)
    rows, lock = [], threading.Lock()

    def sink(t):
        if t is not None:
            with lock:
                rows.append((int(t.key), int(t.value)))

    g = PipeGraph("mb", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)

    def src(shipper, ctx):
        for i in range(stream_len):
            for k in range(N_KEYS):
                shipper.push(TupleT(k, i + 1 + k))

    g.add_source(Source_Builder(src).with_output_batch_size(16).build()) \
     .add(Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 3})
          .with_name("m1").build()) \
     .chain(Filter_TPU_Builder(lambda f: f["value"] % 2 == 0)
            .with_name("f1").build()) \
     .chain(Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 7})
            .with_name("m2").build()) \
     .add_sink(Sink_Builder(sink).build())
    g.run()
    ops = g.get_stats()["Operators"]
    fused = next(o for o in ops if o["kind"] == "Fused_TPU_Chain")
    return sorted(rows), fused["replicas"][0]


def test_megabatch_differential_and_stats(monkeypatch):
    base, r0 = _run_three_op(monkeypatch, "0")
    assert r0["Megabatch_loops"] == 0
    for k in ("1", "4", "8"):
        got, r = _run_three_op(monkeypatch, k)
        assert got == base, f"megabatch K={k} differential mismatch"
        if k == "1":
            # opt-out: no scan groups ever form
            assert r["Megabatch_loops"] == 0
            assert r["Programs_per_batch"] == 1.0
        else:
            assert r["Megabatch_loops"] > 0
            assert r["Megabatch_max"] <= int(k)
            assert r["Megabatch_batches_per_loop_avg"] >= 2.0
            # the whole point: strictly fewer host dispatches than
            # batches (Programs_per_batch < 1 = amortized dispatch)
            assert r["Programs_per_batch"] < 1.0


def test_megabatch_stateful_eos_inflight(monkeypatch):
    """Stateful fused chain under a deep queue + megabatch: EOS with a
    queue full of in-flight commits drains to singles and the carried
    grid tables thread through the scan exactly."""
    monkeypatch.setenv("WF_DISPATCH_DEPTH", "64")

    def run(megabatch):
        monkeypatch.setenv("WF_TPU_FUSION", "1")
        monkeypatch.setenv("WF_MEGABATCH", megabatch)
        rows, lock = [], threading.Lock()

        def sink(t):
            if t is not None:
                with lock:
                    rows.append((int(t.key), int(t.value)))

        g = PipeGraph("mb_state", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)

        def src(shipper, ctx):
            # enough batches to overflow the 64-deep queue mid-stream
            # (groups form) while EOS still finds it near-full (singles)
            for i in range(600):
                for k in range(N_KEYS):
                    shipper.push(TupleT(k, i + 1 + k))

        def step(row, state):
            s2 = {"total": state["total"] + row["value"]}
            return {**row, "value": s2["total"]}, s2

        g.add_source(Source_Builder(src).with_output_batch_size(16)
                     .build()) \
         .add(Map_TPU_Builder(step).with_key_by("key")
              .with_state({"total": jnp.int32(0)}).with_name("sm").build()) \
         .chain(Filter_TPU_Builder(lambda f: f["value"] % 2 == 0)
                .with_name("sf").build()) \
         .add_sink(Sink_Builder(sink).build())
        g.run()
        ops = g.get_stats()["Operators"]
        fused = next(o for o in ops if o["kind"] == "Fused_TPU_Chain")
        return sorted(rows), fused["replicas"][0]

    base, _ = run("0")
    got, r = run("8")
    assert got == base
    assert r["Megabatch_loops"] > 0  # groups really formed mid-stream


# ---------------------------------------------------------------------------
# ordering points under megabatch: checkpoint/restore + supervision
# ---------------------------------------------------------------------------
class _ReplaySource:
    """Replayable source: crashes at ``crash_at`` the first
    ``crash_times`` times, checkpoint requested at ``ckpt_at``."""

    def __init__(self, n, nk=5, ckpt_at=None, crash_at=None,
                 crash_times=None):
        self.n, self.nk = n, nk
        self.ckpt_at, self.crash_at = ckpt_at, crash_at
        self.crash_times = crash_times
        self.crashes = 0
        self.pos = 0

    def __call__(self, shipper):
        while self.pos < self.n:
            if self.crash_at is not None and self.pos == self.crash_at \
                    and (self.crash_times is None
                         or self.crashes < self.crash_times):
                self.crashes += 1
                raise ValueError(f"injected crash #{self.crashes}")
            v = self.pos
            shipper.push({"k": v % self.nk, "v": v})
            self.pos += 1
            if self.ckpt_at is not None and self.pos == self.ckpt_at:
                assert shipper.request_checkpoint() is not None

    def snapshot_position(self):
        return self.pos

    def restore(self, pos):
        self.pos = pos


def _stateful_chain_graph(store, src, results, supervised=False):
    """Stateful map ∘ filter ∘ map fused chain with an idempotent
    per-key-max sink (running prefix sums are strictly increasing)."""
    g = PipeGraph("ck_mb", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.with_checkpointing(store_dir=store)
    if supervised:
        g.with_supervision(RestartPolicy(max_restarts=4, backoff_s=0.02,
                                         backoff_max_s=0.1))
    smap = (Map_TPU_Builder(
        lambda row, state: ({"k": row["k"], "v": row["v"] + state["acc"]},
                            {"acc": state["acc"] + row["v"]}))
        .with_key_by("k").with_state({"acc": np.int64(0)})
        .with_name("smap").build())
    flt = (Filter_TPU_Builder(lambda f: f["v"] % 3 != 0)
           .with_name("fodd").build())
    mtail = (Map_TPU_Builder(lambda f: {**f, "v": f["v"] * 2})
             .with_name("mtail").build())

    def sink(t):
        if t is not None:
            k, v = int(t["k"]), int(t["v"])
            results[k] = max(v, results.get(k, -1))

    g.add_source(Source_Builder(src).with_name("src")
                 .with_output_batch_size(64).build()) \
        .add(smap).chain(flt).chain(mtail) \
        .add_sink(Sink_Builder(sink).with_name("snk").build())
    return g


def test_megabatch_checkpoint_kill_restore(tmp_path, monkeypatch):
    """Checkpoint lands mid-megabatch-stream: the snapshot drains the
    queue to singles, the blob is the same as the unbatched plane's, and
    the restored run converges to the unbatched golden."""
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    monkeypatch.setenv("WF_MEGABATCH", "0")
    golden = {}
    _stateful_chain_graph(str(tmp_path / "gold"), _ReplaySource(2000),
                          golden).run()

    monkeypatch.setenv("WF_MEGABATCH", "8")
    store = str(tmp_path / "store")
    crash_res = {}
    g = _stateful_chain_graph(
        store, _ReplaySource(2000, ckpt_at=600, crash_at=1200), crash_res)
    assert any(s.is_fused_tpu for s in g._stages)
    with pytest.raises(ValueError, match="injected crash"):
        g.run()
    assert g._coordinator.completed == 1

    restore_res = {}
    g2 = _stateful_chain_graph(store, _ReplaySource(2000), restore_res)
    g2.run(restore_from=store)
    merged = {k: max(crash_res.get(k, -1), restore_res.get(k, -1))
              for k in set(crash_res) | set(restore_res)}
    assert merged == golden and len(golden) > 0


def test_megabatch_kill_under_supervision(tmp_path, monkeypatch):
    """Supervised in-process restart mid-megabatch: the error unwind
    aborts the queued group, the rebuild restores from the checkpoint,
    and the healed run equals the unbatched golden."""
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    monkeypatch.setenv("WF_MEGABATCH", "0")
    golden = {}
    _stateful_chain_graph(str(tmp_path / "gold"), _ReplaySource(1600),
                          golden).run()

    monkeypatch.setenv("WF_MEGABATCH", "8")
    results = {}
    g = _stateful_chain_graph(
        str(tmp_path / "run"),
        _ReplaySource(1600, ckpt_at=500, crash_at=1000, crash_times=1),
        results, supervised=True)
    g.run()  # no exception, no manual restore_from
    assert results == golden
    assert g.get_stats()["Supervision"]["Supervision_restarts"] == 1


# ---------------------------------------------------------------------------
# prewarm covers the scan programs: Compile_count flat under megabatch
# ---------------------------------------------------------------------------
def test_megabatch_prewarm_compile_count_flat(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    monkeypatch.setenv("WF_MEGABATCH", "4")
    sch = {"key": np.int32, "value": np.int32}
    seen = [0]
    g = PipeGraph("pw_mb", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.with_prewarm()

    def src(shipper, ctx):
        rng = np.random.default_rng(5)
        for _ in range(120):
            n = int(rng.integers(1, 33))
            shipper.push_columns(
                {"key": rng.integers(0, 8, n).astype(np.int32),
                 "value": rng.integers(0, 100, n).astype(np.int32)})

    g.add_source(Source_Builder(src).with_name("s")
                 .with_output_batch_size(32).build()) \
     .add(Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1})
          .with_schema(sch).with_name("m1").build()) \
     .chain(Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 3})
            .with_schema(sch).with_name("m2").build()) \
     .add_sink(Sink_Builder(lambda t: seen.__setitem__(0, seen[0] + 1)
                            if t else None).with_name("k").build())
    g.run()
    rep = g.prewarm_report
    assert rep is not None and rep["signatures_compiled"] > 0
    st = g.get_stats()
    fused = next(o for o in st["Operators"]
                 if o["kind"] == "Fused_TPU_Chain")
    r = fused["replicas"][0]
    # every stream program — singles AND scan groups — was pre-warmed:
    # Compile_count stays flat after warm-up
    total_compiles = sum(rr.get("Compile_count", 0)
                         for o in st["Operators"] for rr in o["replicas"])
    assert total_compiles == rep["signatures_compiled"]
    assert r["Compile_cache_hits"] > 0
    assert seen[0] > 0


# ---------------------------------------------------------------------------
# legality diagnostics for the new terminator roles
# ---------------------------------------------------------------------------
def _legal_graph(n=8):
    g = PipeGraph("legal_mb", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for i in range(n):
            shipper.push_with_timestamp(TupleT(i % 2, i, i * 100), i * 100)
            shipper.set_next_watermark(i * 100)
    return g, g.add_source(Source_Builder(src)
                           .with_output_batch_size(8).build())


def _ffat_op(p=1, name="w"):
    return (Ffat_Windows_TPU_Builder(
        lambda f: {"value": f["value"]},
        lambda a, b: {"value": a["value"] + b["value"]})
        .with_key_by("key").with_num_win_per_batch(4)
        .with_tb_windows(WIN_US, SLIDE_US).with_name(name)
        .with_parallelism(p).build())


def test_window_terminator_legality_diagnostics(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")
    # stateless prefix + window at p=1: fuses into one stage
    g, mp = _legal_graph()
    m = Map_TPU_Builder(lambda f: f).with_name("m").build()
    mp.add(m).chain(_ffat_op())
    assert g._stages[-1].describe() == "m∘w"

    # chaining PAST a window terminator: refused (window non-terminal)
    g2, mp2 = _legal_graph()
    m2 = Map_TPU_Builder(lambda f: f).with_name("m2").build()
    tail = Map_TPU_Builder(lambda f: f).with_name("tail").build()
    mp2.add(m2).chain(_ffat_op()).chain(tail)
    stage = g2._stages[-1]
    assert stage.describe() == "tail"
    assert "window non-terminal position" in stage.chain_refused
    assert "unchained" in stage.describe(diagnostics=True)

    # window terminator at parallelism 2: needs a cross-device KEYBY
    g3, mp3 = _legal_graph()
    m3 = (Map_TPU_Builder(lambda f: f).with_name("m3")
          .with_parallelism(2).build())
    mp3.add(m3).chain(_ffat_op(p=2, name="w2"))
    stage = g3._stages[-1]
    assert stage.describe() == "w2"
    assert "cross-device KEYBY" in stage.chain_refused

    # stateful prefix: the window terminator needs a STATELESS prefix
    g4, mp4 = _legal_graph()
    sm = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
          .with_state({"x": jnp.int32(0)}).with_name("sm").build())
    mp4.add(sm).chain(_ffat_op(name="w4"))
    stage = g4._stages[-1]
    assert stage.describe() == "w4"
    assert "stateless map/filter prefix" in stage.chain_refused


def test_keyed_terminator_legality_diagnostics(monkeypatch):
    monkeypatch.setenv("WF_TPU_FUSION", "1")

    def kred(p=1, name="kr"):
        return (Reduce_TPU_Builder(
            lambda a, b: {"key": b["key"],
                          "value": a["value"] + b["value"]})
            .with_key_by("key").with_name(name)
            .with_parallelism(p).build())

    # keyed reduce at p=1 terminates the chain (single-chip KEYBY)
    g, mp = _legal_graph()
    m = Map_TPU_Builder(lambda f: f).with_name("m").build()
    mp.add(m).chain(kred())
    assert g._stages[-1].describe() == "m∘kr"

    # at parallelism 2 the shuffle is real: refuse with the diagnosis
    g2, mp2 = _legal_graph()
    m2 = (Map_TPU_Builder(lambda f: f).with_name("m2")
          .with_parallelism(2).build())
    mp2.add(m2).chain(kred(p=2, name="kr2"))
    stage = g2._stages[-1]
    assert stage.describe() == "kr2"
    assert "cross-device KEYBY" in stage.chain_refused

    # mixed parallelism names the re-shard
    g3, mp3 = _legal_graph()
    m3 = Map_TPU_Builder(lambda f: f).with_name("m3").build()
    mp3.add(m3).chain(Map_TPU_Builder(lambda f: f).with_name("m4")
                      .with_parallelism(2).build())
    assert "mixed parallelism" in g3._stages[-1].chain_refused
    assert "re-shard" in g3._stages[-1].chain_refused
