"""Pipelined D2H on the device-plane edges (TPUExitEmitter /
TPUSplittingEmitter FIFOs): ordering and drain semantics. A synchronous
fetch of a fresh device buffer has a fixed cost whatever its size, so
both emitters hold a small FIFO of batches with async host copies in
flight; these tests pin down when the FIFO MUST drain (single-row emits,
punctuations, flush/EOS) so rows never reorder and watermarks stay
monotone."""

import numpy as np
import pytest

from windflow_tpu.basic import ExecutionMode
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.schema import TupleSchema


@pytest.fixture(autouse=True)
def _no_age_bound(monkeypatch):
    """These tests pin exact FIFO depth semantics; the wall-clock age
    bound (WF_PIPELINE_MAX_AGE_MS) would evict heads during slow first
    compiles, so disable it except where a test re-enables it."""
    monkeypatch.setenv("WF_PIPELINE_MAX_AGE_MS", "0")


class RecordingInner:
    """Stands in for the wrapped CPU emitter."""

    def __init__(self):
        self.events = []
        self.num_dests = 1
        self.output_batch_size = 0
        self.execution_mode = ExecutionMode.DEFAULT
        self.stats = None
        self.ports = []

    def emit(self, payload, ts, wm, msg_id=None):
        self.events.append(("row", payload["v"], wm))

    def propagate_punctuation(self, wm):
        self.events.append(("punct", wm))

    def flush(self):
        self.events.append(("flush",))

    def send_eos_all(self):
        self.events.append(("eos",))

    def eos_ports(self):
        return []

    def set_ports(self, ports):
        self.ports = ports


def _batch(v0: int, n: int = 4, wm: int = 0) -> BatchTPU:
    import jax

    schema = TupleSchema({"v": np.int32})
    vals = np.arange(v0, v0 + n, dtype=np.int32)
    return BatchTPU({"v": jax.device_put(vals)},
                    np.arange(n, dtype=np.int64), n, schema, wm=wm)


def test_exit_fifo_defers_then_preserves_order():
    from windflow_tpu.tpu.emitters_tpu import TPUExitEmitter

    inner = RecordingInner()
    em = TPUExitEmitter(inner, depth=2)
    em.emit_device_batch(_batch(0, wm=1))
    em.emit_device_batch(_batch(10, wm=2))
    assert inner.events == []  # both parked in the FIFO
    em.emit_device_batch(_batch(20, wm=3))  # pushes the first one out
    assert [e[1] for e in inner.events] == [0, 1, 2, 3]
    em.flush()
    rows = [e[1] for e in inner.events if e[0] == "row"]
    assert rows == [0, 1, 2, 3, 10, 11, 12, 13, 20, 21, 22, 23]


def test_exit_single_row_and_punctuation_drain_first():
    from windflow_tpu.tpu.emitters_tpu import TPUExitEmitter

    inner = RecordingInner()
    em = TPUExitEmitter(inner, depth=4)
    em.emit_device_batch(_batch(0, n=2, wm=5))
    # a punctuation must not overtake rows carrying older watermarks
    em.propagate_punctuation(7)
    assert inner.events == [("row", 0, 5), ("row", 1, 5), ("punct", 7)]
    em.emit_device_batch(_batch(10, n=2, wm=8))
    em.emit({"v": 99}, ts=0, wm=9)  # single-row emit drains queued batches
    assert [e[1] for e in inner.events][-3:] == [10, 11, 99]
    em.send_eos_all()
    assert inner.events[-1] == ("eos",)


def test_exit_fifo_idle_tick_delivers():
    """The worker's idle tick (on_idle) must flush queued batches so an
    idle stream never withholds already-computed results."""
    from windflow_tpu.tpu.emitters_tpu import TPUExitEmitter

    inner = RecordingInner()
    em = TPUExitEmitter(inner, depth=4)
    em.emit_device_batch(_batch(0, n=2))
    assert inner.events == []
    em.on_idle()
    assert [e[1] for e in inner.events] == [0, 1]


def test_channel_get_timeout_idle():
    from windflow_tpu.runtime.channel import Channel

    ch = Channel()
    ch.register_input()
    assert ch.get(timeout=0.05) is None  # empty channel: idle tick
    ch.put(0, "x")
    assert ch.get(timeout=0.05) == (0, "x")


def test_worker_idle_tick_drains_exit_fifo():
    """End-to-end: a TPU stage feeding a CPU sink delivers its rows while
    the stream is idle (before any EOS), via the worker idle tick."""
    import time

    from windflow_tpu.runtime.channel import Channel, QueuePort
    from windflow_tpu.runtime.worker import Worker
    from windflow_tpu.tpu.emitters_tpu import TPUExitEmitter

    inner = RecordingInner()

    class PassThrough:
        """Minimal replica: forwards device batches to its emitter."""

        def __init__(self, emitter):
            self.emitter = emitter

        def handle_msg(self, ch, msg):
            self.emitter.emit_device_batch(msg)

        def terminate(self):
            self.emitter.flush()

    em = TPUExitEmitter(inner, depth=4)
    rep = PassThrough(em)
    ch = Channel()
    port = QueuePort(ch)
    w = Worker("idle_test", [rep], channel=ch)
    w.start()
    port.send(_batch(0, n=2))
    deadline = time.time() + 5.0
    while not inner.events and time.time() < deadline:
        time.sleep(0.02)  # idle tick (50 ms default) must deliver
    assert [e[1] for e in inner.events] == [0, 1]
    port.send_eos()
    w.join(timeout=5.0)
    assert not w.is_alive() and w.error is None


def test_split_on_idle_reaches_nested_exit_fifo():
    """A TPU->CPU split branch nests a TPUExitEmitter inside the splitting
    emitter; the splitter's idle tick must reach it."""
    from windflow_tpu.tpu.emitters_tpu import (TPUExitEmitter,
                                               TPUSplittingEmitter)

    inner = RecordingInner()
    exit_em = TPUExitEmitter(inner, depth=4)
    split = TPUSplittingEmitter(lambda p: 0, [exit_em])
    split.emit_device_batch(_batch(0, n=2))
    assert inner.events == []  # parked: splitter FIFO, then exit FIFO
    split.on_idle()
    assert [e[1] for e in inner.events] == [0, 1]


def test_native_channel_get_timeout():
    from windflow_tpu.native import NativeChannel, native_available

    if not native_available():
        import pytest
        pytest.skip("native runtime not buildable here")
    ch = NativeChannel(16)
    ch.register_input()
    assert ch.get(timeout=0.05) is None
    ch.put(0, {"v": 1})
    assert ch.get(timeout=0.05) == (0, {"v": 1})


def test_graft_entry_reexecutes():
    """Driver contract: entry()'s fn must run repeatedly on the SAME
    example args (warmup-then-time). The FFAT step donates its forest
    buffers internally; the entry surface must not."""
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    # the served step itself (FfatTPUReplica._make_step), handed what
    # _commit_step hands it: the batch's columns, the packed composite
    # the program sorts, the forest, the fire plan (its keys among it)
    import inspect
    assert list(inspect.signature(fn._wrapped_jit).parameters) == [
        "fields", "comp", "trees", "tvalid", "fire_plan"]
    fields, comp, trees, tvalid, fire_plan = args
    rep = g._ffat_replica()
    assert comp.shape == fields["key"].shape
    assert comp.dtype == rep._comp_dtype()[1]
    # the forest is node-major: a row a node of every key slot's tree
    assert tvalid.shape == trees["value"].shape == (2 * rep.F, rep.K_cap)
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))  # donated args would fail here


import pytest


@pytest.mark.parametrize("win_par", [1, 2])
def test_keyed_window_on_device_computed_key(win_par):
    """All-device chain (YSB shape): the window key is computed ON DEVICE
    by an upstream Map_TPU, so the key column is read via D2H fallback
    (prefetched by the forward emitter's key hint at par=1; routed through
    the TPUKeyByEmitter's D2H FIFO at par=2)."""
    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder, Map_TPU_Builder

    N, GROUPS = 300, 4
    results = {}

    def src(shipper, ctx):
        for i in range(N):
            shipper.push_with_timestamp({"item": i, "one": 1}, i * 10)
            if i % 20 == 19:
                shipper.set_next_watermark(i * 10)

    graph = PipeGraph("device_key", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    mp = graph.add_source(
        Source_Builder(src).with_output_batch_size(64).build())
    mp.add(Map_TPU_Builder(lambda f: {"grp": f["item"] % GROUPS,
                                      "one": f["one"]}).build())
    mp.add(Ffat_Windows_TPU_Builder(
        lambda f: {"count": f["one"]},
        lambda a, b: {"count": a["count"] + b["count"]})
        .with_key_by("grp").with_tb_windows(1000, 1000)
        .with_parallelism(win_par)
        .with_key_capacity(GROUPS).build())
    mp.add_sink(Sink_Builder(
        lambda r, ctx: results.__setitem__((r["grp"], r["wid"]), r["count"])
        if r is not None and r["valid"] else None).build())
    graph.run()

    expected = {}
    for i in range(N):
        expected[(i % GROUPS, (i * 10) // 1000)] = \
            expected.get((i % GROUPS, (i * 10) // 1000), 0) + 1
    assert results == expected


def test_split_fifo_routes_in_order():
    from windflow_tpu.tpu.emitters_tpu import TPUSplittingEmitter

    class BranchRecorder:
        def __init__(self):
            self.rows = []
            self.num_dests = 1
            self.flushed = False

        def emit_device_batch(self, b):
            self.rows.extend(np.asarray(b.fields["v"])[:b.size].tolist())

        def set_stats(self, s):
            pass

        def propagate_punctuation(self, wm):
            pass

        def flush(self):
            self.flushed = True

        def send_eos_all(self):
            pass

        def eos_ports(self):
            return []

    b0, b1 = BranchRecorder(), BranchRecorder()
    em = TPUSplittingEmitter(lambda p: p["v"] % 2, [b0, b1], depth=2)
    for v0 in (0, 10, 20):
        em.emit_device_batch(_batch(v0))
    # depth=2: exactly the first batch has been routed so far
    assert b0.rows == [0, 2] and b1.rows == [1, 3]
    em.flush()
    assert b0.rows == [0, 2, 10, 12, 20, 22]
    assert b1.rows == [1, 3, 11, 13, 21, 23]
    assert b0.flushed and b1.flushed


def test_exit_fifo_age_bound_evicts_on_saturated_stream(monkeypatch):
    """ADVICE r2: with punctuation disabled (non-DEFAULT modes) and a
    saturated stream, queued batches must still be delivered within the
    wall-clock age bound — _pipe_add itself evicts stale heads."""
    import time

    from windflow_tpu.tpu.emitters_tpu import TPUExitEmitter

    monkeypatch.setenv("WF_PIPELINE_MAX_AGE_MS", "30")
    inner = RecordingInner()
    em = TPUExitEmitter(inner, depth=4)
    em.emit_device_batch(_batch(0, wm=1))
    em.emit_device_batch(_batch(10, wm=2))
    time.sleep(0.05)  # both queued entries now exceed the 30 ms bound
    # a third arrival (stream still saturated, no punctuation, no idle
    # tick) must push the stale heads out even though depth=4 allows more
    em.emit_device_batch(_batch(20, wm=3))
    delivered = [e[1] for e in inner.events if e[0] == "row"]
    assert delivered[:8] == [0, 1, 2, 3, 10, 11, 12, 13]


def test_stage_emitter_ships_partial_on_age(monkeypatch):
    """Time-bounded staging (VERDICT r2 item 4): a partial batch older
    than WF_MAX_STAGING_MS ships on the next emit or idle tick instead of
    waiting to fill."""
    import time as _t

    from windflow_tpu.tpu.emitters_tpu import TPUStageEmitter

    monkeypatch.setenv("WF_MAX_STAGING_MS", "20")
    sent = []

    class P:
        def send(self, b):
            sent.append(b)

    em = TPUStageEmitter(1, 1024, None, None, "forward")
    em.set_ports([P()])
    em.emit({"v": 1}, ts=0, wm=0)
    em.emit({"v": 2}, ts=1, wm=0)
    assert not sent  # far below the batch size, fresh
    _t.sleep(0.03)
    # the in-emit sweep is AMORTIZED (every _SWEEP_EVERY rows — a
    # per-row clock read is measurable on the hot path); force the
    # countdown to fire on the next append
    em._sweep_countdown = 1
    em.emit({"v": 3}, ts=2, wm=0)  # age exceeded -> ships all three
    assert len(sent) == 1 and sent[0].size == 3
    # idle tick path
    em.emit({"v": 4}, ts=3, wm=0)
    assert len(sent) == 1
    _t.sleep(0.03)
    assert em.on_idle() is True
    assert len(sent) == 2 and sent[1].size == 1
    # amortized path without touching internals: _SWEEP_EVERY appends
    # after the bound expires must ship the stale buffer mid-stream
    before = len(sent)
    for i in range(5, 5 + em._SWEEP_EVERY // 2):
        em.emit({"v": i}, ts=i, wm=0)
    _t.sleep(0.03)
    for i in range(1000, 1000 + em._SWEEP_EVERY):
        em.emit({"v": i}, ts=i, wm=0)
    # swept by the countdown, not by batch fill: everything shipped is
    # a PARTIAL batch. A loaded host can stretch the append loops past
    # the 20 ms bound, legally triggering extra sweeps (and a periodic
    # punctuation), so the exact ship count is not pinned.
    batches = [b for b in sent[before:] if hasattr(b, "size")]
    assert batches, "countdown sweep never shipped the stale buffer"
    assert all(b.size < em.output_batch_size for b in batches)


class _RecPort:
    def __init__(self):
        self.msgs = []

    def send(self, m):
        self.msgs.append(m)


def test_columnar_exit_fifo_order_punct_and_idle():
    """The columnar exit (with_columns sinks) must obey the same FIFO
    contract as the row exit: batches defer up to depth and deliver in
    order; punctuation drains queued batches first (watermarks stay
    monotone at the sink); the idle tick flushes a quiet stream."""
    from windflow_tpu.tpu.emitters_tpu import TPUColumnarExitEmitter

    em = TPUColumnarExitEmitter(1, depth=2)
    port = _RecPort()
    em.set_ports([port])
    em.emit_device_batch(_batch(0, wm=1))
    em.emit_device_batch(_batch(10, wm=2))
    assert port.msgs == []                     # both parked
    em.emit_device_batch(_batch(20, wm=3))     # pushes the first out
    assert len(port.msgs) == 1
    assert int(np.asarray(port.msgs[0].fields["v"])[0]) == 0
    # punctuation must not overtake queued batches
    em.propagate_punctuation(7)
    kinds = [(getattr(m, "is_punct", False),
              None if getattr(m, "is_punct", False)
              else int(np.asarray(m.fields["v"])[0])) for m in port.msgs]
    assert kinds == [(False, 0), (False, 10), (False, 20), (True, None)]
    assert port.msgs[-1].wm == 7
    # ids stamp densely in delivery order
    assert [m.id for m in port.msgs] == [0, 1, 2, 3]
    # idle tick delivers a parked batch on a quiet stream
    em.emit_device_batch(_batch(30, wm=8))
    before = len(port.msgs)
    assert em.on_idle() is True
    assert len(port.msgs) == before + 1


def test_columnar_exit_round_robins_parallel_sinks():
    from windflow_tpu.tpu.emitters_tpu import TPUColumnarExitEmitter

    em = TPUColumnarExitEmitter(2, depth=0)
    p0, p1 = _RecPort(), _RecPort()
    em.set_ports([p0, p1])
    for i in range(4):
        em.emit_device_batch(_batch(i * 10, wm=i))
    assert len(p0.msgs) == 2 and len(p1.msgs) == 2
    assert [int(np.asarray(m.fields["v"])[0]) for m in p0.msgs] == [0, 20]
    assert [int(np.asarray(m.fields["v"])[0]) for m in p1.msgs] == [10, 30]
