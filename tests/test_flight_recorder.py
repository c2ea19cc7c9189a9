"""Flight recorder / stall watchdog / compile attribution
(monitoring/flightrec.py + the wiring across worker, dispatch, channel,
pipegraph, ops_tpu).

- ring semantics: fixed capacity, wraparound drops oldest-first;
- Chrome trace-event export: ``dump_trace`` output loads with
  ``json.load`` and validates against the trace-event schema
  (scripts/check_metrics.validate_chrome_trace), spans keep per-worker
  same-name spans non-overlapping on a CPU chain and a batched device
  pipeline (each ring is single-writer: one thread's measured intervals
  cannot overlap themselves);
- per-op builder knob ``with_flight_recorder(events=N)``;
- stall watchdog: an injected stuck functor freezes the worker's
  progress counter, the watchdog fires, and the post-mortem dump holds
  that worker's thread stack;
- compile attribution: first call compiles, a value-change is a cache
  hit, a dtype change is a retrace (counted as a new compile);
- crash path: a raising map functor produces ``Worker_last_error``, a
  ``Worker_errors`` entry in the final report, and an automatic
  post-mortem dump.
"""

import json
import os
import sys
import threading
import time

import pytest

from windflow_tpu import (ExecutionMode, Map_Builder, PipeGraph,
                          Sink_Builder, Source_Builder, TimePolicy)
from windflow_tpu.monitoring.flightrec import (FlightRecorder,
                                               instrumented_jit,
                                               to_chrome_trace)
from windflow_tpu.monitoring.stats import StatsRecord

from common import GlobalSum, TupleT, make_ingress_source, make_sum_sink

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
from check_metrics import validate_chrome_trace  # noqa: E402

N_KEYS, STREAM_LEN = 4, 48


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------
def test_ring_wraparound_drops_oldest_first():
    rec = FlightRecorder(4, pid_label="p", tid_label="t")
    for i in range(10):
        rec.event(f"e{i}", float(i))
    assert len(rec) == 4
    assert rec.dropped == 6
    names = [e[1] for e in rec.snapshot()]
    assert names == ["e6", "e7", "e8", "e9"]  # oldest-first, newest kept
    # timestamps monotone in ring order (single-writer append order)
    stamps = [e[0] for e in rec.snapshot()]
    assert stamps == sorted(stamps)


def test_ring_below_capacity_keeps_all():
    rec = FlightRecorder(16)
    for i in range(5):
        rec.event(f"e{i}")
    assert len(rec) == 5 and rec.dropped == 0
    assert [e[1] for e in rec.snapshot()] == [f"e{i}" for i in range(5)]


def test_trace_doc_counts_dropped_events():
    rec = FlightRecorder(2, pid_label="p", tid_label="t")
    for i in range(7):
        rec.event("x", 1.0)
    doc = to_chrome_trace([rec])
    assert doc["droppedEvents"] == 5
    assert not validate_chrome_trace(doc)


# ---------------------------------------------------------------------------
# trace export: CPU chain + batched device pipeline
# ---------------------------------------------------------------------------
def _span_events(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# queue-RESIDENCY spans measure how long an item sat waiting, not what
# the thread was executing: with the dispatch pipeline ahead by design,
# batch B enqueues before batch A's commit runs, so their wait spans
# overlap legitimately
_RESIDENCY_SPANS = ("wait:queue:", "wait:fifo:")


def _assert_same_name_spans_disjoint(doc):
    """Per (tid, name): measured EXECUTION intervals from one
    single-writer ring come from one thread executing sequentially, so
    spans of one kind must not overlap each other (1 µs grace for float
    rounding)."""
    by_key = {}
    for e in _span_events(doc):
        if e["name"].startswith(_RESIDENCY_SPANS):
            continue
        by_key.setdefault((e["pid"], e["tid"], e["name"]), []).append(
            (e["ts"], e["ts"] + e["dur"]))
    checked = 0
    for spans in by_key.values():
        spans.sort()
        for (_, end0), (start1, _) in zip(spans, spans[1:]):
            assert start1 >= end0 - 1.0, (spans,)
            checked += 1
    return checked


def test_cpu_chain_trace_json(tmp_path):
    acc = GlobalSum()
    g = PipeGraph("frec_cpu", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    src = (Source_Builder(make_ingress_source(N_KEYS, STREAM_LEN))
           .with_latency_tracing(1).build())
    m = (Map_Builder(lambda t: TupleT(t.key, t.value * 2, t.ts))
         .with_latency_tracing(1).build())
    snk = (Sink_Builder(make_sum_sink(acc))
           .with_latency_tracing(1).build())
    g.add_source(src).chain(m).chain_sink(snk)
    g.run()
    assert acc.count == N_KEYS * STREAM_LEN

    path = str(tmp_path / "cpu_trace.json")
    assert g.dump_trace(path) == path
    with open(path) as f:
        doc = json.load(f)  # must load with plain json.load
    assert not validate_chrome_trace(doc), validate_chrome_trace(doc)
    spans = _span_events(doc)
    names = {e["name"] for e in spans}
    assert {"svc:map", "svc:sink"} <= names, names
    # chained graph: one worker = one ring = one (pid, tid) pair, with
    # thread_name/process_name metadata present
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m_["name"] for m_ in metas} == {"process_name", "thread_name"}
    assert _assert_same_name_spans_disjoint(doc) > 0
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in spans)


def test_device_pipeline_trace_spans(tmp_path):
    from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder

    acc = GlobalSum()
    g = PipeGraph("frec_tpu", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    src = (Source_Builder(make_ingress_source(N_KEYS, STREAM_LEN))
           .with_output_batch_size(16).build())
    m = Map_TPU_Builder(
        lambda f: {**f, "value": f["value"] * 3 + f["key"]}).build()
    flt = Filter_TPU_Builder(lambda f: (f["value"] % 2) == 0).build()
    snk = Sink_Builder(make_sum_sink(acc)).build()
    g.add_source(src).add(m).add(flt).add_sink(snk)
    g.run()

    doc = g.trace_document()
    assert not validate_chrome_trace(doc), validate_chrome_trace(doc)
    names = {e["name"] for e in _span_events(doc)}
    # the dispatch pipeline's stages + the compaction readback + the jit
    # compiles all leave spans, under the stage table's names
    # (monitoring/tracing.py STAGES: <prefix>:<stage>:<op>)
    assert names >= {"wf:h2d:source", "wf:prep:map_tpu",
                     "wait:queue:map_tpu", "wf:commit:map_tpu",
                     "wf:launch:map_tpu", "wf:emit:map_tpu",
                     "wf:prep:filter_tpu", "wf:commit:filter_tpu",
                     "wf:readback:filter_tpu", "wf:emit:filter_tpu",
                     "wf:exit:filter_tpu", "compile"}, names
    _assert_same_name_spans_disjoint(doc)
    # every stage event carries its batch's id
    staged = [e for e in _span_events(doc)
              if e["name"].startswith("wf:") and ":launch:" not in e["name"]]
    assert staged and all(e["args"]["b"] > 0 for e in staged)
    # compile spans carry the triggering abstract signature
    comp = [e for e in _span_events(doc) if e["name"] == "compile"]
    assert all("signature" in e["args"] for e in comp)
    # device stages don't chain: map/filter rings are distinct tids
    tids = {e["tid"] for e in _span_events(doc)}
    assert len(tids) >= 3  # source, map, filter (+ sink)


def test_per_op_builder_override():
    """with_flight_recorder(events=N) on ONE operator enables a ring for
    that stage only, at that capacity."""
    acc = GlobalSum()
    g = PipeGraph("frec_perop", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    src = Source_Builder(make_ingress_source(2, 8)).build()
    m = (Map_Builder(lambda t: t).with_flight_recorder(64)
         .with_parallelism(2).build())
    snk = Sink_Builder(make_sum_sink(acc)).build()
    g.add_source(src).add(m).add_sink(snk)
    g.run()
    assert len(g._recorders) == 2  # map stage only, one per replica
    assert all(r.capacity == 64 for r in g._recorders)


def test_dump_trace_without_recorder_is_empty_but_valid(tmp_path):
    acc = GlobalSum()
    g = PipeGraph("frec_off", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(make_ingress_source(2, 4)).build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    g.run()
    path = g.dump_trace(str(tmp_path / "empty.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"] == []
    assert not validate_chrome_trace(doc)


def test_checkpoint_spans_in_trace(tmp_path):
    """The checkpoint plane leaves its own timeline: barrier_open on
    the aligning workers, ckpt_snapshot/ckpt_ack per worker, and one
    ckpt_commit on the last acker."""

    class ReplaySrc:
        def __init__(self):
            self.pos = 0

        def __call__(self, shipper):
            while self.pos < 64:
                shipper.push(TupleT(key=self.pos % 4, value=self.pos))
                self.pos += 1
                if self.pos == 32:
                    assert shipper.request_checkpoint() is not None

        def snapshot_position(self):
            return self.pos

        def restore(self, pos):
            self.pos = pos

    acc = GlobalSum()
    g = PipeGraph("frec_ckpt", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    g.with_checkpointing(store_dir=str(tmp_path / "store"))
    g.add_source(Source_Builder(ReplaySrc()).build()) \
     .add(Map_Builder(lambda t: t).build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    g.run()
    assert acc.count == 64
    doc = g.trace_document()
    names = {e["name"] for e in _span_events(doc)}
    assert {"barrier_open", "ckpt_snapshot", "ckpt_ack",
            "ckpt_commit"} <= names, names
    acks = [e for e in _span_events(doc) if e["name"] == "ckpt_ack"]
    assert {e["args"]["ckpt_id"] for e in acks} == {1}
    assert len(acks) == 3  # one per worker (source, map, sink)


# ---------------------------------------------------------------------------
# compile attribution
# ---------------------------------------------------------------------------
def test_compile_counter_first_hit_and_dtype_retrace():
    import jax.numpy as jnp

    st = StatsRecord("jit_op", 0)
    fn = instrumented_jit(lambda x: x * 2, st, label="jit_op")
    a = jnp.arange(8, dtype=jnp.int32)

    fn(a)  # first call: trace+compile
    assert (st.compile_count, st.compile_cache_hits) == (1, 0)
    assert st.compile_last_us > 0
    assert "int32" in st.compile_last_signature

    fn(a + 1)  # same signature, new values: cache hit
    assert (st.compile_count, st.compile_cache_hits) == (1, 1)

    fn(a.astype(jnp.float32))  # dtype change: retrace
    assert (st.compile_count, st.compile_cache_hits) == (2, 1)
    assert "float32" in st.compile_last_signature

    fn(jnp.arange(16, dtype=jnp.int32))  # shape change: retrace
    assert (st.compile_count, st.compile_cache_hits) == (3, 1)
    fn(jnp.arange(16, dtype=jnp.int32) * 5)  # hit again
    assert (st.compile_count, st.compile_cache_hits) == (3, 2)


def test_compile_stats_exported_by_device_pipeline():
    from windflow_tpu.tpu import Map_TPU_Builder

    acc = GlobalSum()
    g = PipeGraph("frec_compile", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    src = (Source_Builder(make_ingress_source(N_KEYS, STREAM_LEN))
           .with_output_batch_size(16).build())
    m = Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1}).build()
    g.add_source(src).add(m) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    g.run()
    rep = next(op for op in g.get_stats()["Operators"]
               if op["name"] == "map_tpu")["replicas"][0]
    assert rep["Compile_count"] >= 1
    assert rep["Compile_cache_hits"] >= 1  # same-shape batches reuse
    assert rep["Compile_usec_total"] >= rep["Compile_last_usec"] > 0
    assert rep["Compile_last_signature"]


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------
def test_watchdog_fires_on_stuck_functor(tmp_path, monkeypatch):
    monkeypatch.setenv("WF_STALL_SEC", "0.4")
    monkeypatch.setenv("WF_LOG_DIR", str(tmp_path))

    release = threading.Event()

    def src(shipper):
        for i in range(4):
            shipper.push(TupleT(key=0, value=i))

    def stuck_map_functor(t):
        if t.value == 2:
            assert release.wait(30.0), "test harness never released"
        return t

    acc = GlobalSum()
    g = PipeGraph("frec_stall", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    g.add_source(Source_Builder(src).build()) \
     .add(Map_Builder(stuck_map_functor).with_name("stuckmap").build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    g.start()
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            fired = list(g._watchdog.fired) if g._watchdog else []
            if any("stuckmap" in w for w in fired) \
                    and g.last_postmortem is not None:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(
                f"watchdog never flagged the stuck worker: "
                f"fired={g._watchdog.fired if g._watchdog else None}")
    finally:
        release.set()
    g.wait_end()

    # the automatic dump: trace JSON + sys._current_frames() stacks,
    # including the stalled worker's (the functor frame is visible)
    dumps = [p for p in os.listdir(tmp_path) if "stall" in p]
    assert dumps, os.listdir(tmp_path)
    with open(tmp_path / dumps[0]) as f:
        doc = json.load(f)
    assert not validate_chrome_trace(doc)
    assert "stalledWorker" in doc
    stacks = doc["stacks"]
    assert isinstance(stacks, dict) and stacks
    all_frames = "".join("".join(v) for v in stacks.values())
    assert "stuck_map_functor" in all_frames
    stuck_threads = [name for name, frames in stacks.items()
                     if "stuck_map_functor" in "".join(frames)]
    assert any("stuckmap" in name for name in stuck_threads), stacks.keys()


def test_watchdog_quiet_on_healthy_idle_graph(monkeypatch):
    """A healthy-but-idle worker (parked in channel.get between slow
    source pushes) must NOT trip the watchdog: idle ticks are forced on
    whenever it is armed, so the progress counter keeps advancing."""
    monkeypatch.setenv("WF_STALL_SEC", "0.3")

    def slow_src(shipper):
        for i in range(3):
            time.sleep(0.45)  # slower than WF_STALL_SEC
            shipper.push(TupleT(key=0, value=i))

    acc = GlobalSum()
    g = PipeGraph("frec_idle", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    g.add_source(Source_Builder(slow_src).build()) \
     .add(Map_Builder(lambda t: t).build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    g.run()
    assert acc.count == 3
    # the source MAY trip (it sleeps inside run_source, where no idle
    # tick can advance it); the channel-fed map/sink workers must not
    fired = g._watchdog.fired if g._watchdog else []
    assert not [w for w in fired if "map" in w or "sink" in w], fired


# ---------------------------------------------------------------------------
# crash visibility
# ---------------------------------------------------------------------------
def test_crash_dump_and_stats_on_raising_functor(tmp_path, monkeypatch):
    monkeypatch.setenv("WF_LOG_DIR", str(tmp_path))

    def bad_map(t):
        if t.value == 3:
            raise ValueError("injected functor failure")
        return t

    acc = GlobalSum()
    g = PipeGraph("frec_crash", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    g.add_source(Source_Builder(make_ingress_source(1, 8)).build()) \
     .add(Map_Builder(bad_map).with_name("badmap").build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    with pytest.raises(ValueError, match="injected functor failure"):
        g.run()

    # stats plane: the exception type + traceback, not a silent death
    st = g.get_stats()
    assert any("badmap" in w for w in st["Worker_errors"])
    assert "ValueError" in next(iter(st["Worker_errors"].values()))
    rep = next(op for op in st["Operators"]
               if op["name"] == "badmap")["replicas"][0]
    assert rep["Worker_crashes"] == 1
    assert "injected functor failure" in rep["Worker_last_error"]
    assert "Traceback" in rep["Worker_last_error"]

    # automatic post-mortem: trace + stacks + the exception text
    assert g.last_postmortem and os.path.exists(g.last_postmortem)
    with open(g.last_postmortem) as f:
        doc = json.load(f)
    assert not validate_chrome_trace(doc)
    assert "badmap" in doc["crashedWorker"]
    assert "injected functor failure" in doc["exception"]
    assert "crash" in {e["name"] for e in _span_events(doc)}
    assert doc["stacks"]


def test_crash_stats_recorded_without_recorder():
    """Worker_last_error / Worker_errors work with the recorder OFF
    (crash visibility is unconditional; only the dump needs a ring)."""
    def bad_map(t):
        raise RuntimeError("boom")

    acc = GlobalSum()
    g = PipeGraph("frec_crash2", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(make_ingress_source(1, 4)).build()) \
     .add(Map_Builder(bad_map).with_name("badmap2").build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc)).build())
    with pytest.raises(RuntimeError):
        g.run()
    st = g.get_stats()
    assert any("badmap2" in w for w in st["Worker_errors"])
    rep = next(op for op in st["Operators"]
               if op["name"] == "badmap2")["replicas"][0]
    assert rep["Worker_crashes"] == 1 and "boom" in rep["Worker_last_error"]
    assert g.last_postmortem is None  # no ring -> no automatic dump
