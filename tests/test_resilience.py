"""Failure handling and resume capability.

The reference's answer to failure is exit(EXIT_FAILURE) (SURVEY.md §5);
here a failing replica unwinds the whole graph so the caller gets the
exception. Resume = the reference's capability level: durable keyed state
(persistent operators) + replayable source positions (Kafka offsets) —
exercised together as a stop/restart story."""

import pytest

from windflow_tpu import (Map_Builder, PipeGraph, Sink_Builder,
                          Source_Builder, WindFlowError)
from windflow_tpu.kafka import Kafka_Source_Builder, MemoryBroker
from windflow_tpu.persistent import DBHandle, P_Reduce_Builder

from common import GlobalSum, TupleT, make_ingress_source, make_sum_sink


def test_failing_replica_unwinds_graph():
    """A user functor raising mid-stream must not deadlock: the graph
    drains, EOS propagates, wait_end re-raises. BOTH map replicas hit
    value 50, so the error surfaces as the aggregate that names every
    dead worker (a single dead worker re-raises its error unchanged —
    test_supervision.py::test_single_error_still_raises_unwrapped)."""
    from windflow_tpu.basic import WorkerFailuresError

    graph = PipeGraph("boom")
    src = (Source_Builder(make_ingress_source(3, 100))
           .with_parallelism(2).build())

    def bad(t):
        if t.value == 50:
            raise ValueError("synthetic failure at value 50")
        return t

    m = Map_Builder(bad).with_parallelism(2).build()
    graph.add_source(src).add(m).add_sink(
        Sink_Builder(lambda t: None).with_parallelism(2).build())
    with pytest.raises(WorkerFailuresError, match="synthetic failure") as ei:
        graph.run()
    assert all(isinstance(e, ValueError)
               for e in ei.value.worker_errors.values())
    assert "map[0]" in str(ei.value) and "map[1]" in str(ei.value)


def test_device_runtime_failure_unwinds_graph():
    """The device RUNTIME (not a user functor) dying mid-stream
    (UNAVAILABLE at dispatch) must unwind like any replica error: drain,
    EOS, wait_end re-raises; and a fresh graph afterwards still runs."""
    from jax.errors import JaxRuntimeError

    from windflow_tpu.tpu import Map_TPU_Builder

    graph = PipeGraph("dev_boom")
    src = (Source_Builder(make_ingress_source(3, 120))
           .with_parallelism(2).with_output_batch_size(16).build())
    op = Map_TPU_Builder(lambda f: {**f, "value": f["value"] + 1}).build()

    orig_build = op.build_replicas

    def build_then_sabotage():
        orig_build()
        rep = op.replicas[0]
        orig_handle = rep.handle_msg
        seen = [0]

        def dying(ch, msg):
            seen[0] += 1
            if seen[0] == 3:
                raise JaxRuntimeError(
                    "UNAVAILABLE: device lost "
                    "(synthetic runtime death)")
            orig_handle(ch, msg)

        rep.handle_msg = dying

    op.build_replicas = build_then_sabotage
    graph.add_source(src).add(op).add_sink(
        Sink_Builder(lambda t: None).build())
    with pytest.raises(JaxRuntimeError, match="synthetic runtime death"):
        graph.run()

    # the failure must not wedge the process: a new graph still runs
    acc = [0]
    g2 = PipeGraph("after")
    g2.add_source(Source_Builder(make_ingress_source(2, 50))
                  .with_output_batch_size(16).build()) \
      .add(Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 2}).build()) \
      .add_sink(Sink_Builder(
          lambda t: acc.__setitem__(0, acc[0] + t.value)
          if t is not None else None).build())
    g2.run()
    assert acc[0] == 2 * 2 * sum(range(1, 51))


def test_failing_source_unwinds_graph():
    graph = PipeGraph("boom_src")

    def bad_src(shipper):
        shipper.push(TupleT(0, 1))
        raise RuntimeError("source died")

    graph.add_source(Source_Builder(bad_src).build()).add_sink(
        Sink_Builder(lambda t: None).build())
    with pytest.raises(RuntimeError, match="source died"):
        graph.run()


def test_stop_and_resume_from_offsets_and_durable_state(tmp_path):
    """Run half the topic, 'crash', then resume a NEW graph from the
    recorded offsets with the same durable state directory — final keyed
    state equals a single uninterrupted run."""
    MemoryBroker.reset()
    b = MemoryBroker.get("resume", 2)
    N = 200
    for i in range(N):
        b.produce("events", {"k": i % 3, "v": i + 1}, partition=i % 2)

    def deser_until(stop_at):
        def f(msg, shipper):
            if msg is None:
                return False
            if msg.offset >= stop_at:
                return False  # simulated crash point per partition
            shipper.push(TupleT(msg.payload["k"], msg.payload["v"]))
            return True
        return f

    def add(t, state):
        state.value += t.value
        state.key = t.key
        return state

    db_dir = str(tmp_path)

    def run_segment(deser, offsets):
        graph = PipeGraph("seg")
        src = (Kafka_Source_Builder(deser).with_brokers("memory://resume")
               .with_topics("events").with_offsets(offsets)
               .with_idleness(50).build())
        red = (P_Reduce_Builder(add).with_key_by(lambda t: t.key)
               .with_initial_state(TupleT(0, 0)).with_db_path(db_dir)
               .with_cache_capacity(2).build())
        graph.add_source(src).add(red).add_sink(
            Sink_Builder(lambda t: None).build())
        graph.run()

    half = N // 4  # per-partition offset of the simulated crash
    run_segment(deser_until(half), {})
    # resume: replay from the recorded per-partition positions
    run_segment(deser_until(10**9),
                {("events", 0): half, ("events", 1): half})

    db = DBHandle("p_reduce_r0", db_dir=db_dir)
    state = {k: v.value for k, v in db.items()}
    db.close()
    expected = {}
    for i in range(N):
        expected[i % 3] = expected.get(i % 3, 0) + i + 1
    assert state == expected


def test_many_graphs_no_leak():
    """Soak: many graphs in one process must not accumulate state (program
    caches die with their ops; channels/workers are per-graph)."""
    import gc

    from windflow_tpu import (ExecutionMode, Map_Builder, PipeGraph,
                              Sink_Builder, Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Map_TPU_Builder

    def one(i):
        acc = []
        g = PipeGraph(f"soak{i}", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)

        def src(shipper):
            for v in range(200):
                shipper.push({"v": v})

        g.add_source(Source_Builder(src).with_output_batch_size(32).build()) \
         .add(Map_TPU_Builder(lambda f: {"v": f["v"] + 1}).build()) \
         .add(Map_Builder(lambda t: t).build()) \
         .add_sink(Sink_Builder(lambda t: acc.append(t) if t else None)
                   .build())
        g.run()
        assert len(acc) == 200

    def rss_kb() -> int:  # CURRENT rss (not the high-water mark, which
        # any earlier test in the process could have set)
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * 4  # pages -> kB

    for i in range(3):  # warmup: compiles + allocator pools
        one(i)
    gc.collect()
    rss0 = rss_kb()
    for i in range(20):
        one(100 + i)
    gc.collect()
    rss1 = rss_kb()
    # 20 more graphs must not grow the resident set by more than ~200MB
    assert rss1 - rss0 < 200_000, (rss0, rss1)
