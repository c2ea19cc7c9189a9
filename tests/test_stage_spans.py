"""The host timeline of the served path (``monitoring/tracing.py``): one
stage helper writes a profiler span, a cumulative ``get_stats()`` counter
and a flight-recorder event at once, on every thread of the device plane.

One small graph of the served shape (columnar source -> ``Filter_TPU``
chained with ``Map_TPU`` -> ``Ffat_Windows_TPU`` -> columnar sink) runs
once with the profiler's annotation class replaced by a recording fake and
the flight recorder on; the cases read that one run. CPU backend; no case
asserts a duration beyond "positive" or an ordering of two of its own
counters."""

import json
import os
import re
import threading

import numpy as np
import pytest

from windflow_tpu import (Columnar_Source_Builder, ExecutionMode,
                          Map_Builder, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.monitoring import tracing
from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder, Filter_TPU_Builder,
                              Map_TPU_Builder)

K, ROWS, BLOCKS, PANE_US = 8, 64, 12, 1000
CHAIN, DEVICE = "views∘join", ("views∘join", "win")


class SpanLog:
    """What the recording fake saw: one entry per closed annotation, with
    the annotation that enclosed it on its thread."""

    def __init__(self):
        self.spans = []
        self._tls = threading.local()
        self._lock = threading.Lock()
        log = self

        class Annotation:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                stack = log._stack()
                self.parent = stack[-1].name if stack else None
                stack.append(self)
                return self

            def __exit__(self, *exc):
                assert log._stack().pop() is self
                with log._lock:
                    log.spans.append({
                        "name": self.name, "b": self.kw.get("b", 0),
                        "cause": self.kw.get("cause", 0),
                        "parent": self.parent,
                        "thread": threading.current_thread().name})

        self.annotation = Annotation

    def _stack(self):
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    def named(self, name, **match):
        return [s for s in self.spans if s["name"] == name
                and all(s[k] == v for k, v in match.items())]


class Columns:
    def __init__(self):
        self.calls = []

    def sink(self, cols, ts):
        if cols is not None:
            self.calls.append({k: np.array(v) for k, v in cols.items()})


def blocks():
    """BLOCKS blocks of ROWS rows, one pane of event time each; the
    watermark rides one block behind, so a block's own commit fires the
    windows its watermark closes."""
    rng = np.random.default_rng(7)
    for p in range(BLOCKS):
        cols = {"k": rng.integers(0, K, ROWS).astype(np.int32),
                "v": rng.integers(0, 100, ROWS).astype(np.int32)}
        ts = np.full(ROWS, p * PANE_US + 5, dtype=np.int64)
        yield cols, ts, p * PANE_US


def device_chain():
    views = (Filter_TPU_Builder(lambda f: f["v"] % 2 == 0)
             .with_name("views").build())
    join = (Map_TPU_Builder(lambda f: {"k": f["k"], "one": f["v"] * 0 + 1})
            .with_name("join").build())
    return views, join


def served_graph(sink, window=True):
    views, join = device_chain()
    g = PipeGraph("stages", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    g.with_flight_recorder()
    pipe = g.add_source(Columnar_Source_Builder(blocks).with_name("src")
                        .with_output_batch_size(ROWS).build()) \
        .add(views).chain(join)
    if window:
        pipe = pipe.add(
            Ffat_Windows_TPU_Builder(
                lambda f: {"count": f["one"]},
                lambda a, b: {"count": a["count"] + b["count"]})
            .with_key_by("k").with_tb_windows(4 * PANE_US, 4 * PANE_US)
            .with_key_capacity(K).with_name("win").build())
    pipe.add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g


def run_recorded(graph):
    """Run ``graph`` with the profiler's annotation class replaced by a
    recording fake; the log, the stats by operator, the ring's events."""
    log = SpanLog()
    before = tracing._ANNOTATION
    tracing._ANNOTATION = log.annotation
    try:
        graph.run()
    finally:
        tracing._ANNOTATION = before
    stats = {o["name"]: o["replicas"][0]
             for o in graph.get_stats()["Operators"]}
    ring = [e for e in graph.trace_document()["traceEvents"]
            if e.get("ph") == "X"]
    return log, stats, ring


@pytest.fixture(scope="module")
def served():
    out = Columns()
    g = served_graph(out.sink)
    log, stats, ring = run_recorded(g)
    assert out.calls, "the windows never reached the sink"
    return {"graph": g, "log": log, "stats": stats, "ring": ring}


# ---------------------------------------------------------------------------
# (a) the counters, where the table says the work happens
# ---------------------------------------------------------------------------
# stage -> operators of the served graph whose thread runs it
RUNS_ON = {
    "ingest": {"src"}, "stage": {"src"}, "h2d": {"src"},
    "prep": set(DEVICE), "queue": set(DEVICE), "commit": set(DEVICE),
    "launch": set(DEVICE), "emit": set(DEVICE),
    # the window operator plans its fires inside its prep
    "fireplan": {"win"},
    # the window operator's commit emits its fired batch without a
    # blocking read: its readback counter reads 0
    "readback": {CHAIN},
    # the chain's keyed re-shard to ONE destination passes batches on
    # without its FIFO; the window operator's columnar exit queues them
    "fifo": {"win"}, "exit": {"win"}, "d2h": {"snk"}, "sink": {"snk"},
}
FIELDS = sorted(
    (field, stage) for stage, sdef in tracing.STAGES.items()
    if stage in RUNS_ON for field in (sdef.total, sdef.count) if field)


@pytest.mark.parametrize("field,stage", FIELDS)
def test_counter_positive_where_the_stage_runs_and_zero_elsewhere(
        served, field, stage):
    for op, rep in served["stats"].items():
        if op in RUNS_ON[stage]:
            assert rep[field] > 0, (op, field, rep[field])
        else:
            assert rep[field] == 0, (op, field, rep[field])


def test_wait_stages_belong_to_the_consumer(served):
    # a source has no input channel: nothing waits on its behalf
    src = served["stats"]["src"]
    assert src["Queue_blocked_put_usec"] == 0 == src["Queue_blocked_get_usec"]
    assert served["stats"]["win"]["Exit_fifo_depth_sum"] >= \
        served["stats"]["win"]["Exit_fifo_batches"] > 0


@pytest.mark.parametrize("op", DEVICE)
def test_commit_holds_its_children(served, op):
    rep = served["stats"][op]
    assert (rep["Dispatch_readback_wait_total_usec"]
            + rep["Dispatch_emit_total_usec"]
            <= rep["Dispatch_commit_total_usec"])
    assert rep["Dispatch_batches"] == rep["Device_batches_in"] == BLOCKS


def test_window_operator_counts_its_fires_and_plans(served):
    win, snk = served["stats"]["win"], served["stats"]["snk"]
    # a row per fired window, empty ones too, each from a program that
    # answered window queries (a step's fire block or a fire-only program)
    assert win["Windows_fired"] == snk["Inputs_received"] > 0
    assert 0 < win["Fire_programs"] == win["Device_batches_out"] \
        <= win["Device_programs_run"]
    assert 0 < win["Fire_plan_total_usec"] <= \
        win["Dispatch_host_prep_total_usec"]
    # the sliding scan is count-based windows' (PR 33): a time-based
    # operator has the counter and never moves it
    assert win["Fire_sliding_programs"] == 0
    for op, rep in served["stats"].items():
        if op != "win":
            assert rep["Windows_fired"] == 0 == rep["Fire_programs"], op
            assert rep["Fire_sliding_programs"] == 0, op


def test_fire_plan_is_a_span_inside_the_window_prep(served):
    log = served["log"]
    plans = log.named("wf:fireplan:win")
    assert len(plans) == BLOCKS
    for s in plans:
        assert s["parent"] == "wf:prep:win" and s["b"] > 0
        assert len(log.named("wf:prep:win", b=s["b"])) == 1
    # and in a dumped trace (the ring), under the same name and ids
    ring = {e["args"]["b"] for e in served["ring"]
            if e["name"] == "wf:fireplan:win"}
    assert ring == {s["b"] for s in plans}


def test_unknown_stage_raises_where_it_is_bound():
    with pytest.raises(ValueError, match="unknown stage 'reedback'"):
        tracing.StageCounters("op").stage("reedback")


# ---------------------------------------------------------------------------
# (b) the spans of one block, under the table's names, nested as it says
# ---------------------------------------------------------------------------
def test_one_block_from_stage_to_window_commit(served):
    log = served["log"]
    # a block past the first: a program's first call is a ``compile``
    b = log.named("wf:stage:src")[BLOCKS // 2]["b"]
    assert b > 0
    for name in ("wf:stage:src", "wf:h2d:src", f"wf:prep:{CHAIN}",
                 f"wf:launch:{CHAIN}",
                 f"wf:readback:{CHAIN}", f"wf:emit:{CHAIN}",
                 "wf:prep:win", "wf:commit:win"):
        assert len(log.named(name, b=b)) == 1, (name, b)
    # a chain that compacts commits in two halves under the batch's own
    # id: the launch, and one launch later the readback and the emit
    # (spans are logged as they close)
    closed = [s["name"].split(":")[1] for s in log.spans
              if s["b"] == b and s["name"].endswith(":" + CHAIN)
              and s["name"].split(":")[1] in ("commit", "launch",
                                              "readback", "emit")]
    assert closed == ["launch", "commit", "readback", "emit", "commit"]
    # the source's work sits in the block's envelope, never in a wf: span
    for name in ("wf:stage:src", "wf:h2d:src"):
        assert log.named(name, b=b)[0]["parent"] == "blk:ingest:src"
    for child in ("launch", "readback", "emit"):
        assert log.named(f"wf:{child}:{CHAIN}", b=b)[0]["parent"] == \
            f"wf:commit:{CHAIN}"
    # a thread that only waits is never named wf:, and never encloses work
    assert not any(s["parent"] and s["parent"].startswith("wait:")
                   for s in log.spans)
    assert {s["name"].split(":")[0] for s in log.spans} == \
        {"wf", "wait", "blk"}


def test_fired_batch_names_its_cause(served):
    log = served["log"]
    fired = [s for s in log.named("wf:emit:win") if s["cause"]]
    assert fired, log.named("wf:emit:win")
    for s in fired:
        assert s["parent"] == "wf:commit:win"
        # the input batch whose commit fired it, and the new batch's own
        # id from the window operator's exit to the sink's functor
        assert len(log.named("wf:commit:win", b=s["cause"])) == 1
        assert s["b"] not in {c["b"] for c in log.named("wf:commit:win")}
        for name in ("wf:exit:win", "wf:d2h:snk", "wf:sink:snk"):
            assert log.named(name, b=s["b"], cause=s["cause"]), (name, s)


def test_one_id_from_stage_to_sink_without_a_window():
    out = Columns()
    log, stats, _ = run_recorded(served_graph(out.sink, window=False))
    kept = sum(len(c["k"]) for c in out.calls)
    assert kept == stats["snk"]["Inputs_received"] > 0
    ids = {s["b"] for s in log.named("wf:sink:snk")}
    assert ids and 0 not in ids
    for b in ids:
        for name in ("wf:stage:src", "wf:h2d:src", f"wf:commit:{CHAIN}",
                     f"wf:exit:{CHAIN}", "wf:d2h:snk"):
            assert log.named(name, b=b), (name, b)


# ---------------------------------------------------------------------------
# (c) the ring holds the same names and ids
# ---------------------------------------------------------------------------
def test_ring_holds_the_same_spans(served):
    def key(name, b, cause):
        return (name, b, cause)

    seen = sorted(key(s["name"], s["b"], s["cause"])
                  for s in served["log"].spans
                  if not s["name"].startswith("wait:"))
    ring = sorted(key(e["name"], e["args"]["b"], e["args"].get("cause", 0))
                  for e in served["ring"]
                  if e["name"].startswith(("wf:", "blk:")))
    assert ring == seen
    # residency spans exist in the ring alone (a profiler span cannot be
    # opened in the past), with the waiting batch's id
    for name in (f"wait:queue:{CHAIN}", "wait:queue:win", "wait:fifo:win"):
        waits = [e for e in served["ring"] if e["name"] == name]
        assert waits and all(e["args"]["b"] > 0 for e in waits), name


# ---------------------------------------------------------------------------
# (d) CPU time per worker thread
# ---------------------------------------------------------------------------
def test_thread_cpu_while_running_and_frozen_after_the_end():
    gate, seen = threading.Event(), threading.Event()

    def gated():
        for i, blk in enumerate(blocks()):
            if i == BLOCKS - 2:
                assert gate.wait(60)
            yield blk

    def sink(cols, ts):
        if cols is not None:
            seen.set()

    views, join = device_chain()
    g = PipeGraph("clocks", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    g.add_source(Columnar_Source_Builder(gated).with_name("src")
                 .with_output_batch_size(ROWS).build()) \
        .add(views).chain(join) \
        .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    g.start()
    try:
        assert seen.wait(60)
        live = {o["name"]: o["replicas"][0]
                for o in g.get_stats()["Operators"]}
        for op, rep in live.items():
            assert rep["Thread_cpu_usec"] > 0, op
            assert rep["Thread_wall_usec"] >= rep["Thread_cpu_usec"] * 0.5
    finally:
        gate.set()
        g.wait_end()
    first = {o["name"]: o["replicas"][0] for o in g.get_stats()["Operators"]}
    again = {o["name"]: o["replicas"][0] for o in g.get_stats()["Operators"]}
    for op in first:
        assert first[op]["Thread_cpu_usec"] == again[op]["Thread_cpu_usec"]
        assert first[op]["Thread_wall_usec"] == again[op]["Thread_wall_usec"]
        assert first[op]["Thread_cpu_usec"] >= live[op]["Thread_cpu_usec"]


def test_thread_cpu_counts_each_worker_once():
    """Three chained CPU operators share one worker: one of their records
    reports the thread, the others 0, so a sum counts a thread once."""
    got = []

    def src(shipper):
        for i in range(200):
            shipper.push({"v": i})

    g = PipeGraph("one_thread", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .chain(Map_Builder(lambda t: t).with_name("m").build()) \
        .chain_sink(Sink_Builder(lambda t: got.append(t))
                    .with_name("k").build())
    g.run()
    reps = [r for o in g.get_stats()["Operators"] for r in o["replicas"]]
    assert len(g._workers) == 1 and len(reps) == 3
    assert sum(1 for r in reps if r["Thread_cpu_usec"] > 0) == 1
    assert sum(1 for r in reps if r["Thread_wall_usec"] > 0) == 1


# ---------------------------------------------------------------------------
# (e) stable program names
# ---------------------------------------------------------------------------
def _program_names(cache):
    return {p._wrapped_jit.__name__ for p in cache.values()
            if hasattr(p, "_wrapped_jit")}


def test_window_programs_keep_the_names_the_roofline_metric_reads(served):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "window_step_roofline.sat.json")) as f:
        pattern = re.compile(json.load(f)["params"]["modules"])
    replica = _replica(served["graph"], "win")
    names = _program_names(replica._prog_cache)
    assert names == {"step", "fire", "rebuild"}
    # XLA names a module jit_<function name>
    assert all(pattern.search("jit_" + n) for n in names)


def test_time_based_programs_take_the_pack_and_the_arguments_they_took(
        served):
    """PR 39 gave a time-based plan the count-based layout: per firing
    key slot, never per lane. A time-based operator's plan is ``1 + 2
    (G_CAP + 3) + (5 + key words) min(K_cap, W)`` words, its head the
    group table and the two dirty ring ranges a step's level rebuild
    goes by; its programs take the batch's columns, the composite,
    the forest and the plan, and no key table; the rule that chooses
    the scan is never asked about it."""
    import inspect

    from windflow_tpu.tpu import ffat_tpu

    replica = _replica(served["graph"], "win")
    assert replica.slide_units == 1 and ffat_tpu.G_CAP == 32
    for W, K_cap, kw in ((8, 16, 1), (64, 16, 2), (32768, 4096, 1)):
        n = ffat_tpu.plan_len(W, K_cap, True, kw)
        assert n == 1 + 70 + (5 + kw) * min(K_cap, W)
        groups, chunks, total = ffat_tpu.plan_views(
            np.zeros(n, np.int32), K_cap, True, kw)
        assert (groups.shape, chunks.shape, total.shape) == (
            (35, 2), (5 + kw, min(K_cap, W)), (1,))
    by_name = {p._wrapped_jit.__name__: p._wrapped_jit
               for p in replica._prog_cache.values()
               if hasattr(p, "_wrapped_jit")}
    args = {n: list(inspect.signature(f).parameters)
            for n, f in by_name.items()}
    assert args == {
        "step": ["fields", "comp", "trees", "tvalid", "fire_plan"],
        "fire": ["trees", "tvalid", "fire_plan"],
        "rebuild": ["trees", "tvalid"]}
    # a plan of this operator: two keys' next windows, one ring range
    slots = np.array([replica._keymap.slot(1000 + k) for k in range(2)])
    chunks = (slots, np.full(2, 40), np.ones(2, np.int64),
              np.full(2, 10), np.full(2, 43))
    keys = replica._chunk_keys(chunks[0])
    pairs = np.unique(replica._range_words(chunks[1], chunks[2],
                                           chunks[4] + 1)[0])
    pack, n_groups = replica._pack_fire_arrays(chunks, 8, keys, pairs)
    groups, rows, total = ffat_tpu.plan_views(pack, replica.K_cap, True, 1)
    assert replica._key_words() == 1
    assert pack.dtype == np.int32 and pack.size == replica._plan_len(8) \
        == 1 + 70 + 6 * min(replica.K_cap, 8)
    assert n_groups == 1 == groups[32, 0] and total[0] == 2
    assert rows[:, :2].T.tolist() == [
        [s, 40 % replica.F, 1, 10, 4, 1000 + k]
        for k, s in enumerate(slots.tolist())]
    assert not rows[:, 2:].any()


def test_fused_chain_program_carries_its_operators_names(served):
    replica = _replica(served["graph"], "views")
    assert _program_names(replica._prog_cache) == {"chain_views_join"}
    assert tracing.program_name("chain", "views", "jo∘in") == \
        "chain_views_jo_in"


def _replica(graph, op_name):
    for w in graph._workers:
        for node in w.chain:
            ops = getattr(node, "ops", None) or [getattr(node, "op", None)]
            if any(getattr(o, "name", None) == op_name for o in ops):
                return node
    raise KeyError(op_name)


# ---------------------------------------------------------------------------
# (f) the CPU plane's per-tuple path has no stage
# ---------------------------------------------------------------------------
def test_cpu_plane_times_nothing_per_tuple(monkeypatch):
    n, used = 3000, []
    real = tracing.Stage.__call__

    def counting(self, b=0, cause=0):
        used.append(self.name)
        return real(self, b, cause)

    monkeypatch.setattr(tracing.Stage, "__call__", counting)
    got = []

    def src(shipper):
        for i in range(n):
            shipper.push({"v": i})

    g = PipeGraph("cpu_plane", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).build()) \
        .add(Map_Builder(lambda t: {"v": t["v"] + 1}).build()) \
        .add_sink(Sink_Builder(
            lambda t: got.append(t) if t is not None else None).build())
    g.run()
    assert len(got) == n
    # only a channel's blocked branches may open a span there: a thread
    # that found its queue full or empty, never a tuple's processing
    assert set(used) <= {"put", "get"}, set(used)


# ---------------------------------------------------------------------------
# (g) the host's time, accounted from inside the program (PR 36): a
# worker's wall split into CPU / backpressured / starved / on the device /
# unaccounted, CPU beside wall on four stages, the process's stalls
# ---------------------------------------------------------------------------
ACCOUNT = ("Thread_cpu_usec", "Worker_blocked_put_usec",
           "Worker_blocked_get_usec", "Worker_device_wait_usec",
           "Worker_unaccounted_usec")


@pytest.mark.parametrize("op", ["src", CHAIN, "win", "snk"])
def test_worker_account_sums_to_its_wall(served, op):
    rep = served["stats"][op]
    parts = [rep[f] for f in ACCOUNT]
    assert all(p >= 0 for p in parts), dict(zip(ACCOUNT, parts))
    wall = rep["Thread_wall_usec"]
    assert wall > 0
    assert sum(parts) == pytest.approx(wall, rel=0.01), \
        dict(zip(ACCOUNT, parts), wall=wall)
    # frozen once the thread has ended
    again = {o["name"]: o["replicas"][0]
             for o in served["graph"].get_stats()["Operators"]}[op]
    for f in ACCOUNT + ("Thread_wall_usec",):
        assert again[f] == rep[f], f


def test_worker_account_is_where_the_waits_are(served):
    st = served["stats"]
    # a source has no input channel and never starves; a sink puts into
    # no channel and is never backpressured
    assert st["src"]["Worker_blocked_get_usec"] == 0
    assert st["snk"]["Worker_blocked_put_usec"] == 0
    # every channel-fed worker waited for its first block at least
    for op in (CHAIN, "win", "snk"):
        assert st[op]["Worker_blocked_get_usec"] > 0, op
    # a wait on the device is the wall less the CPU of the spans that
    # read from it or launch on it: the sink's reads of device arrays,
    assert st["snk"]["Sink_d2h_wait_total_usec"] > 0
    assert st["snk"]["Worker_device_wait_usec"] == pytest.approx(max(
        0, st["snk"]["Sink_d2h_wait_total_usec"]
        - st["snk"]["Sink_d2h_cpu_total_usec"]), abs=1.0)
    # the chain's readbacks and launches
    c = st[CHAIN]
    assert c["Dispatch_readback_wait_total_usec"] > 0
    assert c["Worker_device_wait_usec"] == pytest.approx(max(
        0, c["Dispatch_readback_wait_total_usec"]
        - c["Dispatch_readback_cpu_total_usec"]
        + c["Device_launch_total_usec"]
        - c["Device_launch_cpu_total_usec"]), abs=1.0)


def test_worker_account_counts_each_worker_once():
    got = []

    def src(shipper):
        for i in range(200):
            shipper.push({"v": i})

    g = PipeGraph("one_account", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .chain(Map_Builder(lambda t: t).with_name("m").build()) \
        .chain_sink(Sink_Builder(lambda t: got.append(t))
                    .with_name("k").build())
    g.run()
    reps = [r for o in g.get_stats()["Operators"] for r in o["replicas"]]
    assert len(g._workers) == 1 and len(reps) == 3
    reporting = [r for r in reps if r["Thread_wall_usec"] > 0]
    assert len(reporting) == 1
    for r in reps:
        if r is not reporting[0]:
            assert all(r[f] == 0 for f in ACCOUNT), r["Operator_name"]


def test_backpressure_is_counted_on_both_sides():
    """One producer, a slow consumer: the spans of the blocked ``put``
    are the consumer's ``Queue_blocked_put_usec`` and the producer's own
    ``Worker_blocked_put_usec``."""
    import time

    def src(shipper):
        for i in range(60):
            shipper.push({"v": i})

    def slow(t):
        if t is not None:
            time.sleep(0.002)

    g = PipeGraph("both_sides", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME, channel_capacity=4)
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .add_sink(Sink_Builder(slow).with_name("k").build())
    g.run()
    st = {o["name"]: o["replicas"][0] for o in g.get_stats()["Operators"]}
    assert st["k"]["Queue_puts_blocked"] > 0
    assert st["s"]["Worker_blocked_put_usec"] == pytest.approx(
        st["k"]["Queue_blocked_put_usec"], rel=0.02)
    assert st["s"]["Worker_blocked_put_usec"] > 20_000  # most of 120 ms
    assert st["k"]["Worker_blocked_put_usec"] == 0
    # and the consumer's own record still says whose queue was full
    assert st["s"]["Queue_blocked_put_usec"] == 0


def test_cpu_beside_wall_on_the_stages_that_name_a_field(monkeypatch):
    import time

    from windflow_tpu.monitoring.stats import StatsRecord

    calls = []
    real = tracing._cpu_ns
    monkeypatch.setattr(tracing, "_cpu_ns",
                        lambda: calls.append(1) or real())
    st = StatsRecord("op", 0)
    with st.stage("prep")(1):
        time.sleep(0.02)  # off the processor: wall, not CPU
    d = st.to_dict()
    assert d["Dispatch_host_prep_total_usec"] \
        - d["Dispatch_host_prep_cpu_total_usec"] >= 18_000
    assert len(calls) == 2
    # a stage whose StageDef names no ``cpu`` field never reads that clock
    plain = [n for n, sdef in tracing.STAGES.items() if sdef.cpu is None]
    assert {"put", "get", "emit", "stage", "h2d", "sink", "exit",
            "fireplan", "keys"} <= set(plain)
    for name in plain:
        with st.stage(name)(1):
            pass
    st.stage("queue").since(tracing.stamp_ns(), 1)
    assert len(calls) == 2
    # and a stage that names one reads it on one span in CPU_EVERY (a
    # system call, 5.8 us on the benchmark's host), scaled up
    commit = st.stage("commit")
    for b in range(2 * tracing.CPU_EVERY):
        with commit(b):
            pass
    assert len(calls) == 2 + 2 * 2
    assert st.stage_count("commit") == 2 * tracing.CPU_EVERY
    assert {sdef.cpu for sdef in tracing.STAGES.values() if sdef.cpu} == {
        "Dispatch_host_prep_cpu_total_usec", "Dispatch_commit_cpu_total_usec",
        "Device_launch_cpu_total_usec", "Ingest_cpu_total_usec",
        "Dispatch_readback_cpu_total_usec", "Sink_d2h_cpu_total_usec"}


def test_launch_wall_less_cpu_is_a_wait_on_the_device():
    """``launch`` adds its wall less its CPU to the calling thread's
    account; ``put`` its whole wall; a thread without an account (no
    worker) is left alone."""
    import time

    acct = tracing.new_thread_account()
    out = {}

    def body():
        tracing.set_thread_account(acct)
        c = tracing.StageCounters("op")
        with c.stage("launch")():
            time.sleep(0.02)
        out["cpu"] = c.stage_cpu_usec("launch")
        out["wall"] = c.stage_usec("launch")
        with c.stage("emit")():
            time.sleep(0.005)  # not a wait the table marks

    t = threading.Thread(target=body)
    t.start()
    t.join(30)
    assert not t.is_alive()
    assert acct[tracing.BACKPRESSURED] == 0 == acct[tracing.STARVED]
    assert acct[tracing.DEVICE_WAIT] / 1e3 == pytest.approx(
        out["wall"] - out["cpu"], abs=1.0)
    assert acct[tracing.DEVICE_WAIT] >= 18_000_000
    # this thread has no account: the same span adds nowhere
    c = tracing.StageCounters("op")
    with c.stage("launch")():
        pass
    assert acct[tracing.DEVICE_WAIT] / 1e3 == pytest.approx(
        out["wall"] - out["cpu"], abs=1.0)


def test_a_parked_worker_reads_starved_not_unaccounted():
    """A wait still open at poll time is counted up to that instant: a
    sink parked on its empty channel while the source holds back."""
    import time

    gate = threading.Event()

    def src(shipper):
        assert gate.wait(60)
        shipper.push({"v": 1})

    g = PipeGraph("parked", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .add_sink(Sink_Builder(lambda t: None).with_name("k").build())
    g.start()
    try:
        time.sleep(0.3)
        k = g.get_stats()["Operators"][1]["replicas"][0]
    finally:
        gate.set()
        g.wait_end()
    assert k["Operator_name"] == "k"
    assert k["Worker_blocked_get_usec"] >= 0.8 * k["Thread_wall_usec"] \
        > 100_000
    assert k["Worker_unaccounted_usec"] <= 0.2 * k["Thread_wall_usec"]
    end = g.get_stats()["Operators"][1]["replicas"][0]
    assert end["Worker_blocked_get_usec"] >= k["Worker_blocked_get_usec"]


def _watchdog(recorders=()):
    import types

    from windflow_tpu.monitoring.flightrec import StallWatchdog
    graph = types.SimpleNamespace(name="g", _workers=[],
                                  _recorders=list(recorders))
    return StallWatchdog(graph)


@pytest.mark.parametrize("late_ms,cpu_ms,gc_ms,stalls", [
    (0.08, 0.01, 0.0, 0),     # timer slack: a tick, no stall
    (4.9, 4.0, 0.0, 0),       # a turn on the interpreter lock: no stall
    (250.0, 1.0, 0.0, 1),     # the host took the process off the CPU
    (250.0, 255.0, 0.0, 1),   # a thread kept the interpreter
    (400.0, 390.0, 380.0, 1),  # ... and it was the garbage collector
])
def test_watchdog_tick_is_a_pure_function_of_three_clocks(
        late_ms, cpu_ms, gc_ms, stalls):
    from windflow_tpu.monitoring.flightrec import TICK_NS
    wd = _watchdog()
    t0, c0, g0 = 5_000_000_000, 70_000_000, 3_000_000
    assert wd._tick(t0, c0, g0) is None  # the first reading only arms
    assert wd.ticks == 0
    late = int(late_ms * 1e6)
    stall = wd._tick(t0 + TICK_NS + late, c0 + int(cpu_ms * 1e6),
                     g0 + int(gc_ms * 1e6))
    f = wd.process_fields()
    assert f["Process_ticks"] == 1
    assert f["Process_tick_late_total_usec"] == pytest.approx(late / 1e3)
    assert f["Process_stalls"] == stalls
    if not stalls:
        assert stall is None
        assert f["Process_stall_usec"] == 0 == f["Process_stall_cpu_usec"]
        return
    assert stall == {"late_us": pytest.approx(late / 1e3),
                     "cpu_us": pytest.approx(cpu_ms * 1e3),
                     "gc_us": pytest.approx(gc_ms * 1e3)}
    assert f["Process_stall_usec"] == pytest.approx(late / 1e3)
    # the cause: CPU near 0 across the gap, or near the gap
    assert f["Process_stall_cpu_usec"] == pytest.approx(cpu_ms * 1e3)
    # an on-time tick after it counts a tick and no second stall
    assert wd._tick(t0 + 2 * TICK_NS + late + 1000, c0, g0) is None
    assert wd.process_fields()["Process_stalls"] == 1
    assert wd.process_fields()["Process_ticks"] == 2


def test_process_stall_is_reported_with_its_cause(capsys):
    """A thread that keeps the interpreter for a quarter of a second (a
    busy loop under a long switch interval): the watchdog's wake-up is
    that late, and the stall is in ``Process_stalls``, on stderr and in
    the ring, its CPU near the gap."""
    import sys
    import time

    def src(shipper):
        time.sleep(0.1)  # the watchdog is asleep in its tick by now
        before = sys.getswitchinterval()
        sys.setswitchinterval(2.0)
        try:
            end = time.perf_counter() + 0.25
            while time.perf_counter() < end:
                pass
        finally:
            sys.setswitchinterval(before)
        shipper.push({"v": 1})
        time.sleep(0.05)  # let the late wake-up be counted

    g = PipeGraph("held", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.with_flight_recorder()
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .add_sink(Sink_Builder(lambda t: None).with_name("k").build())
    g.run()
    assert not g._watchdog.is_alive()  # stopped and joined with the graph
    rep = g.get_stats()["Operators"][0]["replicas"][0]
    assert rep["Process_stalls"] >= 1
    assert rep["Process_stall_usec"] >= 100_000
    # a thread kept the interpreter: the process was on the CPU
    assert rep["Process_stall_cpu_usec"] >= 0.25 * rep["Process_stall_usec"]
    assert rep["Process_ticks"] > 5
    assert "the process stood still" in capsys.readouterr().err
    events = [e for e in g.trace_document()["traceEvents"]
              if e.get("name") == "stall:process"]
    assert events and set(events[0]["args"]) == {"late_us", "cpu_us",
                                                 "gc_us"}
    assert events[0]["args"]["late_us"] >= 100_000


def test_process_fields_sit_on_one_record_and_gc_is_counted():
    import gc
    import time

    def src(shipper):
        time.sleep(0.05)  # the watchdog thread has registered its callback
        gc.collect()
        for i in range(10):
            shipper.push({"v": i})

    g = PipeGraph("one_record", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_name("s").build()) \
        .add(Map_Builder(lambda t: t).with_name("m").build()) \
        .add_sink(Sink_Builder(lambda t: None).with_name("k").build())
    n_callbacks = len(gc.callbacks)
    g.run()
    assert len(gc.callbacks) == n_callbacks  # taken back with the thread
    reps = [r for o in g.get_stats()["Operators"] for r in o["replicas"]]
    fields = ("Process_ticks", "Process_tick_late_total_usec",
              "Process_stalls", "Process_stall_usec",
              "Process_stall_cpu_usec", "Gc_pause_total_usec",
              "Gc_collections_full")
    holders = [r for r in reps if any(f in r for f in fields)]
    assert len(holders) == 1 and holders[0]["Operator_name"] == "s"
    assert all(f in holders[0] for f in fields)
    assert holders[0]["Gc_collections_full"] >= 1
    assert holders[0]["Gc_pause_total_usec"] > 0
    assert holders[0]["Process_ticks"] >= 3


# the eleven per-layer metrics that read the account (benchmark/metrics/):
# data files through a reader that is there; each counter they name is a
# field the program reports
ACCOUNT_METRICS = (
    "window_busy_share.sat", "window_input_wait_share.sat",
    "window_backpressured_share.sat", "window_device_wait_share.sat",
    "window_unaccounted_share.sat", "first_backpressured_share.sat",
    "interp_wait_cores.sat", "interp_acquire_us.sat",
    "process_stall_share.sat", "gc_pause_share.sat",
    "dispatch_cpu_us_per_batch.sat")


@pytest.mark.parametrize("name", ACCOUNT_METRICS)
def test_account_metric_reads_a_counter_the_program_has(name):
    from windflow_tpu.monitoring.stats import StatsRecord

    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and "workloads" not in entry[0]  # every cell
    # appended together in PR 36 (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(ACCOUNT_METRICS[0])
    assert tuple(names[first:first + 11]) == ACCOUNT_METRICS
    for k in ("unit", "layer", "source", "moves"):
        assert entry[0][k] == spec[k], k
    assert spec["source"] == "program_counter"
    assert spec["moves"] == "events_per_s" and entry[0]["better"] == "lower"
    # nothing, not 0, from a program without the counter
    assert spec["reader"] == "counter_ratio_present.py"
    fields = set(StatsRecord("op", 0).to_dict()) | set(
        _watchdog().process_fields())
    den = spec["params"]["den"]
    pairs = spec["params"]["num"] + (den if isinstance(den, list) else [])
    for role, field in pairs:
        assert field in fields, (role, field)
        assert role in ("window", "first", "*"), role
