"""Event-time health plane tests.

Three legs:

- late-record CONSERVATION: an identical deterministic late stream
  (half of the late tuples admissibly late, half beyond the allowed
  lateness) is replayed through every window engine — Keyed_Windows
  CPU, FFAT CPU, FFAT device, fused window-terminated device chain,
  mesh — and each must satisfy the exact invariant
  ``Inputs_received == on_time + Late_admitted + Late_dropped`` with
  the model-predicted counts; all FFAT engines must agree exactly on
  ``Late_dropped``;
- WATERMARK plumbing: advance tracking through an operator chain, the
  idle/stalled distinction in ``poll_watermark``, and a live
  frozen-watermark graph incrementing ``Watermark_stalls`` with the
  doctor naming ``event-time-stalled``;
- the pipeline DOCTOR: deterministic synthetic-snapshot scenarios for
  the acceptance bottlenecks (backpressured-by a slow sink,
  overloaded/shedding, ingest-bound) plus dispatch-bound, healthy, and
  the stateful ``PipelineDoctor`` wrapper + text rendering.

The stream advances its watermark only every ``WM_EVERY`` tuples with
an output batch size dividing it, so every device batch carries ONE
watermark that equals the per-tuple watermark the CPU engines see —
late classification is then identical across batched and per-tuple
paths by construction.
"""

import time

import pytest

from windflow_tpu import (ExecutionMode, Ffat_Windows_Builder,
                          Interval_Join_Builder, Keyed_Windows_Builder,
                          PipeGraph, Sink_Builder, Source_Builder,
                          TimePolicy)
from windflow_tpu.monitoring.doctor import (PipelineDoctor, diagnose,
                                            render_text)
from windflow_tpu.monitoring.stats import StatsRecord
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder, Map_TPU_Builder

# after a warm-up, every 20th tuple lags by an ADMISSIBLE 3 ms (within
# the 4.5 ms allowed lateness) and every 20th+7 by an INADMISSIBLE
# 10 ms. The watermark advances every 2.5 ms (WM_EVERY * TS_STEP), so an
# admissible straggler's pane starts at least 525 µs ABOVE the purge
# frontier (wm - lateness) and an inadmissible one's pane ends at least
# 2 ms BELOW it — drop/admit never rides a pane-quantization boundary.
# The warm-up guarantees every late tuple targets a window that on-time
# traffic populated and (for the inadmissible ones) already fired.
N = 2_000
TS_STEP = 25
WM_EVERY = 100
OBS = 50  # output batch size; divides WM_EVERY
WARMUP = 600
LATENESS = 4_500
LATE_ADMIT_US = 3_000
LATE_DROP_US = 10_000
WIN = SLIDE = 1_000  # tumbling: pane == window on every engine
N_KEYS = 8
TS0 = 200_000  # offset keeps late timestamps in positive event time


def late_src(shipper, ctx):
    ts = TS0
    for i in range(N):
        ts += TS_STEP
        if i % 20 == 0 and i >= WARMUP:
            t = ts - LATE_ADMIT_US
        elif i % 20 == 7 and i >= WARMUP:
            t = ts - LATE_DROP_US
        else:
            t = ts
        shipper.push_with_timestamp({"key": i % N_KEYS, "value": 1}, t)
        if (i % WM_EVERY) == WM_EVERY - 1:
            shipper.set_next_watermark(ts)


def expected_late_counts():
    """Replay ``late_src`` against the shipper's watermark semantics
    (``set_next_watermark`` applies to SUBSEQUENT pushes): a tuple is
    late iff its ts is behind the watermark riding its own push."""
    wm = next_wm = 0
    ts, admit, drop = TS0, 0, 0
    for i in range(N):
        ts += TS_STEP
        wm = max(wm, next_wm)
        if i % 20 == 0 and i >= WARMUP and ts - LATE_ADMIT_US < wm:
            admit += 1
        elif i % 20 == 7 and i >= WARMUP and ts - LATE_DROP_US < wm:
            drop += 1
        if (i % WM_EVERY) == WM_EVERY - 1:
            next_wm = ts
    return admit, drop


def _late_counters(op):
    out = {}
    for k in ("Inputs_received", "Late_records", "Late_dropped",
              "Late_admitted"):
        out[k] = sum(r.get(k, 0) for r in op["replicas"])
    return out


def _find_op(g, name=None, kind=None):
    for o in g.get_stats()["Operators"]:
        if (name is None or o["name"] == name) \
                and (kind is None or o["kind"] == kind):
            return o
    raise AssertionError(f"operator {name or kind} not found")


def run_late_replay(engine, monkeypatch):
    """Replay the deterministic late stream through one window engine;
    returns the window operator's late-accounting counters."""
    # the device plane decides lateness per batch, and the model assumes
    # batches of OBS rows: no partial batch may ship by wall-clock age
    # (a loaded machine then moves the batch boundaries and the counts)
    monkeypatch.setenv("WF_MAX_STAGING_MS", "0")
    # nor by a punctuation: an emitter that has sent DEFAULT_WM_AMOUNT
    # (64) more tuples and finds 100 ms of wall clock gone flushes its
    # partial batch ahead of the punctuation, 14 rows into a batch of
    # 50; every later batch then straddles a watermark advance, carries
    # the older one, and counts fewer rows late. Watermarks still ride
    # the batches and end-of-stream still flushes
    monkeypatch.setattr(
        "windflow_tpu.runtime.emitters.DEFAULT_WM_INTERVAL_USEC",
        float("inf"))
    g = PipeGraph(f"evt_health_{engine}", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    src = Source_Builder(late_src).with_output_batch_size(OBS).build()
    results = []
    snk = Sink_Builder(
        lambda r: results.append(r) if r is not None else None).build()
    if engine == "keyed_cpu":
        op = (Keyed_Windows_Builder(lambda ws: len(list(ws)))
              .with_key_by(lambda t: t["key"])
              .with_tb_windows(WIN, SLIDE).with_lateness(LATENESS)
              .with_name("win").build())
    elif engine == "ffat_cpu":
        op = (Ffat_Windows_Builder(lambda t: 1, lambda a, b: a + b)
              .with_key_by(lambda t: t["key"])
              .with_tb_windows(WIN, SLIDE).with_lateness(LATENESS)
              .with_name("win").build())
    else:  # device variants share the Ffat_Windows_TPU program
        b = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"]},
                lambda a, b_: {"value": a["value"] + b_["value"]})
             .with_key_by("key").with_tb_windows(WIN, SLIDE)
             .with_lateness(LATENESS).with_name("win"))
        if engine == "mesh":
            b = b.with_key_capacity(N_KEYS).with_mesh()
        op = b.build()
    mp = g.add_source(src)
    if engine == "fused":
        # window-terminated fused chain: a stateless Map_TPU prefix
        # composes INTO the window replica's step program
        # (FusedFfatReplica) under WF_TPU_FUSION=1
        monkeypatch.setenv("WF_TPU_FUSION", "1")
        pre = (Map_TPU_Builder(lambda f: {**f, "value": f["value"]})
               .with_name("pre").build())
        mp = mp.add(pre).chain(op)
    else:
        mp = mp.add(op)
    mp.add_sink(snk)
    g.run()
    win_op = (_find_op(g, kind="Fused_TPU_Chain") if engine == "fused"
              else _find_op(g, name="win"))
    assert results, f"{engine}: no windows fired"
    return _late_counters(win_op)


# ---------------------------------------------------------------------------
# late-record conservation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["keyed_cpu", "ffat_cpu", "ffat_tpu",
                                    "fused", "mesh"])
def test_late_conservation_invariant(engine, monkeypatch):
    exp_admit, exp_drop = expected_late_counts()
    assert exp_admit > 0 and exp_drop > 0  # the shape exercises both
    st = run_late_replay(engine, monkeypatch)
    assert st["Inputs_received"] == N
    # exact conservation: every input classified exactly once
    on_time = st["Inputs_received"] - st["Late_records"]
    assert on_time + st["Late_admitted"] + st["Late_dropped"] == N
    assert st["Late_admitted"] == st["Late_records"] - st["Late_dropped"]
    # and the classification matches the model exactly
    assert st["Late_admitted"] == exp_admit, st
    assert st["Late_dropped"] == exp_drop, st
    assert st["Late_records"] == exp_admit + exp_drop, st


def test_late_drop_agreement_across_engines(monkeypatch):
    """The SAME stream through every FFAT engine (CPU, device, fused
    chain, mesh) must agree exactly on what was dropped."""
    counts = {e: run_late_replay(e, monkeypatch)
              for e in ("ffat_cpu", "ffat_tpu", "fused", "mesh")}
    drops = {e: c["Late_dropped"] for e, c in counts.items()}
    lates = {e: c["Late_records"] for e, c in counts.items()}
    assert len(set(drops.values())) == 1, drops
    assert len(set(lates.values())) == 1, lates
    assert drops["ffat_cpu"] == expected_late_counts()[1]


def test_interval_join_counts_admitted_late():
    """The join never drops: late probes are admitted-late only."""
    n_straggler = 50

    def src_a(shipper, ctx):
        # high timestamps, watermark never set: side A can never be
        # late, and contributes nothing to the join's watermark
        for i in range(20):
            shipper.push_with_timestamp(
                {"key": 0, "value": i}, 10_000_000 + i)

    def src_b(shipper, ctx):
        ts = 0
        for i in range(200):
            ts += 100
            shipper.push_with_timestamp({"key": 0, "value": i}, ts)
            if i % 10 == 9:
                shipper.set_next_watermark(ts)
        # stragglers ride with their OWN stream's watermark (20_000),
        # so they arrive late deterministically — the join's watermark
        # is at least the one carried by the tuple itself
        for j in range(n_straggler):
            shipper.push_with_timestamp(
                {"key": 0, "value": -j}, ts - 19_000 + j)

    g = PipeGraph("evt_health_join", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    op = (Interval_Join_Builder(lambda a, b: (a["value"], b["value"]))
          .with_key_by(lambda t: t["key"])
          .with_boundaries(-500, 500).with_name("join").build())
    mpa = g.add_source(Source_Builder(src_a).build())
    mpb = g.add_source(Source_Builder(src_b).build())
    mpa.merge(mpb).add(op).add_sink(Sink_Builder(lambda t: None).build())
    g.run()
    st = _late_counters(_find_op(g, name="join"))
    assert st["Late_records"] >= n_straggler
    assert st["Late_dropped"] == 0
    assert st["Late_admitted"] == st["Late_records"]


def test_lateness_histogram_scalar_and_batched_paths_agree():
    """``note_late`` feeds the lateness histogram identically through
    the scalar (CPU) and array (device) paths."""
    a = StatsRecord("x", 0, sample_every=1)
    b = StatsRecord("y", 0, sample_every=1)
    vals = [3, 17, 255, 256, 1_000_000, 0, 50_000] * 13
    a.note_late(len(vals), 5, vals)           # batched device path
    for v in vals:                            # scalar CPU path
        b.note_late(1, 0, v)
    assert a.hist_lateness.counts == b.hist_lateness.counts
    assert a.hist_lateness.count == len(vals)
    assert a.hist_lateness.sum_us == b.hist_lateness.sum_us
    assert a.late_records == b.late_records == len(vals)
    assert a.late_dropped == 5
    d = a.to_dict()
    assert d["Late_admitted"] == len(vals) - 5
    assert d["Latency_lateness_samples"] == len(vals)


# ---------------------------------------------------------------------------
# watermark plumbing
# ---------------------------------------------------------------------------
def test_watermark_poll_idle_vs_stalled(monkeypatch):
    monkeypatch.setenv("WF_WM_STALL_SEC", "0.5")
    st = StatsRecord("op", 0)
    t0 = time.monotonic()
    st.wm_current, st.wm_advances = 100, 1
    assert st.poll_watermark(t0) == 0.0  # advance observed: lag resets
    # no inputs since the advance: IDLE, never a stall
    assert st.poll_watermark(t0 + 2.0) == pytest.approx(2e6)
    assert st.wm_stalls == 0
    assert st.to_dict()["Watermark_idle"] == 1
    # inputs flowing + frozen watermark past the threshold: one stall
    st.inputs_received += 10
    st.poll_watermark(t0 + 3.0)
    assert st.wm_stalls == 1
    # edge-triggered: polling again does not double-count
    st.poll_watermark(t0 + 4.0)
    assert st.wm_stalls == 1
    # the next advance re-arms the trigger
    st.wm_advances = 2
    assert st.poll_watermark(t0 + 5.0) == 0.0
    st.inputs_received += 10
    st.poll_watermark(t0 + 6.0)
    assert st.wm_stalls == 2


def test_watermark_advances_through_operator_chain():
    """Punctuations drive wm_current/wm_advances on every replica; the
    event-time lag derives from the max pushed source ts."""
    def src(shipper, ctx):
        ts = 0
        for i in range(300):
            ts += 100
            shipper.push_with_timestamp({"key": 0, "value": i}, ts)
            if i % 30 == 29:
                shipper.set_next_watermark(ts - 1_000)
        # trailing push applies the last watermark (set_next_watermark
        # takes effect on the NEXT push)
        shipper.push_with_timestamp({"key": 0, "value": -1}, ts)

    g = PipeGraph("evt_health_wm", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    g.add_source(Source_Builder(src).with_output_batch_size(10).build()) \
        .add_sink(Sink_Builder(lambda t: None).with_name("snk").build())
    g.run()
    src_rep = _find_op(g, kind="Source")["replicas"][0]
    snk_rep = _find_op(g, name="snk")["replicas"][0]
    assert src_rep["Watermark_current_ts"] == 29_000
    assert src_rep["Watermark_advances"] == 10
    # the source saw ts up to 30_000 while its watermark is 29_000
    assert src_rep["Watermark_event_lag_usec"] == 1_000
    # the sink's watermark follows the source's punctuations
    assert snk_rep["Watermark_current_ts"] == 29_000
    assert snk_rep["Watermark_advances"] >= 1


def test_frozen_watermark_stalls_and_doctor_names_it(monkeypatch):
    """A live graph whose source keeps pushing but never advances its
    watermark: ``Watermark_stalls`` increments and the doctor's verdict
    is event-time-stalled."""
    monkeypatch.setenv("WF_WM_STALL_SEC", "0.2")
    stop = [False]

    def src(shipper, ctx):
        ts = 0
        while not stop[0]:
            ts += 10
            shipper.push_with_timestamp({"key": 0, "value": 1}, ts)
            if ts == 10:
                shipper.set_next_watermark(1)  # first and only advance
            time.sleep(0.0005)

    g = PipeGraph("evt_health_stall", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    g.add_source(Source_Builder(src).with_output_batch_size(8).build()) \
        .add_sink(Sink_Builder(lambda t: None).build())
    g.start()
    try:
        pd = PipelineDoctor(stall_sec=0.2)
        pd.observe("g", g.get_stats())
        diag, stalled = None, []
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            time.sleep(0.35)
            diag = pd.observe("g", g.get_stats())
            stalled = [f for f in (diag["findings"] if diag else [])
                       if f["verdict"] == "event-time-stalled"]
            if stalled:
                break
        assert stalled, diag and render_text(diag)
        src_op = _find_op(g, kind="Source")
        assert sum(r["Watermark_stalls"]
                   for r in src_op["replicas"]) >= 1
    finally:
        stop[0] = True
        g.wait_end()


# ---------------------------------------------------------------------------
# pipeline doctor: deterministic synthetic-snapshot scenarios
# ---------------------------------------------------------------------------
def _rep(**kw):
    base = {"Replica_id": 0, "Inputs_received": 0, "Outputs_sent": 0,
            "Queue_blocked_put_usec": 0, "Queue_blocked_get_usec": 0,
            "Shed_records": 0, "Watermark_idle": 0}
    base.update(kw)
    return base


def _graph(ops, overload=None):
    g = {"Operators": [{"name": n, "kind": k, "parallelism": 1,
                        "replicas": reps} for n, k, reps in ops]}
    if overload:
        g["Overload"] = overload
    return g


_PREV3 = _graph([("src", "Source", [_rep()]), ("map", "Map", [_rep()]),
                 ("snk", "Sink", [_rep()])])


def test_doctor_blames_slow_sink_backpressure():
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=10_000)]),
        ("map", "Map", [_rep(Inputs_received=9_000)]),
        ("snk", "Sink", [_rep(Inputs_received=4_000,
                              Queue_blocked_put_usec=800_000,
                              Queue_len=60, Queue_capacity=64,
                              Service_time_usec=210.0)])])
    d = diagnose(_PREV3, cur, 1.0)
    assert not d["healthy"]
    assert d["bottleneck"]["operator"] == "snk"
    assert d["bottleneck"]["verdict"] == "compute-bound"
    bp = [f for f in d["findings"] if f["verdict"] == "backpressured-by"]
    assert {f["operator"] for f in bp} == {"src", "map"}
    assert all(f["by"] == "snk" for f in bp)
    assert "snk" in d["summary"]


def test_doctor_reads_the_workers_own_account():
    """Records with a worker's account: ``backpressured-by`` goes to the
    operator whose own thread stood blocked (``Worker_blocked_put_usec``),
    not to everything upstream, and a worker whose unaccounted time is
    above its CPU time is named interpreter-bound."""
    acct = dict(Worker_blocked_put_usec=0, Worker_unaccounted_usec=0,
                Thread_cpu_usec=0)
    prev = _graph([("src", "Source", [_rep(**acct)]),
                   ("map", "Map", [_rep(**acct)]),
                   ("win", "Ffat_Windows_TPU", [_rep(**acct)]),
                   ("snk", "Sink", [_rep(**acct)])])
    cur = _graph([
        # the source pushes into a channel that is never full
        ("src", "Source", [_rep(Inputs_received=10_000,
                                Thread_cpu_usec=300_000,
                                Worker_blocked_put_usec=20_000,
                                Worker_unaccounted_usec=50_000)]),
        # map stood blocked on win's channel for 0.7 s of the second
        ("map", "Map", [_rep(Inputs_received=10_000,
                             Thread_cpu_usec=150_000,
                             Worker_blocked_put_usec=700_000,
                             Worker_unaccounted_usec=100_000)]),
        # win: its producers blocked on it; 0.35 s on the CPU, 0.5 s
        # runnable and not running
        ("win", "Ffat_Windows_TPU", [_rep(Inputs_received=9_000,
                                          Queue_blocked_put_usec=700_000,
                                          Thread_cpu_usec=350_000,
                                          Worker_blocked_put_usec=0,
                                          Worker_unaccounted_usec=500_000)]),
        ("snk", "Sink", [_rep(Inputs_received=100,
                              Thread_cpu_usec=10_000,
                              Worker_blocked_put_usec=0,
                              Worker_unaccounted_usec=5_000)])])
    d = diagnose(prev, cur, 1.0)
    by_verdict = {}
    for f in d["findings"]:
        by_verdict.setdefault(f["verdict"], []).append(f)
    assert d["bottleneck"]["operator"] == "win"
    bp = by_verdict["backpressured-by"]
    assert [f["operator"] for f in bp] == ["map"]  # not src: it never stood
    assert bp[0]["by"] == "win"
    assert bp[0]["evidence"]["blocked_put_frac_own"] == pytest.approx(0.7)
    interp = by_verdict["interpreter-bound"]
    assert [f["operator"] for f in interp] == ["win"]
    assert interp[0]["evidence"]["unaccounted_frac"] == pytest.approx(0.5)
    assert interp[0]["evidence"]["busy_frac"] == pytest.approx(0.35)
    assert "interpreter-bound" in render_text(d)


def test_doctor_flags_overload_shedding_above_backpressure():
    """Shedding outranks everything else: the graph is overloaded even
    when backpressure symptoms coexist."""
    prev = _graph([("src", "Source", [_rep()]), ("snk", "Sink", [_rep()])])
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=5_000,
                                Shed_records=3_000)]),
        ("snk", "Sink", [_rep(Inputs_received=5_000,
                              Queue_blocked_put_usec=500_000)])],
        overload={"Overload_state": 3,
                  "Overload_window_p99_usec": 90_000.0})
    d = diagnose(prev, cur, 1.0)
    top = d["bottleneck"]
    assert top["verdict"] == "overloaded" and top["operator"] == "src"
    assert top["evidence"]["shed_records_delta"] == 3_000
    # backpressure still reported, ranked below
    assert any(f["verdict"] == "compute-bound" for f in d["findings"])


def test_doctor_flags_ingest_bound_source():
    """Every downstream operator starves on an empty queue and nothing
    is backpressured: the source is the bottleneck."""
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=100)]),
        ("map", "Map", [_rep(Inputs_received=100,
                             Queue_blocked_get_usec=900_000,
                             Queue_len=0)]),
        ("snk", "Sink", [_rep(Inputs_received=100,
                              Queue_blocked_get_usec=950_000,
                              Queue_len=0)])])
    d = diagnose(_PREV3, cur, 1.0)
    assert d["bottleneck"]["verdict"] == "ingest-bound"
    assert d["bottleneck"]["operator"] == "src"
    ev = d["bottleneck"]["evidence"]
    assert set(ev["starving_operators"]) == {"map", "snk"}


def test_doctor_flags_dispatch_bound_device_op():
    prev = _graph([("src", "Source", [_rep()]),
                   ("dev", "Map_TPU", [_rep()]),
                   ("snk", "Sink", [_rep()])])
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=5_000)]),
        ("dev", "Map_TPU", [_rep(Inputs_received=5_000,
                                 Dispatch_host_prep_total_usec=100_000,
                                 Dispatch_commit_total_usec=700_000,
                                 Compile_count=5)]),
        ("snk", "Sink", [_rep(Inputs_received=4_000)])])
    d = diagnose(prev, cur, 1.0)
    dis = [f for f in d["findings"] if f["verdict"] == "dispatch-bound"]
    assert dis and dis[0]["operator"] == "dev"
    assert dis[0]["evidence"]["compile_delta"] == 5


def test_doctor_healthy_when_nothing_wrong():
    prev = _graph([("src", "Source", [_rep()]), ("snk", "Sink", [_rep()])])
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=1_000)]),
        ("snk", "Sink", [_rep(Inputs_received=1_000,
                              Queue_blocked_get_usec=100_000)])])
    d = diagnose(prev, cur, 1.0)
    assert d["healthy"] and d["bottleneck"] is None
    assert d["findings"] == []
    assert "healthy" in d["summary"]


def test_doctor_stateful_wrapper_and_render():
    pd = PipelineDoctor(stall_sec=5.0)
    assert pd.observe("g", _PREV3, now=10.0) is None  # first tick: no delta
    cur = _graph([
        ("src", "Source", [_rep(Inputs_received=10_000)]),
        ("map", "Map", [_rep(Inputs_received=9_000)]),
        ("snk", "Sink", [_rep(Inputs_received=4_000,
                              Queue_blocked_put_usec=800_000)])])
    d = pd.observe("g", cur, now=11.0)
    assert d["graph"] == "g" and d["bottleneck"]["operator"] == "snk"
    txt = render_text(d)
    assert "snk" in txt and "backpressured-by" in txt and "evidence" in txt
