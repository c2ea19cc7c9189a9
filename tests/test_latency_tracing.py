"""Latency-tracing / observability plane (monitoring/histogram.py,
monitoring/tracing.py, /metrics export).

- histogram record/merge/percentile invariants against a sorted-list
  oracle (property-style over several distributions/seeds);
- sampled end-to-end latency on source -> map -> sink graphs (per-tuple
  CPU plane, batched CPU plane, and the TPU staging plane on the CPU
  backend);
- queue-occupancy / backpressure gauges under a slow-sink scenario;
- EWMA first-sample seeding (no bias toward 0);
- MonitoringThread bounded reconnect (dashboard started mid-run);
- /metrics scrape + Prometheus text-format validity via
  scripts/check_metrics.py run as the tier-1 smoke.
"""

import os
import random
import socket
import subprocess
import sys
import time

import pytest

from windflow_tpu import (ExecutionMode, Map_Builder, PipeGraph,
                          Sink_Builder, Source_Builder, TimePolicy)
from windflow_tpu.monitoring.histogram import (LatencyHistogram,
                                               bucket_bounds, bucket_index)
from windflow_tpu.monitoring.stats import StatsRecord
from windflow_tpu.monitoring.tracing import parse_sample_rate

from common import GlobalSum, make_ingress_source, make_sum_sink


# ---------------------------------------------------------------------------
# histogram invariants vs a sorted-list oracle
# ---------------------------------------------------------------------------
def _oracle_pct(samples, q):
    import math
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(len(ordered) * q) - 1))]


def _sample_sets():
    rng = random.Random(42)
    yield "uniform", [rng.randint(0, 1_000_000) for _ in range(5000)]
    yield "exponential", [int(rng.expovariate(1 / 500.0))
                          for _ in range(5000)]
    yield "constant", [777] * 1000
    yield "tiny", [0, 1, 2, 3]
    yield "wide", [rng.choice([1, 100, 10_000, 1_000_000, 10**8])
                   for _ in range(2000)]


def test_histogram_percentiles_within_one_bucket():
    for name, samples in _sample_sets():
        h = LatencyHistogram()
        for s in samples:
            h.record(float(s))
        assert h.count == len(samples)
        assert h.max_us == max(samples)
        assert abs(h.sum_us - sum(samples)) < 1e-6 * max(1, sum(samples))
        for q in (0.5, 0.9, 0.99, 1.0):
            orc = _oracle_pct(samples, q)
            got = h.percentile(q)
            # the histogram answers with its bucket's upper edge (clamped
            # to the exact max): within one bucket of the oracle
            b_orc = bucket_index(int(orc))
            b_got = bucket_index(max(0, int(got) - 1))
            assert abs(b_orc - b_got) <= 1, \
                (name, q, orc, got, b_orc, b_got)
            lo, _ = bucket_bounds(max(0, b_orc - 1))
            _, hi = bucket_bounds(min(b_orc + 1, bucket_index(int(h.max_us))))
            assert lo <= got <= max(hi, h.max_us), (name, q, orc, got)


def test_histogram_merge_equals_single_writer():
    rng = random.Random(7)
    samples = [int(rng.expovariate(1 / 2000.0)) for _ in range(4000)]
    whole = LatencyHistogram()
    parts = [LatencyHistogram() for _ in range(4)]
    for i, s in enumerate(samples):
        whole.record(s)
        parts[i % 4].record(s)
    merged = LatencyHistogram.merged(parts)
    assert merged.counts == whole.counts
    assert merged.count == whole.count
    assert merged.max_us == whole.max_us
    assert abs(merged.sum_us - whole.sum_us) < 1e-9 * max(1, whole.sum_us)
    for q in (0.5, 0.9, 0.99):
        assert merged.percentile(q) == whole.percentile(q)


def test_histogram_sparse_roundtrip():
    h = LatencyHistogram()
    for v in (3, 50, 50, 123456, 10**7):
        h.record(v)
    h2 = LatencyHistogram.from_sparse(h.to_sparse())
    assert h2.counts == h.counts
    assert h2.count == h.count
    assert h2.max_us == h.max_us


def test_parse_sample_rate():
    assert parse_sample_rate(1) == 1
    assert parse_sample_rate("1") == 1
    assert parse_sample_rate("1/64") == 64
    assert parse_sample_rate(0.01) == 128  # rounds up to a power of two
    assert parse_sample_rate(0) == 0
    assert parse_sample_rate("") == 0
    assert parse_sample_rate(None) == 0
    assert parse_sample_rate("garbage") == 0
    assert parse_sample_rate("1/0") == 0


# ---------------------------------------------------------------------------
# sampled end-to-end latency (CPU planes)
# ---------------------------------------------------------------------------
def _sink_stats(graph, op_index=-1):
    return graph.get_stats()["Operators"][op_index]["replicas"][0]


@pytest.mark.parametrize("batch", [0, 4])
def test_e2e_latency_cpu_graph(batch):
    n = 3000
    seen = [0]

    def src(shipper):
        for v in range(n):
            shipper.push({"v": v})

    g = PipeGraph("lat_cpu", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_latency_tracing(1)
                 .with_output_batch_size(batch).build()) \
     .add(Map_Builder(lambda t: {"v": t["v"] + 1})
          .with_latency_tracing(1).build()) \
     .add_sink(Sink_Builder(lambda t: seen.__setitem__(0, seen[0] + 1)
                            if t else None).with_latency_tracing(1).build())
    g.run()
    assert seen[0] == n
    sink = _sink_stats(g)
    assert sink["Latency_e2e_samples"] > 0
    assert sink["Latency_e2e_p50_usec"] > 0
    assert sink["Latency_e2e_p99_usec"] >= sink["Latency_e2e_p50_usec"]
    assert sink["Latency_e2e_max_usec"] >= sink["Latency_e2e_p99_usec"]
    # per-operator service percentiles populate alongside the EWMA
    mapr = g.get_stats()["Operators"][1]["replicas"][0]
    assert mapr["Latency_service_samples"] > 0
    assert mapr["Latency_service_p99_usec"] >= mapr["Latency_service_p50_usec"]


def test_e2e_latency_sampling_interval():
    """1/8 sampling records ~1/8th of the tuples at the sink."""
    n = 4000

    def src(shipper):
        for v in range(n):
            shipper.push({"v": v})

    g = PipeGraph("lat_sampled", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_latency_tracing("1/8").build()) \
     .add_sink(Sink_Builder(lambda t: None)
               .with_latency_tracing(1).build())
    g.run()
    sink = _sink_stats(g)
    assert sink["Latency_e2e_samples"] == n // 8


def test_tracing_disabled_adds_no_state():
    """Default (sampling off): no histograms, no samples, no stamp work."""
    n = 500

    def src(shipper):
        for v in range(n):
            shipper.push({"v": v})

    g = PipeGraph("lat_off", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).build()) \
     .add_sink(Sink_Builder(lambda t: None).build())
    g.run()
    sink = _sink_stats(g)
    assert sink["Latency_sample_every"] == 0
    assert sink["Latency_e2e_samples"] == 0
    assert "Latency_e2e_hist" not in sink
    # the replicas allocated no histogram objects at all
    for op in g._ops:
        for r in op.replicas:
            assert r.stats.hist_service is None
            assert r.stats.hist_e2e is None


def test_e2e_latency_device_plane():
    """Source -> Map_TPU -> Sink on the CPU backend: stamps survive the
    columnar staging path (BatchTPU trace_min/max) and the row exit."""
    from windflow_tpu.tpu import Map_TPU_Builder

    acc = GlobalSum()
    g = PipeGraph("lat_tpu", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(make_ingress_source(4, 64))
                 .with_output_batch_size(16)
                 .with_latency_tracing(1).build()) \
     .add(Map_TPU_Builder(lambda f: {**f, "value": f["value"] * 2})
          .with_latency_tracing(1).build()) \
     .add_sink(Sink_Builder(make_sum_sink(acc))
               .with_latency_tracing(1).build())
    g.run()
    assert acc.count == 4 * 64
    sink = _sink_stats(g)
    assert sink["Latency_e2e_samples"] > 0
    assert sink["Latency_e2e_p99_usec"] > 0
    # the device operator recorded dispatch prep/commit histograms
    dev = g.get_stats()["Operators"][1]["replicas"][0]
    assert dev["Latency_prep_samples"] > 0
    assert dev["Latency_commit_samples"] > 0


# ---------------------------------------------------------------------------
# queue gauges under backpressure
# ---------------------------------------------------------------------------
def test_queue_gauges_slow_sink_backpressure():
    n, cap = 600, 8

    def src(shipper):
        for v in range(n):
            shipper.push({"v": v})

    def slow_sink(t):
        if t is not None:
            time.sleep(0.0002)

    g = PipeGraph("backpressure", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME, channel_capacity=cap)
    g.add_source(Source_Builder(src).build()) \
     .add_sink(Sink_Builder(slow_sink).build())
    g.run()
    sink = _sink_stats(g)
    assert sink["Queue_capacity"] == cap
    assert sink["Queue_depth_max"] >= cap  # the queue filled up
    assert sink["Queue_puts_blocked"] > 0  # producer hit backpressure
    assert sink["Queue_blocked_put_usec"] > 0
    assert sink["Queue_len"] == 0  # drained at EOS


# ---------------------------------------------------------------------------
# the prep / commit latency hook: bound only where sampling is on
# ---------------------------------------------------------------------------
def test_dispatch_latency_hook_is_bound_only_under_sampling():
    # sampling off: no histogram, so the stage binds no per-batch hook
    # and a prep or commit pays no Python call for one
    st = StatsRecord("op", 0)
    assert st.hist_prep is None and st.hist_commit is None
    assert st.stage("prep")._note is None
    assert st.stage("commit")._note is None
    with st.stage("prep")(1):
        pass
    assert st.stage_count("prep") == 1
    assert st.dispatch_host_prep_total_us > 0.0
    # sampling on: each duration lands in the stage's own histogram,
    # beside the totals
    st2 = StatsRecord("op", 0, sample_every=1)
    prep, commit = st2.stage("prep"), st2.stage("commit")
    for b in (1, 2, 3):
        with prep(b):
            pass
    with commit(1):
        pass
    assert st2.hist_prep.count == 3 and st2.hist_commit.count == 1
    d = st2.to_dict()
    assert d["Latency_prep_samples"] == 3
    assert d["Latency_commit_samples"] == 1
    assert d["Dispatch_batches"] == 3
    assert d["Dispatch_host_prep_total_usec"] > 0.0
    assert d["Dispatch_commit_total_usec"] > 0.0


# ---------------------------------------------------------------------------
# MonitoringThread bounded reconnect
# ---------------------------------------------------------------------------
class _FakeGraph:
    name = "fake_graph"

    def to_dot(self):
        return "digraph g {}"

    def to_svg(self):
        return ""

    def get_stats(self):
        return {"PipeGraph_name": self.name, "Operators": [],
                "Dropped_tuples": 0, "Threads": 0, "Mode": "DEFAULT",
                "Time_policy": "INGRESS_TIME"}


def test_monitoring_thread_reconnects_to_late_dashboard():
    from windflow_tpu.monitoring.monitor import (MonitoringServer,
                                                 MonitoringThread)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    mt = MonitoringThread(_FakeGraph(), "127.0.0.1", port, period_sec=0.1)
    mt.start()
    time.sleep(0.8)  # at least one connect fails (dashboard absent)
    srv = MonitoringServer("127.0.0.1", port)
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if "fake_graph" in srv.snapshot()["reports"]:
                break
            time.sleep(0.05)
        snap = srv.snapshot()
        assert "fake_graph" in snap["reports"], \
            "dashboard started mid-run never received a report"
        assert "fake_graph" in snap["diagrams"]
        assert mt.connects >= 1
    finally:
        mt.stop()
        mt.join(timeout=3)
        srv.close()


# ---------------------------------------------------------------------------
# /metrics scrape smoke (scripts/check_metrics.py as a tier-1 test)
# ---------------------------------------------------------------------------
def test_check_metrics_smoke():
    script = os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "check_metrics.py")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    p = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert '"check_metrics": "OK"' in p.stdout


def test_prometheus_text_escaping_and_shape():
    """The renderer escapes hostile label values and emits parseable
    samples (the deeper validity checks live in check_metrics.py)."""
    import re

    from windflow_tpu.monitoring.monitor import prometheus_text

    hist = LatencyHistogram()
    for v in (10, 100, 1000):
        hist.record(v)
    snap = {"n_reports": 3, "reports": {
        'evil"graph\nname\\': {
            "Dropped_tuples": 2,
            "Operators": [{
                "name": 'op"1',
                "replicas": [{
                    "Replica_id": 0, "Inputs_received": 5,
                    "Outputs_sent": 4, "Queue_len": 1,
                    "Latency_e2e_hist": hist.to_sparse(),
                }],
            }],
        }}}
    text = prometheus_text(snap)
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        assert "\n" not in line
        assert re.match(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})?\s+\S+$', line), \
            line
    assert 'windflow_inputs_received_total' in text
    assert 'windflow_e2e_latency_usec_count' in text
    assert '\\"' in text  # quote escaped inside label values
    # histogram internal consistency: +Inf bucket equals count
    m = re.search(r'windflow_e2e_latency_usec_bucket\{.*le="\+Inf"\} (\d+)',
                  text)
    assert m and int(m.group(1)) == 3
