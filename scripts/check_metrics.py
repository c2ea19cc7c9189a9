#!/usr/bin/env python
"""Smoke check for the /metrics + /trace export plane.

Starts an in-process ``MonitoringServer`` (TCP collector + HTTP), runs a
tiny source -> map -> sink graph with tracing + latency sampling + the
flight recorder enabled, scrapes ``/metrics`` and ``/trace`` over real
HTTP, and asserts that

- ``/metrics`` returns 503 with a clear body BEFORE any graph report
  arrives (a scraper must see "not ready", not empty-but-200),
- the scrape parses as Prometheus text exposition format (every
  non-comment line is ``name{labels} value`` with a float value),
- the required metric families exist (throughput counters, queue
  gauges, service + end-to-end latency histograms, compile attribution,
  worker-crash counters),
- histogram families are internally consistent (cumulative buckets
  monotone, ``_count`` equals the ``+Inf`` bucket),
- ``GET /trace?ms=50`` returns a well-formed Chrome trace-event
  document (the flight-recorder capture window).

Exit code 0 on success. Wired into the tier-1 suite via
``tests/test_latency_tracing.py`` (not a separate CI job).
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the mesh leg needs the virtual multi-device platform; must land before
# anything initializes jax (no-op under pytest — conftest already did it)
from windflow_tpu.mesh import ensure_virtual_devices  # noqa: E402

ensure_virtual_devices()

REQUIRED_FAMILIES = (
    "windflow_inputs_received_total",
    "windflow_outputs_sent_total",
    "windflow_queue_occupancy",
    "windflow_queue_capacity",
    "windflow_queue_blocked_put_seconds_total",
    "windflow_service_latency_usec",
    "windflow_e2e_latency_usec",
    "windflow_reports_total",
    "windflow_compile_total",
    "windflow_compile_cache_hits_total",
    "windflow_compile_seconds_total",
    "windflow_worker_crashes_total",
    # elastic rescaling (the run performs one live rescale)
    "windflow_operator_parallelism",
    "windflow_rescale_total",
    "windflow_rescale_last_pause_seconds",
    "windflow_rescale_last_total_seconds",
    "windflow_checkpoints_completed_total",
    # exactly-once sink 2PC (the run's sink is transactional)
    "windflow_sink_txn_precommits_total",
    "windflow_sink_txn_commits_total",
    "windflow_sink_txn_aborts_total",
    "windflow_sink_txn_fenced_writes_total",
    # self-healing supervision (the run performs one live supervised
    # restart: an injected source crash the supervisor recovers from)
    "windflow_restart_total",
    "windflow_restart_last_seconds",
    # durable-recovery plane: fallback-ladder + device-loss signals
    # (0-valued on a clean run, but the families must export)
    "windflow_recovery_ladder_depth",
    "windflow_recovery_verify_failures_total",
    "windflow_recovery_degraded_devices",
    "windflow_ckpt_verify_failures_total",
    # incremental + async checkpointing (0-valued while WF_CKPT_DELTA /
    # WF_CKPT_ASYNC are off, but the families must export)
    "windflow_checkpoint_cut_pause_seconds",
    "windflow_checkpoint_delta_bytes_total",
    "windflow_checkpoint_async_uploads_total",
    "windflow_checkpoint_async_pending",
    # dead-letter / error-policy + Kafka retry accounting (per-replica
    # scalars: present with value 0 on every replica when unused)
    "windflow_dlq_records_total",
    "windflow_kafka_reconnects_total",
    # overload-protection plane (the run declares an SLO, so the
    # governor reports its state gauge even while idle; shed counters
    # are per-replica scalars, 0 when nothing sheds)
    "windflow_shed_records_total",
    "windflow_shed_bytes_total",
    "windflow_overload_state",
    "windflow_overload_escalations_total",
    "windflow_overload_slo_p99_seconds",
    # mesh execution plane (a second graph runs a mesh-sharded stateful
    # map over the virtual 8-device mesh; Mesh_* stats exist only on
    # mesh replicas, so these families prove the mesh plane exports)
    "windflow_mesh_devices",
    "windflow_mesh_steps_total",
    "windflow_mesh_shuffle_bytes_total",
    "windflow_mesh_step_seconds_total",
    "windflow_mesh_shard_occupancy",
    "windflow_mesh_shard_skew",
    # megabatch scan loop (per-replica scalars: present with value 0
    # when WF_MEGABATCH is off or the replica is not a fused chain)
    "windflow_megabatch_loops_total",
    "windflow_megabatch_batches_per_loop_avg",
    "windflow_megabatch_max",
    "windflow_programs_per_batch",
    # columnar ingest plane (a third graph runs a Columnar_Source so
    # the block counters carry real samples; row-only replicas export
    # them as 0)
    "windflow_ingest_blocks_total",
    "windflow_ingest_rows_per_block_avg",
    "windflow_ingest_block_ns_per_row",
    # tiered keyed state (a fourth graph runs a with_tiering stateful
    # map whose key set overflows the hot tier, so the Tier_* stats —
    # emitted only on tiered replicas — carry real samples)
    "windflow_tier_hot_keys",
    "windflow_tier_cold_keys",
    "windflow_tier_promotes_total",
    "windflow_tier_demotes_total",
    "windflow_tier_promote_seconds_total",
    "windflow_tier_miss_rate",
    # event-time health plane (a fifth graph runs an EVENT_TIME keyed
    # window over a 5%-late stream into a deliberately slow sink, so the
    # watermark gauges, late counters, the lateness histogram AND the
    # pipeline doctor all carry real samples)
    "windflow_watermark_timestamp_usec",
    "windflow_watermark_advances_total",
    "windflow_watermark_lag_seconds",
    "windflow_watermark_event_lag_seconds",
    "windflow_watermark_idle",
    "windflow_watermark_stalls_total",
    "windflow_late_records_total",
    "windflow_late_dropped_total",
    "windflow_late_admitted_total",
    "windflow_lateness_usec",
    "windflow_doctor_healthy",
    "windflow_doctor_findings",
)

# verdict vocabulary shared with monitoring/doctor.py (schema check of
# the /doctor smoke below)
_DOCTOR_VERDICTS = frozenset((
    "ingest-bound", "compute-bound", "dispatch-bound", "backpressured-by",
    "event-time-stalled", "overloaded", "interpreter-bound"))

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})?\s+'
    r'[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN)$')


def validate_exposition(text: str) -> list:
    """Format errors in a /metrics payload (empty list = valid)."""
    errors = []
    for ln, line in enumerate(text.splitlines(), 1):
        if not line or line.startswith("#"):
            continue
        if not _SAMPLE_RE.match(line):
            errors.append(f"line {ln}: not a valid sample: {line!r}")
    return errors


def check_histogram_consistency(text: str, family: str) -> list:
    """Monotone cumulative buckets; _count == +Inf bucket, per series."""
    errors = []
    series = {}
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        m = re.match(rf'^{family}_(bucket|count|sum)\{{([^}}]*)\}}\s+(\S+)$',
                     line)
        if not m:
            continue
        kind, labels, value = m.groups()
        key = re.sub(r',?le="[^"]*"', "", labels)
        series.setdefault(key, {"buckets": [], "count": None})
        if kind == "bucket":
            le = re.search(r'le="([^"]*)"', labels).group(1)
            series[key]["buckets"].append(
                (float("inf") if le == "+Inf" else float(le), float(value)))
        elif kind == "count":
            series[key]["count"] = float(value)
    for key, s in series.items():
        buckets = sorted(s["buckets"])
        cums = [c for _, c in buckets]
        if cums != sorted(cums):
            errors.append(f"{family}{{{key}}}: non-monotone buckets {cums}")
        if buckets and s["count"] is not None \
                and buckets[-1][0] == float("inf") \
                and buckets[-1][1] != s["count"]:
            errors.append(f"{family}{{{key}}}: +Inf bucket "
                          f"{buckets[-1][1]} != count {s['count']}")
    return errors


_TRACE_PHASES = frozenset("BEXiIMCbnesStfPOND(){}Rcav,")


def validate_chrome_trace(doc) -> list:
    """Schema errors in a Chrome trace-event document (empty = valid):
    object form with a ``traceEvents`` list whose entries carry a string
    ``name``, a known one-char ``ph``, integer ``pid``/``tid``, and —
    for complete (``X``) spans — non-negative numeric ``ts``/``dur``."""
    errors = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"]
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        if not isinstance(ev.get("name"), str):
            errors.append(f"event {i}: name missing/not a string")
        ph = ev.get("ph")
        if not (isinstance(ph, str) and len(ph) == 1
                and ph in _TRACE_PHASES):
            errors.append(f"event {i}: bad phase {ph!r}")
        for k in ("pid", "tid"):
            if not isinstance(ev.get(k), int):
                errors.append(f"event {i}: {k} missing/not an int")
        if ph == "X":
            for k in ("ts", "dur"):
                v = ev.get(k)
                if not isinstance(v, (int, float)) or v < 0:
                    errors.append(f"event {i}: {k}={v!r} (want >= 0)")
    return errors


def run_mesh_graph():
    """A second tiny graph exercising the mesh execution plane: source
    -> mesh-sharded stateful Map (virtual 8-device mesh) -> sink, so
    the ``windflow_mesh_*`` families have real samples. Reports to the
    same monitoring server via the env already set by the caller."""
    import numpy as np

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Map_TPU_Builder

    def src(shipper):
        for i in range(2_000):
            shipper.push({"k": i % 7, "v": float(i + 1)})

    seen = [0]
    g = PipeGraph("check_metrics_mesh", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    op = (Map_TPU_Builder(
            lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                             st + row["v"]))
          .with_state(np.float32(0)).with_key_by("k")
          .with_mesh(key_capacity=7).with_name("mscan").build())
    g.add_source(Source_Builder(src).with_name("msrc")
                 .with_output_batch_size(64).build()) \
        .add(op) \
        .add_sink(Sink_Builder(
            lambda t: seen.__setitem__(0, seen[0] + 1) if t else None)
            .with_name("mout").build())
    g.run()
    assert seen[0] == 2_000, f"mesh sink saw {seen[0]} tuples"


def run_columnar_graph():
    """A third tiny graph over the columnar ingest plane: block source
    -> device map -> sink, so the ``windflow_ingest_*`` families carry
    non-zero samples (row-only replicas export them as 0)."""
    import numpy as np

    from windflow_tpu import (ArrayBlockSource, Columnar_Source_Builder,
                              ExecutionMode, PipeGraph, Sink_Builder,
                              TimePolicy)
    from windflow_tpu.tpu import Map_TPU_Builder

    n = 4_000
    blocks = ArrayBlockSource({"v": np.arange(n, dtype=np.int64)},
                              block_size=512)
    seen = [0]
    g = PipeGraph("check_metrics_columnar", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.add_source(Columnar_Source_Builder(blocks).with_name("csrc")
                 .with_output_batch_size(256).build()) \
        .add(Map_TPU_Builder(lambda f: {"v": f["v"] * 2})
             .with_name("cmap").build()) \
        .add_sink(Sink_Builder(
            lambda t: seen.__setitem__(0, seen[0] + 1) if t else None)
            .with_name("cout").build())
    g.run()
    assert seen[0] == n, f"columnar sink saw {seen[0]} tuples"
    src_reps = [o for o in g.get_stats()["Operators"]
                if o["name"] == "csrc"][0]["replicas"]
    assert sum(r["Ingest_blocks"] for r in src_reps) > 0, \
        "columnar source reported no ingest blocks"


def run_tiered_graph():
    """A fourth tiny graph exercising the tiered keyed-state store: a
    stateful map whose distinct key set (20) overflows the hot tier
    (8), so promotes/demotes fire and the ``windflow_tier_*`` families
    carry real samples."""
    import numpy as np

    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Map_TPU_Builder

    def src(shipper):
        for i in range(2_000):
            shipper.push({"k": i % 20, "v": float(i + 1)})

    seen = [0]
    g = PipeGraph("check_metrics_tiered", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    op = (Map_TPU_Builder(
            lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                             st + row["v"]))
          .with_state(np.float32(0)).with_key_by("k")
          .with_tiering(policy="lru", hot_capacity=8)
          .with_name("tscan").build())
    # batch size 8: each batch's distinct-key working set fits the hot
    # tier while the 20-key stream forces steady promote/demote churn
    g.add_source(Source_Builder(src).with_name("tsrc")
                 .with_output_batch_size(8).build()) \
        .add(op) \
        .add_sink(Sink_Builder(
            lambda t: seen.__setitem__(0, seen[0] + 1) if t else None)
            .with_name("tout").build())
    g.run()
    assert seen[0] == 2_000, f"tiered sink saw {seen[0]} tuples"
    reps = [o for o in g.get_stats()["Operators"]
            if o["name"] == "tscan"][0]["replicas"]
    assert sum(r.get("Tier_promotes", 0) for r in reps) > 0, \
        "tiered map reported no promotes"


def run_event_time_graph(host: str, http_port: int) -> list:
    """The event-time health leg: an EVENT_TIME source whose stream is
    5% late (50 ms behind a watermark with zero allowed lateness) feeds
    a keyed time window into a DELIBERATELY SLOW sink. While it runs,
    poll ``GET /doctor`` and schema-check the diagnosis: the doctor must
    emit at least one finding with a verdict from the shared vocabulary
    (the slow sink is the planted bottleneck). Returns problem strings
    (empty = OK); also leaves Late_* / Watermark_* / lateness-histogram
    samples behind for the family checks."""
    import threading
    import time as _time

    from windflow_tpu import (ExecutionMode, Keyed_Windows_Builder,
                              PipeGraph, Sink_Builder, Source_Builder,
                              TimePolicy)

    lateness_us = 50_000

    def src(shipper):
        ts = 0
        for i in range(40_000):
            ts += 25  # synthetic event clock: 1 s of event time total
            late = (i % 20) == 7  # deterministic 5% late share
            shipper.push_with_timestamp(
                {"k": i % 8, "v": i}, ts - lateness_us if late else ts)
            if (i % 100) == 99:
                shipper.set_next_watermark(ts)

    fired = [0]

    def slow_sink(res):
        if res is not None:
            fired[0] += 1
            _time.sleep(0.004)  # the planted bottleneck

    g = PipeGraph("check_metrics_event_time", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    g.add_source(Source_Builder(src).with_name("esrc").build()) \
        .add(Keyed_Windows_Builder(lambda ws: len(list(ws)))
             .with_key_by(lambda t: t["k"])
             .with_tb_windows(2_000, 2_000)  # 500 fires over the stream
             .with_name("ewin").build()) \
        .add_sink(Sink_Builder(slow_sink).with_name("eout").build())
    problems = []
    g.start()
    # the server diagnoses each 1 Hz report; poll /doctor until this
    # graph's diagnosis lands (two reports give the first tick delta)
    diag = None
    deadline = _time.monotonic() + 20
    while _time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://{host}:{http_port}/doctor", timeout=5) as r:
                doc = json.load(r)
            diag = doc.get("check_metrics_event_time")
            if diag and diag.get("findings"):
                break
        except urllib.error.HTTPError as e:
            if e.code != 503:  # 503 = no tick delta yet; keep polling
                raise
        _time.sleep(0.25)
    g.wait_end()
    if not isinstance(diag, dict):
        return ["/doctor never produced a diagnosis for the slow-sink "
                "graph"]
    for k in ("healthy", "findings", "summary", "dt_sec", "bottleneck"):
        if k not in diag:
            problems.append(f"/doctor diagnosis missing key {k!r}")
    finds = diag.get("findings") or []
    if not finds:
        problems.append("/doctor found nothing on a graph with a "
                        "deliberately slow sink")
    for f in finds:
        if f.get("verdict") not in _DOCTOR_VERDICTS:
            problems.append(f"/doctor verdict {f.get('verdict')!r} not "
                            f"in the shared vocabulary")
        if not f.get("operator") or "evidence" not in f:
            problems.append(f"/doctor finding missing operator/evidence: "
                            f"{f}")
    # the planted bottleneck is the sink: the top finding must name it
    # (either directly or as the backpressured-by target)
    top = diag.get("bottleneck") or {}
    if finds and top.get("operator") != "eout" \
            and top.get("by") != "eout":
        problems.append(f"/doctor blamed {top.get('operator')!r}, not "
                        f"the slow sink: {diag.get('summary')}")
    # late accounting: the 5%-late stream must be visible in the stats
    ewin = [o for o in g.get_stats()["Operators"]
            if o["name"] == "ewin"][0]["replicas"]
    if sum(r.get("Late_records", 0) for r in ewin) == 0:
        problems.append("event-time leg recorded no late tuples")
    return problems


def run_graph_and_scrape():
    """Run the tiny graph against a fresh server; return (metrics text,
    /trace document, pre-run /metrics status code)."""
    from windflow_tpu import (ExecutionMode, Map_Builder, PipeGraph,
                              Sink_Builder, Source_Builder, TimePolicy)
    from windflow_tpu.monitoring.monitor import MonitoringServer

    server = MonitoringServer()
    http_port = server.serve_http(0)
    os.environ["WF_TRACING_ENABLED"] = "1"
    os.environ["WF_DASHBOARD_MACHINE"] = server.host
    os.environ["WF_DASHBOARD_PORT"] = str(server.port)
    os.environ["WF_LATENCY_SAMPLE"] = "1"
    os.environ.setdefault("WF_LOG_DIR", tempfile.mkdtemp(prefix="wf_log_"))
    try:
        # no graph has reported yet: a scrape must say "not ready"
        # loudly, not hand Prometheus an empty-but-200 exposition
        try:
            with urllib.request.urlopen(
                    f"http://{server.host}:{http_port}/metrics",
                    timeout=10) as r:
                pre_status = r.status
        except urllib.error.HTTPError as e:
            pre_status = e.code
        import threading
        import time as _time

        gate = threading.Event()
        pos = [0]
        crashed = [False]

        def src(shipper):
            while pos[0] < 20_000:
                if pos[0] == 10_000:
                    gate.wait(20)
                if pos[0] == 15_000 and not crashed[0]:
                    # the supervised-restart leg: the supervisor must
                    # recover this in-process (windflow_restart_*)
                    crashed[0] = True
                    raise RuntimeError("injected crash for check_metrics")
                shipper.push({"v": pos[0]})
                pos[0] += 1
                if pos[0] == 12_000:
                    # post-rescale checkpoint: the supervised restore
                    # must target the CURRENT (rescaled) topology
                    shipper.request_checkpoint()

        src.snapshot_position = lambda: pos[0]
        src.restore = lambda p: pos.__setitem__(0, p)

        seen = [0]
        g = PipeGraph("check_metrics", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)
        g.with_flight_recorder()  # /trace must have rings to capture
        # one live rescale mid-run so the windflow_rescale_* and
        # operator-parallelism families have real samples to validate
        g.with_checkpointing(
            store_dir=tempfile.mkdtemp(prefix="wf_ckpt_"))
        from windflow_tpu import RestartPolicy
        g.with_supervision(RestartPolicy(max_restarts=3, backoff_s=0.05,
                                         backoff_max_s=0.2))
        # overload governor attached but IDLE (a 60 s budget never
        # breaches): the windflow_overload_* families must export even
        # when the ladder never engages
        g.with_slo(60_000.0)
        g.add_source(Source_Builder(src).with_name("src").build()) \
         .add(Map_Builder(lambda t: {"v": t["v"] * 2})
              .with_name("dbl").build()) \
         .add_sink(Sink_Builder(
             lambda t: seen.__setitem__(0, seen[0] + 1) if t else None)
             .with_name("out")
             .with_exactly_once(
                 staging_dir=tempfile.mkdtemp(prefix="wf_txn_"))
             .build())
        g.start()
        deadline = _time.monotonic() + 15
        while pos[0] < 10_000 and _time.monotonic() < deadline:
            _time.sleep(0.01)
        threading.Timer(0.2, gate.set).start()
        rep = g.rescale("dbl", 2, timeout_s=20)
        assert rep.changed and rep["pause_s"] > 0, rep
        g.wait_end()
        assert seen[0] == 20_000, f"sink saw {seen[0]} tuples"
        sup = g.get_stats().get("Supervision", {})
        assert sup.get("Supervision_restarts") == 1, \
            f"expected 1 supervised restart, saw {sup}"
        # the mesh-plane leg: a second graph over the virtual mesh so the
        # windflow_mesh_* families carry real samples
        run_mesh_graph()
        # the columnar-ingest leg: a block source feeds the device map
        # so the windflow_ingest_* families carry non-zero samples
        run_columnar_graph()
        # the tiered-state leg: the key set overflows the hot tier so
        # the windflow_tier_* families carry non-zero samples
        run_tiered_graph()
        # the event-time health leg: 5%-late stream + slow sink; polls
        # /doctor live and leaves Late_*/Watermark_* samples behind
        doctor_problems = run_event_time_graph(server.host, http_port)
        # the final report is flushed by the monitor thread at stop but
        # consumed by the server's reader thread: wait for it to land
        import time
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            reports = server.snapshot()["reports"]
            if "check_metrics" in reports \
                    and "check_metrics_mesh" in reports \
                    and "check_metrics_columnar" in reports \
                    and "check_metrics_tiered" in reports \
                    and "check_metrics_event_time" in reports:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("monitoring report never reached the "
                                 "server (reconnect/report plane broken)")
        with urllib.request.urlopen(
                f"http://{server.host}:{http_port}/metrics",
                timeout=10) as r:
            ctype = r.headers.get("Content-Type", "")
            text = r.read().decode()
        assert ctype.startswith("text/plain"), f"bad content type {ctype!r}"
        assert "version=0.0.4" in ctype, \
            f"missing exposition version in content type {ctype!r}"
        # the flight-recorder capture window (graph finished: the doc is
        # metadata-only but must still be schema-valid JSON)
        with urllib.request.urlopen(
                f"http://{server.host}:{http_port}/trace?ms=50",
                timeout=10) as r:
            trace_doc = json.load(r)
        return text, trace_doc, pre_status, doctor_problems
    finally:
        server.close()


def main() -> int:
    text, trace_doc, pre_status, doctor_problems = run_graph_and_scrape()
    problems = list(doctor_problems)
    if pre_status != 503:
        problems.append(f"pre-run /metrics returned {pre_status}, want 503")
    problems.extend(f"/trace: {e}"
                    for e in validate_chrome_trace(trace_doc))
    for fam in REQUIRED_FAMILIES:
        if f"\n# TYPE {fam} " not in "\n" + text:
            problems.append(f"missing required family: {fam}")
    problems.extend(validate_exposition(text))
    for fam in ("windflow_service_latency_usec", "windflow_e2e_latency_usec",
                "windflow_lateness_usec"):
        problems.extend(check_histogram_consistency(text, fam))
    # the sampled run must produce non-zero end-to-end latency evidence
    m = re.search(r'windflow_e2e_latency_usec_count\{[^}]*operator="out'
                  r'"[^}]*\}\s+(\d+)', text) or \
        re.search(r'windflow_e2e_latency_usec_count\{[^}]*\}\s+(\d+)', text)
    if not m or int(m.group(1)) <= 0:
        problems.append("no end-to-end latency samples at the sink")
    if problems:
        print(json.dumps({"check_metrics": "FAIL", "problems": problems}))
        return 1
    print(json.dumps({"check_metrics": "OK",
                      "families": len(REQUIRED_FAMILIES),
                      "lines": len(text.splitlines())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
