#!/usr/bin/env python
"""Benchmark: FFAT sliding-window aggregation throughput on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tuples/sec", "platform": ...,
   "device_kind": ..., "n_devices": N, ...}

It runs on what ``jax.devices()`` gives and exits non-zero when that is
not a TPU. The one exception is a caller that set ``JAX_PLATFORMS=cpu``
explicitly: the run then proceeds on the CPU backend and the metric name
ends in `` (cpu)`` — a CPU-backend number never carries a device
metric's bare name. Errors propagate: a measurement that raises ends the
run with a traceback and no JSON line. The process starts no child; the
mesh plane has its own command (``scripts/bench_mesh.py``, one process
owning all chips).

Extra fields report the high-cardinality configuration (10k keys),
fired-window rates (windows/sec scales with key count under TB sliding
windows, so tuples/sec alone under-describes that regime), fire latency
and the secondary device operators.

``WF_BENCH_REPEATS`` (default 5) sets the timed chunks per
configuration; mean, min and best are all reported.

``python bench.py --surge`` and ``--replay`` are CPU-plane scenario
runs (autoscaler/governor under a traffic step; realistic replay with
exactly-once, tiering and delta checkpoints). They write
results/surge.json and results/replay.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

N_KEYS = 64
BATCH = 65536  # v5e batch size chosen in the round-2 chip session (host
               # control plane amortizes per batch); not re-swept since
N_BATCHES = 24
WIN_PER_BATCH = 128
WARMUP = 4
WIN_US = 100_000
SLIDE_US = 25_000
# Event time advances TS_STEP/AGG_RATE_KEYS µs per tuple in EVERY config:
# the aggregate stream-time rate is held constant across key counts, so
# the high-cardinality config measures "same stream, more keys" (per-key
# density thins out; fired windows/sec scales with cardinality). At the
# base config this is TS_STEP µs between consecutive tuples of one key.
TS_STEP = 50
AGG_RATE_KEYS = N_KEYS

HC_KEYS = 10_240  # high-cardinality configuration
HC_WIN_PER_BATCH = None  # auto-sized from key capacity
HC_BATCHES = 8

# The throughput pass is repeated over one continuous stream; mean, min
# and best across chunks are all reported (the headline value is the
# MEAN); the latency pass is not repeated.
REPEATS = int(os.environ.get("WF_BENCH_REPEATS", "5"))


def _make_replica(n_keys: int, win_per_batch: int):
    from windflow_tpu.basic import WinType
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU

    op = Ffat_Windows_TPU(
        lift=lambda f: {"value": f["value"]},
        combine=lambda a, b: {"value": a["value"] + b["value"]},
        key_extractor="key",
        win_len=WIN_US, slide_len=SLIDE_US, win_type=WinType.TB,
        num_win_per_batch=win_per_batch, key_capacity=n_keys,
        name="bench_ffat")
    op.build_replicas()
    return op.replicas[0]


class _CountingEmitter:
    def __init__(self):
        self.windows = 0
        self.last_batch = None  # device-sync anchor (block on its fields)

    def emit_device_batch(self, b):
        self.windows += b.size
        self.last_batch = b

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass

    def flush(self):
        pass


def _stage_batches(n_keys: int, n_batches: int, seed: int,
                   with_ts: bool, batch_size: int = 0,
                   wm_every: int = 1):
    """Pre-staged synthetic keyed batches (staging excluded from timing:
    the metric is the device-operator path, matching the reference's
    per-operator counters). with_ts drives event-time/watermarks for the
    window benchmark; plain arange timestamps otherwise. ``wm_every=N``
    releases the watermark only on every Nth batch (parked in between —
    the production periodic-watermark shape; N=1 is the r1-r3
    per-batch-watermark protocol)."""
    B = batch_size or BATCH
    import jax
    import numpy as np

    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.schema import TupleSchema

    schema = TupleSchema({"key": np.int32, "value": np.int32})
    rng = np.random.default_rng(seed)
    batches = []
    ts0 = 0
    wm_hold = 0
    for i in range(n_batches):
        keys = rng.integers(0, n_keys, B).astype(np.int64)
        cols = {
            "key": jax.device_put(keys.astype(np.int32)),
            "value": jax.device_put(
                rng.integers(0, 100, B).astype(np.int32)),
        }
        if with_ts:
            ts = ts0 + np.arange(B, dtype=np.int64) * TS_STEP // AGG_RATE_KEYS
            ts0 = int(ts[-1]) + TS_STEP
            b = BatchTPU(cols, ts, B, schema,
                         wm=max(0, int(ts[0]) - 1000),
                         host_keys=keys)  # numpy key metadata: no boxing
            if (i + 1) % wm_every == 0:
                wm_hold = int(ts[-1])
            b.wm = wm_hold if wm_every > 1 else int(ts[-1])
        else:
            b = BatchTPU(cols, np.arange(B, dtype=np.int64), B,
                         schema, host_keys=keys)
        batches.append(b)
    return batches


def _run_config(n_keys: int, win_per_batch: int, n_batches: int,
                lat_batches: int = 0, repeats: int = 1,
                batch_size: int = 0, wm_every: int = 1):
    """Returns (chunks, p50 fire latency µs, p99 fire latency µs,
    programs), where ``chunks``
    is a list of per-chunk (tuples/s, windows/s) pairs — aggregation
    (mean/min/best) is the caller's job (_chunk_stats).

    Throughput and latency are measured in SEPARATE passes over one
    continuous stream: the throughput pass lets dispatch pipeline freely
    (syncing once at the end), the latency pass blocks on the emitted
    window batch per step — on an async backend a per-batch timer without
    the block would measure dispatch, not window delivery. With
    ``repeats`` > 1 the throughput pass times ``repeats`` contiguous
    chunks of the stream (see REPEATS above)."""
    import jax

    rep = _make_replica(n_keys, win_per_batch)
    sink = _CountingEmitter()
    rep.emitter = sink
    B = batch_size or BATCH
    batches = _stage_batches(
        n_keys, repeats * n_batches + lat_batches + WARMUP, 0, with_ts=True,
        batch_size=B, wm_every=wm_every)

    for b in batches[:WARMUP]:
        rep.handle_msg(0, b)
    rep.dispatch.drain()  # commit deferred warmup batches (WF_DISPATCH_DEPTH)
    jax.block_until_ready(rep.trees)

    chunks = []  # per-chunk (tuples/s, windows/s)
    for r in range(repeats):
        lo = WARMUP + r * n_batches
        w0 = sink.windows
        t0 = time.perf_counter()
        for b in batches[lo:lo + n_batches]:
            rep.handle_msg(0, b)
        rep.dispatch.drain()  # the chunk's windows must be EMITTED
        jax.block_until_ready(rep.trees)
        elapsed = time.perf_counter() - t0
        chunks.append((n_batches * B / elapsed,
                       (sink.windows - w0) / elapsed))

    fire_lat = []
    for b in batches[WARMUP + repeats * n_batches:]:
        # drain the dispatch queue first so a firing batch's timing does
        # not absorb async backlog from preceding non-firing batches
        rep.dispatch.drain()
        jax.block_until_ready(rep.trees)
        before = sink.windows
        tb = time.perf_counter()
        rep.handle_msg(0, b)
        rep.dispatch.drain()  # latency = fire-to-DELIVERY, so the
        # deferred commit (and its emit) belongs inside the timed region
        if sink.windows > before:  # this batch fired windows
            _sync(sink)  # windows DELIVERED, not merely dispatched
            fire_lat.append(time.perf_counter() - tb)

    import math

    def _pct(q: float) -> float:  # nearest-rank percentile, µs
        if not fire_lat:
            return 0.0
        ordered = sorted(fire_lat)
        return ordered[min(len(ordered) - 1,
                           max(0, math.ceil(len(ordered) * q) - 1))] * 1e6

    return (chunks, _pct(0.50), _pct(0.99), rep.stats.device_programs_run)


def _sync(sink: "_CountingEmitter") -> None:
    """Wait for the device to drain: block on the LAST emitted batch's
    columns (works for every op type; completion of the last program
    implies all earlier ones on the single dispatch queue)."""
    import jax

    if sink.last_batch is not None:
        jax.block_until_ready(list(sink.last_batch.fields.values()))


def _run_op_config(make_op, n_keys: int, n_batches: int,
                   repeats: int = 1, batch_size: int = 0):
    """Generic device-op throughput: pre-staged keyed batches -> op.
    Best contiguous chunk of ``repeats`` (same protocol as _run_config)."""
    B = batch_size or BATCH
    op = make_op()
    op.build_replicas()
    rep = op.replicas[0]
    sink = _CountingEmitter()
    rep.emitter = sink
    bs = _stage_batches(n_keys, repeats * n_batches + WARMUP, 1,
                        with_ts=False, batch_size=B)
    for b in bs[:WARMUP]:
        rep.handle_msg(0, b)
    rep.dispatch.drain()
    _sync(sink)  # warmup compute must not bleed into the timed region
    best = 0.0
    for r in range(repeats):
        lo = WARMUP + r * n_batches
        t0 = time.perf_counter()
        for b in bs[lo:lo + n_batches]:
            rep.handle_msg(0, b)
        rep.dispatch.drain()  # deferred commits must emit to count
        _sync(sink)
        best = max(best, n_batches * B / (time.perf_counter() - t0))
    return best


def _surge_mode() -> None:
    """Traffic-spike scenario (``bench.py --surge``): a Zipf-keyed
    stream steps from a base rate to 2x mid-run, twice — once with the
    autoscaler on and once with the topology static. Reports sink-side
    p99 latency before / during (early surge) / after (late surge, when
    the autoscaler has reacted) for both runs, plus the measured rescale
    pause. CPU-plane by construction (the elastic plane is host-side
    routing). A second pair of runs steps to 4x —
    PAST the autoscaler's MAX_PAR — with the overload governor off
    (pegged p99: scale-out exhausted) and on (admission control holds
    p99 inside WF_SURGE_SLO_MS, every shed accounted). Writes
    results/surge.json and prints one JSON line."""
    import threading

    import numpy as np

    from windflow_tpu import (ExecutionMode, PipeGraph, Reduce,
                              Sink_Builder, Source_Builder, TimePolicy)
    from windflow_tpu.scaling import AutoscalePolicy

    n_keys = int(os.environ.get("WF_SURGE_KEYS", "64"))
    base_rate = float(os.environ.get("WF_SURGE_RATE", "1500"))
    phase_s = float(os.environ.get("WF_SURGE_PHASE_SEC", "6"))
    work_s = float(os.environ.get("WF_SURGE_WORK_USEC", "500")) / 1e6
    rng = np.random.default_rng(7)
    # Zipf-skewed key table (rank-weighted, capped to n_keys)
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    probs = (1.0 / ranks ** 1.2)
    probs /= probs.sum()
    key_table = rng.choice(n_keys, size=1 << 16, p=probs)

    def run(autoscale: bool) -> dict:
        samples = []  # (t_rel, latency_us) at the sink
        lock = threading.Lock()
        t_start = [0.0]

        class SurgeSource:
            """Rate-paced pusher: base rate for one phase, 2x for two
            phases (the step), stamped with wall-clock push time."""

            def __init__(self):
                self.pos = 0

            def __call__(self, shipper):
                t_start[0] = time.monotonic()
                i = 0
                while True:
                    t_rel = time.monotonic() - t_start[0]
                    if t_rel >= 3 * phase_s:
                        return
                    rate = base_rate if t_rel < phase_s else 2 * base_rate
                    # push a 10-tuple burst, then pace to the target rate
                    for _ in range(10):
                        k = int(key_table[i & 0xFFFF])
                        shipper.push({"key": k, "v": i,
                                      "t0": time.perf_counter()})
                        i += 1
                    self.pos = i
                    time.sleep(max(0.0, 10 / rate
                                   - (time.monotonic() - t_start[0]
                                      - t_rel)))

            def snapshot_position(self):
                return self.pos

            def restore(self, pos):
                self.pos = pos

        def hot_step(t, s):
            # fixed per-tuple service time, sized so parallelism 1
            # saturates between base and 2x rate — the surge NEEDS the
            # scale-up. sleep (not a busy-wait): it releases the GIL
            # like real native/device work would, so replicas overlap
            # and the starved producer actually builds a queue. The
            # state is the latest tuple, so the sink (which receives
            # the emitted state) times the tuple's whole path via t0
            time.sleep(work_s)
            return t

        def sink(t):
            if t is None:
                return
            lat = (time.perf_counter() - t["t0"]) * 1e6
            with lock:
                samples.append((time.monotonic() - t_start[0], lat))

        import shutil
        store = os.path.join("results", f"surge_ckpt_{autoscale}")
        shutil.rmtree(store, ignore_errors=True)
        g = PipeGraph(f"surge_{'auto' if autoscale else 'static'}",
                      ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME,
                      channel_capacity=128)
        g.with_checkpointing(store_dir=store)
        if autoscale:
            g.with_autoscaler(AutoscalePolicy(
                interval_s=0.25, cooldown_s=3.0, max_parallelism=4,
                up_blocked_put_ms=20, hysteresis=2, factor=2.0))
        # Reduce re-emits its state per tuple, so sink latency covers
        # the whole queue + service path of the bottleneck
        red = Reduce(hot_step, key_extractor=lambda t: t["key"],
                     name="hot", parallelism=1)
        g.add_source(Source_Builder(SurgeSource()).with_name("src")
                     .build()) \
            .add(red) \
            .add_sink(Sink_Builder(sink).with_name("snk").build())
        g.run()
        st = g.get_stats()
        shutil.rmtree(store, ignore_errors=True)  # scratch, not artifact

        def p99(lo, hi):
            window = sorted(v for t, v in samples if lo <= t < hi)
            if not window:
                return 0.0
            return window[min(len(window) - 1,
                              int(0.99 * (len(window) - 1)))]

        rs = st.get("Rescales", {})
        return {
            "tuples": len(samples),
            "p99_before_us": round(p99(phase_s * 0.3, phase_s), 1),
            "p99_surge_early_us": round(p99(phase_s, 1.5 * phase_s), 1),
            "p99_surge_late_us": round(p99(2 * phase_s, 3 * phase_s), 1),
            "rescale_events": rs.get("Rescale_events", 0),
            "rescale_pause_s": rs.get("Rescale_last_pause_s", 0.0),
            "final_parallelism": [o["parallelism"]
                                  for o in st["Operators"]
                                  if o["name"] == "hot"][0],
        }

    # ---- 4x surge PAST MAX_PAR: the overload-governor leg -------------
    # The 2x surge above is absorbable by scale-out; this one is NOT
    # (offered 4x base vs MAX_PAR=2 replicas of a ~1x-rate operator).
    # governor=False shows the failure mode the static/autoscaled runs
    # cannot escape — pegged p99 bounded only by channel capacity;
    # governor=True must hold p99 inside the SLO by admission control,
    # with every shed record accounted (offered == admitted + shed).
    slo_ms = float(os.environ.get("WF_SURGE_SLO_MS", "50"))
    max_par = int(os.environ.get("WF_SURGE_MAX_PAR", "2"))

    def run_4x(governed: bool) -> dict:
        from windflow_tpu import GovernorPolicy
        samples = []
        lock = threading.Lock()
        t_start = [0.0]
        pushed = [0]

        class Surge4xSource:
            """Replayable across the mid-surge rescale: the cursor AND
            the elapsed phase clock ride the snapshot, so a restart
            resumes the rate schedule instead of replaying the ramp."""

            def __init__(self):
                self.pos = 0
                self.t_off = 0.0

            def __call__(self, shipper):
                t0 = time.monotonic() - self.t_off
                if not t_start[0]:
                    t_start[0] = t0
                i = self.pos
                while True:
                    t_rel = time.monotonic() - t0
                    self.t_off = t_rel
                    if t_rel >= 3 * phase_s:
                        pushed[0] = i
                        return
                    rate = base_rate if t_rel < phase_s else 4 * base_rate
                    for _ in range(10):
                        k = int(key_table[i & 0xFFFF])
                        # cursor BEFORE the push (barriers inject at push
                        # boundaries): offered == admitted + shed exactly,
                        # even across the mid-surge rescale
                        self.pos = i
                        shipper.push({"key": k, "v": i,
                                      "t0": time.perf_counter()})
                        i += 1
                    self.pos = i
                    time.sleep(max(0.0, 10 / rate
                                   - (time.monotonic() - t0 - t_rel)))

            def snapshot_position(self):
                return (self.pos, self.t_off)

            def restore(self, state):
                self.pos, self.t_off = state

        def hot_step(t, s):
            time.sleep(work_s)
            return t

        def sink(t):
            if t is None:
                return
            lat = (time.perf_counter() - t["t0"]) * 1e6
            with lock:
                samples.append((time.monotonic() - t_start[0], lat))

        import shutil
        store = os.path.join("results", f"surge4x_ckpt_{governed}")
        shutil.rmtree(store, ignore_errors=True)
        g = PipeGraph(f"surge4x_{'gov' if governed else 'nogov'}",
                      ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME,
                      channel_capacity=128)
        g.with_checkpointing(store_dir=store)
        g.with_autoscaler(AutoscalePolicy(
            interval_s=0.25, cooldown_s=3.0, max_parallelism=max_par,
            up_blocked_put_ms=20, hysteresis=2, factor=2.0))
        if governed:
            g.with_slo(slo_ms, GovernorPolicy(
                slo_p99_ms=slo_ms, interval_s=0.25, cooldown_s=0.75,
                breach_hysteresis=2, max_parallelism=max_par))
        red = Reduce(hot_step, key_extractor=lambda t: t["key"],
                     name="hot", parallelism=1)
        g.add_source(Source_Builder(Surge4xSource()).with_name("src")
                     .build()) \
            .add(red) \
            .add_sink(Sink_Builder(sink).with_name("snk").build())
        g.run()
        st = g.get_stats()
        shutil.rmtree(store, ignore_errors=True)

        def p99(lo, hi):
            window = sorted(v for t, v in samples if lo <= t < hi)
            if not window:
                return 0.0
            return window[min(len(window) - 1,
                              int(0.99 * (len(window) - 1)))]

        src_reps = [r for o in st["Operators"] if o["name"] == "src"
                    for r in o["replicas"]]
        admitted = sum(r["Inputs_received"] for r in src_reps)
        shed = sum(r["Shed_records"] for r in src_reps)
        offered = admitted + shed
        ov = st.get("Overload", {})
        out = {
            "delivered": len(samples),
            "offered": offered, "admitted": admitted, "shed": shed,
            "shed_fraction": round(shed / offered, 4) if offered else 0.0,
            "offered_matches_push_count": offered == pushed[0],
            "p99_before_us": round(p99(phase_s * 0.3, phase_s), 1),
            "p99_surge_late_us": round(p99(2 * phase_s, 3 * phase_s), 1),
            "final_parallelism": [o["parallelism"]
                                  for o in st["Operators"]
                                  if o["name"] == "hot"][0],
        }
        if governed:
            out["governor"] = {
                "state": ov.get("Overload_state_name"),
                "escalations": ov.get("Overload_escalations"),
                "admit_rate_tps": ov.get("Overload_admit_rate_tps"),
                "offered_tps": ov.get("Overload_offered_tps"),
                "admitted_tps": ov.get("Overload_admitted_tps"),
            }
        return out

    print("surge: static topology run", file=sys.stderr)
    static = run(False)
    print("surge: autoscaled run", file=sys.stderr)
    auto = run(True)
    print("surge: 4x past MAX_PAR, governor off", file=sys.stderr)
    gov_off = run_4x(False)
    print("surge: 4x past MAX_PAR, governor on", file=sys.stderr)
    gov_on = run_4x(True)
    recovered = (auto["rescale_events"] >= 1
                 and auto["p99_surge_late_us"]
                 < max(1.0, 0.5 * static["p99_surge_late_us"]))
    governed_held = (gov_on["shed"] > 0
                     and gov_on["p99_surge_late_us"] < slo_ms * 1e3
                     <= gov_off["p99_surge_late_us"])
    result = {
        "metric": "surge_p99_recovery (cpu-plane)",
        "zipf_keys": n_keys, "base_rate_tps": base_rate,
        "phase_sec": phase_s,
        "static": static, "autoscaled": auto,
        "autoscaler_recovered_p99": recovered,
        "surge_4x_past_max_par": {
            "slo_ms": slo_ms, "max_par": max_par,
            "governor_off": gov_off, "governor_on": gov_on,
            "governor_held_slo": governed_held,
        },
    }
    os.makedirs("results", exist_ok=True)
    with open(os.path.join("results", "surge.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


def _replay_mode() -> None:
    """Realistic-traffic replay scenario (``bench.py --replay``): a
    million-user-shaped workload — Zipf-skewed keys, a compressed
    diurnal rate curve (0.5x -> 1x -> 2x -> 1.5x -> 0.7x), ragged burst
    sizes and late events (EVENT_TIME with bounded lateness) — through
    time-based keyed windows into a sink, run once at-least-once and
    once with the exactly-once sink plane on, checkpointing every ~2 s.
    Reports throughput for both runs, the measured exactly-once
    overhead and the commit accounting (epochs pre-committed/committed,
    commit latency). The runs are wall-clock rate-paced so tuple counts
    differ slightly; correctness differentials live in
    tests/test_exactly_once.py. CPU-plane by construction. Writes
    results/replay.json.

    A third leg exercises the tiered keyed-state store on the device
    plane: Zipf-1.1 keys drawn from a 10M-distinct-key space through a
    stateful device scan whose hot tier is a FIXED device budget
    (``with_tiering``), the cold tail host-spilled. Reports
    ``tiered_keys_per_device_budget`` — addressable key space per
    device-resident slot — plus the observed distinct keys and the
    Tier_* counters. Skipped (with a note) when the device plane is
    unavailable."""
    import shutil
    import tempfile
    import numpy as np

    from windflow_tpu import (ExecutionMode, Keyed_Windows, PipeGraph,
                              Sink_Builder, Source_Builder, TimePolicy,
                              WinType)

    n_keys = int(os.environ.get("WF_REPLAY_KEYS", "512"))
    base_rate = float(os.environ.get("WF_REPLAY_RATE", "12000"))
    block_rows = int(os.environ.get("WF_REPLAY_BLOCK", "512"))
    phase_s = float(os.environ.get("WF_REPLAY_PHASE_SEC", "2"))
    late_frac = float(os.environ.get("WF_REPLAY_LATE_FRAC", "0.05"))
    lateness_us = 200_000
    rate_curve = (0.5, 1.0, 2.0, 1.5, 0.7)  # compressed diurnal shape
    rng = np.random.default_rng(11)
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    probs = 1.0 / ranks ** 1.1
    probs /= probs.sum()
    key_table = rng.choice(n_keys, size=1 << 16, p=probs)
    jitter_table = rng.integers(0, lateness_us, size=1 << 16)
    late_table = rng.random(1 << 16) < late_frac
    burst_table = rng.integers(1, 32, size=4096)  # ragged bursts

    class ReplaySource:
        """Rate-paced Zipf pusher with event-time jitter: most tuples
        carry now-ish timestamps, a ``late_frac`` slice lags by up to
        the window lateness bound, watermarks advance behind the
        lag so late-but-admissible tuples genuinely arrive late.
        Traffic is generated as COLUMN BLOCKS: each burst is built
        vectorized (table lookups on whole index ranges), accumulated
        to ``WF_REPLAY_BLOCK`` rows and shipped in one
        ``push_columns`` call — no per-tuple Python on the ingest
        path. A tuple late by the full bound stays admissible after
        the worst-case block delay: block delay <= lateness, so
        ts >= wm_at_flush - 2*lateness, within the window grace."""

        def __init__(self):
            self.pos = 0

        def __call__(self, shipper):
            t0 = time.monotonic()
            i = 0
            total_s = len(rate_curve) * phase_s
            pend: list = []
            pend_n = 0

            def flush():
                nonlocal pend, pend_n
                if not pend:
                    return
                shipper.push_columns(
                    {"key": np.concatenate([c[0] for c in pend]),
                     "v": np.concatenate([c[1] for c in pend])},
                    ts=np.concatenate([c[2] for c in pend]))
                pend, pend_n = [], 0

            while True:
                t_rel = time.monotonic() - t0
                if t_rel >= total_s:
                    flush()
                    return
                rate = base_rate * rate_curve[
                    min(int(t_rel / phase_s), len(rate_curve) - 1)]
                burst = int(burst_table[i & 0xFFF])
                now_us = int(time.time() * 1e6)
                idx = (i + np.arange(burst)) & 0xFFFF
                ts = now_us - np.where(late_table[idx], jitter_table[idx], 0)
                pend.append((key_table[idx].astype(np.int64),
                             np.arange(i, i + burst, dtype=np.int64),
                             ts.astype(np.int64)))
                pend_n += burst
                i += burst
                if pend_n >= block_rows:
                    flush()
                shipper.set_next_watermark(now_us - lateness_us)
                self.pos = i
                time.sleep(max(0.0, burst / rate
                               - (time.monotonic() - t0 - t_rel)))

        def snapshot_position(self):
            return self.pos

        def restore(self, pos):
            self.pos = pos

    def run(exactly_once: bool) -> dict:
        results = {}
        src = ReplaySource()
        store = tempfile.mkdtemp(prefix="wf_replay_ckpt_")
        txn = tempfile.mkdtemp(prefix="wf_replay_txn_")
        g = PipeGraph(f"replay_{'eo' if exactly_once else 'alo'}",
                      ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                      channel_capacity=256)
        g.with_checkpointing(interval=2.0, store_dir=store)
        win = Keyed_Windows(lambda rows: sum(r["v"] for r in rows),
                            key_extractor=lambda t: t["key"],
                            win_len=500_000, slide_len=500_000,
                            win_type=WinType.TB, lateness=lateness_us,
                            name="sessions", parallelism=2)

        def sink(t):
            if t is not None:
                results[(t.key, t.wid)] = t.value

        snk = Sink_Builder(sink).with_name("snk")
        if exactly_once:
            snk = snk.with_exactly_once(staging_dir=txn)
        g.add_source(Source_Builder(src).with_name("src").build()) \
            .add(win) \
            .add_sink(snk.build())
        t0 = time.perf_counter()
        g.run()
        elapsed = time.perf_counter() - t0
        st = g.get_stats()
        src_rep = [o for o in st["Operators"]
                   if o["name"] == "src"][0]["replicas"][0]
        ns_row = src_rep.get("Ingest_block_ns_per_row", 0)
        out = {
            "tuples": src.pos,
            "tuples_per_sec": round(src.pos / elapsed, 1),
            # host ingest-plane capacity (1e9 / ns-per-row on the block
            # path); the run itself is wall-clock rate-paced, so this is
            # the un-throttled ceiling, not the paced rate above
            "ingest_tuples_per_sec": round(1e9 / ns_row, 1) if ns_row
            else 0.0,
            "ingest_blocks": src_rep.get("Ingest_blocks", 0),
            "window_results": len(results),
            "checkpoints": st.get("Checkpoints", {}).get(
                "Checkpoints_completed", 0),
        }
        if exactly_once:
            snk_op = [op for op in g._ops if op.name == "snk"][0]
            rep = snk_op.replicas[0]
            drv = rep._txn
            out["txn"] = {
                "precommits": rep.stats.txn_precommits,
                "commits": rep.stats.txn_commits,
                "commit_latency_mean_us": round(
                    drv.commit_latency_total_us / max(1, drv.commits), 1),
            }
        shutil.rmtree(store, ignore_errors=True)
        shutil.rmtree(txn, ignore_errors=True)
        return out, results

    def run_tiered() -> dict:
        """Zipf-1.1 traffic over a 10M-distinct-key space through a
        tiered stateful device scan: hot_capacity is the fixed device
        budget, every other key lives in the host cold store. The
        heavy-tail draw means each 512-row batch touches well under
        hot_capacity distinct keys while the run as a whole touches
        orders of magnitude more than fit on device."""
        key_space = int(os.environ.get("WF_REPLAY_TIER_KEYSPACE",
                                       str(10_000_000)))
        hot = int(os.environ.get("WF_REPLAY_TIER_HOT", "1024"))
        n = int(os.environ.get("WF_REPLAY_TIER_TUPLES", "80000"))
        batch = 512
        try:
            from windflow_tpu.tpu import Map_TPU_Builder
        except Exception as e:  # device plane absent: report, don't fail
            return {"skipped": f"device plane unavailable: {e}"}
        trng = np.random.default_rng(11)
        # zipf(1.1) is the unbounded heavy tail; fold the rare
        # beyond-space draws back in rather than rejecting
        keys = (trng.zipf(1.1, size=n) - 1) % key_space
        vals = np.arange(n, dtype=np.float64)

        def src(shipper):
            for i in range(n):
                shipper.push({"k": int(keys[i]), "v": float(vals[i])})

        g = PipeGraph("replay_tiered", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)
        g.add_source(Source_Builder(src).with_name("src")
                     .with_output_batch_size(batch).build()) \
         .add(Map_TPU_Builder(
                lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                                 st + row["v"]))
              .with_state(np.float32(0)).with_key_by("k")
              .with_tiering(policy="lru", hot_capacity=hot)
              .with_name("scan").build()) \
         .add_sink(Sink_Builder(lambda t: None).with_name("snk").build())
        t0 = time.perf_counter()
        g.run()
        elapsed = time.perf_counter() - t0
        rep = [o for o in g.get_stats()["Operators"]
               if o["name"] == "scan"][0]["replicas"][0]
        distinct = rep.get("Tier_hot_keys", 0) + rep.get("Tier_cold_keys", 0)
        return {
            "key_space": key_space,
            "hot_capacity": hot,
            "tuples": n,
            "tuples_per_sec": round(n / elapsed, 1),
            "distinct_keys_seen": distinct,
            "keys_per_device_budget": round(key_space / hot, 1),
            "tier_promotes": rep.get("Tier_promotes", 0),
            "tier_demotes": rep.get("Tier_demotes", 0),
            "tier_miss_rate": rep.get("Tier_miss_rate", 0.0),
        }

    def run_delta() -> dict:
        """Incremental-checkpoint leg: Zipf-1.1 traffic through a DENSE
        stateful device scan with ``WF_CKPT_DELTA``/``WF_CKPT_ASYNC`` on
        and commit-waited checkpoints. A preload pass registers the full
        key space (fixing the table capacity, so every later epoch is
        delta-eligible); each epoch then snapshots only the rows the
        heavy-tail traffic touched since the last full base. Records
        ``ckpt_delta_bytes_ratio`` — per-epoch delta bytes over
        per-epoch full-base bytes."""
        try:
            from windflow_tpu.tpu import Map_TPU_Builder
        except Exception as e:  # device plane absent: report, don't fail
            return {"skipped": f"device plane unavailable: {e}"}
        from windflow_tpu.checkpoint import CheckpointStore

        key_space = int(os.environ.get("WF_REPLAY_DELTA_KEYS", "4096"))
        n = int(os.environ.get("WF_REPLAY_DELTA_TUPLES", "40000"))
        skew = float(os.environ.get("WF_REPLAY_DELTA_SKEW", "1.5"))
        epoch_every, batch = 8_000, 512
        store = tempfile.mkdtemp(prefix="wf_replay_delta_")
        drng = np.random.default_rng(11)
        # steeper skew than the tiered leg: the delta plane's payoff is
        # the change RATE, so the leg models a hot working set over a
        # large registered key space (zipf 1.1 folded into 4k keys
        # touches nearly every key each epoch — deltas degenerate to
        # full size there by construction)
        keys = (drng.zipf(skew, size=n) - 1) % key_space
        vals = np.arange(n, dtype=np.float64)

        class DeltaSource:
            def __init__(self):
                self.pos = 0

            def __call__(self, shipper):
                st = CheckpointStore(store)
                for k in range(key_space):  # register every key
                    shipper.push({"k": k, "v": 0.0})
                for i in range(n):
                    shipper.push({"k": int(keys[i]), "v": float(vals[i])})
                    self.pos = i + 1
                    if self.pos % epoch_every == 0:
                        before = st.latest() or 0
                        shipper.request_checkpoint()
                        deadline = time.time() + 30
                        while (st.latest() or 0) <= before \
                                and time.time() < deadline:
                            time.sleep(0.002)

            def snapshot_position(self):
                return self.pos

            def restore(self, pos):
                self.pos = pos

        src = DeltaSource()
        g = PipeGraph("replay_delta", ExecutionMode.DEFAULT,
                      TimePolicy.INGRESS_TIME)
        g.with_checkpointing(store_dir=store)
        g.add_source(Source_Builder(src).with_name("src")
                     .with_output_batch_size(batch).build()) \
         .add(Map_TPU_Builder(
                lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                                 st + row["v"]))
              .with_state(np.float32(0)).with_key_by("k")
              .with_name("scan").build()) \
         .add_sink(Sink_Builder(lambda t: None).with_name("snk").build())
        old = {k: os.environ.get(k)
               for k in ("WF_CKPT_DELTA", "WF_CKPT_ASYNC")}
        os.environ["WF_CKPT_DELTA"] = "1"
        os.environ["WF_CKPT_ASYNC"] = "1"
        t0 = time.perf_counter()
        try:
            g.run()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        elapsed = time.perf_counter() - t0
        st = g.get_stats()
        ck = st.get("Checkpoints", {})
        rep = [o for o in st["Operators"]
               if o["name"] == "scan"][0]["replicas"][0]
        shutil.rmtree(store, ignore_errors=True)
        completed = ck.get("Checkpoints_completed", 0)
        dblobs = ck.get("Checkpoint_delta_blobs", 0)
        dbytes = ck.get("Checkpoint_delta_bytes", 0)
        fbytes = ck.get("Checkpoint_full_bytes", 0)
        full_epochs = max(1, completed - dblobs)
        ratio = ((dbytes / dblobs) / (fbytes / full_epochs)
                 if dblobs and fbytes else 0.0)
        return {
            "key_space": key_space,
            "tuples": n + key_space,
            "tuples_per_sec": round((n + key_space) / elapsed, 1),
            "checkpoints": completed,
            "delta_blobs": dblobs,
            "delta_bytes_per_epoch": round(dbytes / dblobs, 1)
            if dblobs else 0.0,
            "full_bytes_per_epoch": round(fbytes / full_epochs, 1),
            "async_uploads": ck.get("Checkpoint_async_uploads", 0),
            "cut_pause_last_us": rep.get("Checkpoint_cut_pause_usec",
                                         0.0),
            "ckpt_delta_bytes_ratio": round(ratio, 4),
        }

    print("replay: at-least-once run", file=sys.stderr)
    alo, alo_res = run(False)
    print("replay: exactly-once run", file=sys.stderr)
    eo, eo_res = run(True)
    print("replay: tiered-state run (Zipf 1.1, 10M key space)",
          file=sys.stderr)
    tiered = run_tiered()
    print("replay: incremental-checkpoint run (delta + async)",
          file=sys.stderr)
    delta = run_delta()
    overhead = (100.0 * (1.0 - eo["tuples_per_sec"]
                         / alo["tuples_per_sec"])
                if alo["tuples_per_sec"] else 0.0)
    result = {
        "metric": "replay_realistic_traffic (cpu-plane)",
        "zipf_keys": n_keys, "base_rate_tps": base_rate,
        "block_rows": block_rows,
        "rate_curve": list(rate_curve), "phase_sec": phase_s,
        "late_fraction": late_frac, "lateness_usec": lateness_us,
        "ingest_tuples_per_sec": alo["ingest_tuples_per_sec"],
        "at_least_once": alo, "exactly_once": eo,
        "exactly_once_overhead_pct": round(overhead, 2),
        "tiered": tiered,
        "tiered_keys_per_device_budget":
            tiered.get("keys_per_device_budget", 0.0),
        "ckpt_delta": delta,
        "ckpt_delta_bytes_ratio":
            delta.get("ckpt_delta_bytes_ratio", 0.0),
    }
    os.makedirs("results", exist_ok=True)
    with open(os.path.join("results", "replay.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


def chip_devices(who: str):
    """``jax.devices()`` for a measurement, with the persistent compile
    cache placed first. Exits non-zero unless they are TPUs — or the
    CPU backend a caller asked for with ``JAX_PLATFORMS=cpu`` (shared
    with scripts/bench_mesh.py: ONE rule)."""
    import jax

    from windflow_tpu.runtime.compile_cache import setup_compile_cache

    setup_compile_cache()
    dev = jax.devices()
    platform = dev[0].platform
    print(f"{who}: platform={platform} device_kind={dev[0].device_kind} "
          f"n_devices={len(dev)}", file=sys.stderr)
    if platform != "tpu" and not (
            platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu"):
        sys.exit(f"{who}: needs a TPU, jax.devices() gave {platform!r} "
                 "(set JAX_PLATFORMS=cpu to run on the CPU backend on "
                 "purpose; its numbers are not device metrics)")
    return dev


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--surge":
        _surge_mode()
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--replay":
        _replay_mode()
        return
    _measure_and_report(chip_devices("bench"))


def _chunk_stats(chunks) -> dict:
    """mean / min / best tuples-per-sec (and mean windows-per-sec) over
    the timed stream chunks — ONE aggregation (mean) for every headline
    field; best/min disclose the spread (at REPEATS=5 a percentile label
    would be dishonest; min is what it is)."""
    if not chunks:
        return {"mean": 0.0, "min": 0.0, "best": 0.0, "wps_mean": 0.0}
    tl = sorted(c[0] for c in chunks)
    return {"mean": sum(tl) / len(tl), "min": tl[0], "best": tl[-1],
            "wps_mean": sum(c[1] for c in chunks) / len(chunks)}


def _measure_and_report(devices) -> None:
    platform = devices[0].platform

    def _log(msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr)

    _log(f"repeats={REPEATS} at "
         f"{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}")
    chunks, p50_us, p99_us, programs = _run_config(
        N_KEYS, WIN_PER_BATCH, N_BATCHES, lat_batches=N_BATCHES,
        repeats=REPEATS)
    st = _chunk_stats(chunks)
    wps = st["wps_mean"]
    _log(f"{N_KEYS} keys 64k batches -> mean {st['mean']:,.0f} / "
         f"min {st['min']:,.0f} / best {st['best']:,.0f} t/s, "
         f"{wps:,.0f} win/s (mean), {programs} programs")
    # the original 16k-batch protocol (same key count / window config):
    # robustness means >=1x at BOTH operating points, not only the
    # batch-size sweet spot
    chunks16, _, _, _ = _run_config(
        N_KEYS, WIN_PER_BATCH, 4 * N_BATCHES, repeats=REPEATS,
        batch_size=16384)
    st16 = _chunk_stats(chunks16)
    _log(f"{N_KEYS} keys 16k batches -> mean {st16['mean']:,.0f} / "
         f"min {st16['min']:,.0f} / best {st16['best']:,.0f} t/s")
    hc_chunks, _, _, _ = _run_config(
        HC_KEYS, HC_WIN_PER_BATCH, HC_BATCHES, repeats=REPEATS)
    hc_st = _chunk_stats(hc_chunks)
    hc_wps = hc_st["wps_mean"]
    _log(f"{HC_KEYS} keys -> mean {hc_st['mean']:,.0f} t/s, "
         f"{hc_wps:,.0f} win/s (mean)")
    # sparse-watermark variant (watermark every 8th batch — the
    # production shape: continuous batches, periodic watermarks): the
    # regime the deferred level rebuild targets; additive field, the
    # headline configs keep their r1-r3 per-batch-watermark protocol
    sw_chunks, _, _, _ = _run_config(
        HC_KEYS, HC_WIN_PER_BATCH, HC_BATCHES, repeats=REPEATS,
        batch_size=16384, wm_every=8)
    sw_st = _chunk_stats(sw_chunks)
    _log(f"{HC_KEYS} keys sparse-wm 16k batches -> mean "
         f"{sw_st['mean']:,.0f} t/s")
    # latency-optimized operating point: small batches span less stream
    # time per step (batch size is a per-op builder knob, as in the
    # reference). Both p99 figures are OPERATOR fire-to-delivery latency
    # (the sink consumes device batches directly); a CPU sink behind the
    # default depth-4 exit FIFO adds up to one watermark-punctuation
    # interval — set WF_EXIT_PIPELINE_DEPTH=0 for latency-sensitive exits.
    _, lat_p50_us, lat_p99_us, _ = _run_config(N_KEYS, 64, 4,
                                               lat_batches=48,
                                               batch_size=16384)
    _log(f"fire latency p50/p99 {p50_us:,.0f}/{p99_us:,.0f}us "
         f"(64k batches) / {lat_p50_us:,.0f}/{lat_p99_us:,.0f}us "
         f"(16k batches)")

    # secondary device ops (one line each in the JSON extras)
    import jax.numpy as jnp

    from windflow_tpu.tpu.ops_tpu import Map_TPU, Reduce_TPU

    smap_tps = _run_op_config(
        lambda: Map_TPU(lambda row, st: ({**row, "value": row["value"]
                                          + st["n"]}, {"n": st["n"] + 1}),
                        key_extractor="key", state_init={"n": jnp.int32(0)},
                        name="bench_smap"), 64, 12, repeats=REPEATS)
    kred_tps = _run_op_config(
        lambda: Reduce_TPU(lambda a, b: {"key": b["key"],
                                         "value": a["value"] + b["value"]},
                           key_extractor="key", name="bench_kred"), 256, 12,
        repeats=REPEATS)

    def _fused_chain_op():
        # 3-op device chain (map∘filter∘map) as ONE fused replica — one
        # XLA program + one dispatch commit per batch (tpu/fused_ops.py);
        # measured at 16k batches, the host-bound regime fusion targets
        from windflow_tpu.tpu.fused_ops import FusedTPUReplica
        from windflow_tpu.tpu.ops_tpu import Filter_TPU

        class _FusedChain:
            def build_replicas(self):
                ops = [Map_TPU(lambda f: {**f, "value": f["value"] * 3
                                          + f["key"]}, name="bench_fm1"),
                       Filter_TPU(lambda f: (f["value"] % 2) == 0,
                                  name="bench_ff1"),
                       Map_TPU(lambda f: {**f, "value": f["value"] + 1},
                               name="bench_fm2")]
                self.replicas = [FusedTPUReplica(ops, 0)]

        return _FusedChain()

    fused_tps = _run_op_config(_fused_chain_op, 64, 12, repeats=REPEATS,
                               batch_size=16384)

    def _megabatch_chain_op():
        # same fused chain behind a WF_MEGABATCH=16 dispatch queue: the
        # overflow pops run 16 queued batches as ONE lax.scan dispatch
        # (runtime/dispatch.py); 48 batches/repeat so the 16-deep queue
        # overflows and the steady window is scan groups, not singles
        from windflow_tpu.runtime.dispatch import DeviceDispatchQueue
        from windflow_tpu.tpu.fused_ops import FusedTPUReplica
        from windflow_tpu.tpu.ops_tpu import Filter_TPU

        class _MBChain:
            def build_replicas(self):
                ops = [Map_TPU(lambda f: {**f, "value": f["value"] * 3
                                          + f["key"]}, name="bench_bm1"),
                       Filter_TPU(lambda f: (f["value"] % 2) == 0,
                                  name="bench_bf1"),
                       Map_TPU(lambda f: {**f, "value": f["value"] + 1},
                               name="bench_bm2")]
                r = FusedTPUReplica(ops, 0)
                r.dispatch = DeviceDispatchQueue(stats=r.stats, depth=16,
                                                 megabatch=16)
                self.replicas = [r]

        return _MBChain()

    mb_tps = _run_op_config(_megabatch_chain_op, 64, 48, repeats=REPEATS,
                            batch_size=16384)
    _log(f"stateful map {smap_tps:,.0f} t/s, "
         f"keyed reduce {kred_tps:,.0f} t/s, "
         f"fused 3-op chain {fused_tps:,.0f} t/s (16k), "
         f"megabatch x16 {mb_tps:,.0f} t/s (16k)")

    metric = "ffat_sliding_window_tuples_per_sec_per_chip"
    if platform != "tpu":
        metric += f" ({platform})"
    result = {
        "metric": metric,
        "value": round(st["mean"], 1),
        "unit": "tuples/sec",
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "throughput_aggregation": f"mean-of-{REPEATS}-chunks",
        "value_min": round(st["min"], 1),
        "value_best": round(st["best"], 1),
        "tuples_per_sec_16k_batches": round(st16["mean"], 1),
        "p50_window_fire_latency_us": round(p50_us, 1),
        "p99_window_fire_latency_us": round(p99_us, 1),
        "p50_window_fire_latency_us_latency_config": round(lat_p50_us, 1),
        "p99_window_fire_latency_us_latency_config": round(lat_p99_us, 1),
        "windows_per_sec": round(wps, 1),
        "hc_keys": HC_KEYS,
        "hc_tuples_per_sec": round(hc_st["mean"], 1),
        "hc_windows_per_sec": round(hc_wps, 1),
        "hc_sparse_wm_tuples_per_sec": round(sw_st["mean"], 1),
        "stateful_map_tuples_per_sec": round(smap_tps, 1),
        "keyed_reduce_tuples_per_sec": round(kred_tps, 1),
        "fused_chain_tuples_per_sec": round(fused_tps, 1),
        "megabatch_tuples_per_sec": round(mb_tps, 1),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
