"""The eleven per-layer metrics that read the host's account of its own
time (PR 36: ``Worker_blocked_put/get_usec``, ``Worker_device_wait_usec``,
``Worker_unaccounted_usec`` beside ``Thread_cpu_usec``; the watchdog's
``Process_*`` / ``Gc_*``; the thread-CPU twins of the prep and commit
stages): a ``--rehearse-cpu --trace 1`` run of ``ysb.saturated`` reports
each of them with a finite, non-negative value, and the five rows that
split the window worker's wall sum to the window. Counts and host-clock
times of a CPU run: no device number (every name ends in
``.cpu_rehearsal``)."""

import json
import math
import os
import subprocess
import sys

from harness.cell import BENCH_DIR, ROOT

WINDOW_ROWS = (
    "window_busy_share.sat", "window_input_wait_share.sat",
    "window_backpressured_share.sat", "window_device_wait_share.sat",
    "window_unaccounted_share.sat")
ACCOUNT_METRICS = WINDOW_ROWS + (
    "first_backpressured_share.sat", "interp_wait_cores.sat",
    "interp_acquire_us.sat", "process_stall_share.sat",
    "gc_pause_share.sat", "dispatch_cpu_us_per_batch.sat")


def test_traced_rehearsal_reports_the_worker_account():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "ysb.saturated", "--seed", "2147484013", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0
    read = {}
    for name in ACCOUNT_METRICS:
        m = r["metrics"].get(name + ".cpu_rehearsal")
        assert m is not None, (name, sorted(r["metrics"]))
        read[name] = m["value"]
        assert math.isfinite(read[name]) and read[name] >= 0, (name, m)
    # the worker's wall, split five ways, is the window
    assert abs(sum(read[n] for n in WINDOW_ROWS) - 100.0) <= 2.0, read
    # its thread ran, the watchdog ticked, and a batch cost CPU
    for name in ("window_busy_share.sat", "interp_acquire_us.sat",
                 "dispatch_cpu_us_per_batch.sat"):
        assert read[name] > 0, (name, read)
    # what a batch costs in CPU is inside what it takes on the wall
    wall = r["metrics"]["dispatch_host_us_per_batch.sat.cpu_rehearsal"]
    assert read["dispatch_cpu_us_per_batch.sat"] <= wall["value"] * 1.01
