"""The eight per-layer metrics that read the program's stage counters
(``windflow_tpu/monitoring/tracing.py`` STAGES, ``Thread_cpu_usec``): a
``--rehearse-cpu --trace 1`` run of ``ysb.saturated`` reports each of them
with a finite, non-negative value. Counts and host-clock times of a CPU
run: no device number (every name ends in ``.cpu_rehearsal``)."""

import json
import math
import os
import subprocess
import sys

from harness.cell import BENCH_DIR, ROOT

STAGE_METRICS = (
    "worker_cpu_cores.sat", "source_cpu_share.sat",
    "window_starved_share.sat", "stage_copy_us_per_batch.sat",
    "h2d_put_us_per_batch.sat", "launch_us_per_program.sat",
    "readback_wait_us_per_batch.sat", "exit_fifo_wait_ms_per_batch.sat")


def test_traced_rehearsal_reports_the_stage_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "ysb.saturated", "--seed", "2147483929", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0
    read = {}
    for name in STAGE_METRICS:
        m = r["metrics"].get(name + ".cpu_rehearsal")
        assert m is not None, (name, sorted(r["metrics"]))
        read[name] = m["value"]
        assert math.isfinite(read[name]) and read[name] >= 0, (name, m)
    # the threads did run, and the source did stage and ship batches
    for name in ("worker_cpu_cores.sat", "stage_copy_us_per_batch.sat",
                 "h2d_put_us_per_batch.sat", "launch_us_per_program.sat"):
        assert read[name] > 0, (name, read)
