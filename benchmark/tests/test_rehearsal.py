"""CPU rehearsal of every cell at the tiny size its workload file gives:
the result's shape, ``correct`` true, the control not correct, and
``correct`` false with the timed path broken underneath the harness. No
number here is a device number (every metric name ends in
``.cpu_rehearsal``).

Beside the benchmark's cells runs ``cb8.saturated``, a test-only
deployment under ``tests/data`` with an index and a workload file of its
own: a count-based window and a filter after it, whose module says
``results_due`` and ``windows_per_event`` and whose file has no ``window``
key. It proves that such a deployment is files alone."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from harness.cell import BENCH_DIR, ROOT, Cell
from harness.runner import run_cell

DATA = os.path.join(BENCH_DIR, "tests", "data")
# test-only cells: their index and the directory of their workload files
TEST_ONLY = {"cb8.saturated": (os.path.join(DATA, "index.json"),
                               os.path.join(DATA, "workloads"))}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]] + list(TEST_ONLY)
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def make_cell(name, rehearse=False):
    return Cell(name, rehearse, *TEST_ONLY.get(name, ()))


def rehearse(name, seed=2_147_483_659, seconds=1.5, trace=False, **kw):
    return run_cell(make_cell(name, True), seed, seconds, trace,
                    time.perf_counter(), log=lambda m: None, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_and_control_is_not(name):
    r = rehearse(name, control=True)
    assert list(r)[:5] == KEYS and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = make_cell(name)
    assert set(r["metrics"]) == {m["name"] + ".cpu_rehearsal"
                                 for m, _ in cell.metrics("end_to_end")}
    for c, limit in cell.cfg["limits"].items():
        assert r["compared"][c] == {"value": 0, "limit": limit}
    assert r["compared"]["control_result_mismatches"]["value"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(name):
    r = rehearse(name, seed=11, seconds=2.0, trace=True)
    assert r["correct"] is True
    names = {m["name"] + ".cpu_rehearsal" for m, _ in make_cell(name).metrics("per_layer")}
    # trace-sourced metrics read nothing on the CPU backend and are left
    # out; a reader never returns 0 for a share
    assert set(r["metrics"]) <= names
    assert not any("roofline" in k or "idle" in k for k in r["metrics"])
    assert any(k.startswith("compiles_in_window") for k in r["metrics"])
    assert "breakdown" in r and r["device"]["window_s"] > 0


def _alter_value(cell_name):
    col = make_cell(cell_name).cfg["result"]["value"]
    seen = []

    def fault(cols):
        if not seen and len(cols[col]):
            seen.append(1)
            cols = dict(cols)
            v = np.array(cols[col])
            v[0] += 1
            cols[col] = v
        return cols
    return fault


def _drop_call():
    seen = []

    def fault(cols):
        if not seen and cols["valid"].any():
            seen.append(1)
            return None
        return cols
    return fault


def _halve_block():
    seen = []

    def fault(cols, ts):
        seen.append(1)
        if len(seen) == 4:
            half = len(ts) // 2
            return {k: v[:half] for k, v in cols.items()}, ts[:half]
        return cols, ts
    return fault


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "answer_never_comes",
                                   "half_of_a_batch_left_out"])
def test_broken_timed_path_is_not_correct(name, fault):
    faults = {"answer_altered": {"sink": _alter_value(name)},
              "answer_never_comes": {"sink": _drop_call()},
              "half_of_a_batch_left_out": {"source": _halve_block()}}[fault]
    r = rehearse(name, seed=5, faults=faults)
    assert r["correct"] is False
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    if fault == "answer_never_comes":
        assert "result_mismatches" in bad and r["failed"] > 0
    elif fault == "answer_altered":
        assert bad == {"result_mismatches"}
    else:
        assert "events_unaccounted" in bad


def _run_py(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_run_py_fails_without_a_tpu_and_prints_no_result():
    p = _run_py("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_run_py_last_line_is_the_result_and_ignores_bench_run():
    p = _run_py("--workload", CELLS[0], "--seed", "2147483777", "--seconds",
                "1", "--trace", "0", "--rehearse-cpu",
                env={"BENCH_RUN": "7"})
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(r) == KEYS + ["compared"] and r["correct"] is True
    assert r["device"]["platform"] == "cpu"
    assert all(k.endswith(".cpu_rehearsal") for k in r["metrics"])
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")
