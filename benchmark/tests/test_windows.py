"""The dense fold against a per-event dict fold, the comparison against
each way a result set can be wrong, and the two things a configuration's
module may say in place of the time-window defaults."""

import json
import os

import numpy as np
import pytest

from harness.cell import BENCH_DIR, ROOT, Cell
from harness.runner import Run
from harness.traffic import Offered
from harness.windows import (compare_results, dense_window_fold, table_rows,
                             time_windows_per_event)


def dict_fold(blocks, win, slide):
    out = {}
    for keys, weights, ts in blocks:
        for k, v, t in zip(keys.tolist(), weights.tolist(), ts.tolist()):
            w = t // slide
            while w >= 0 and w * slide + win > t:
                s, n = out.get((k, w), (0, 0))
                out[(k, w)] = (s + v, n + 1)
                w -= 1
    return out


def stream(seed, n_blocks=5, rows=300, n_keys=7, step=37):
    rng = np.random.default_rng(seed)
    t0, blocks = 0, []
    for _ in range(n_blocks):
        ts = t0 + np.arange(rows, dtype=np.int64) * step
        t0 = int(ts[-1]) + step + int(rng.integers(0, 5000))
        blocks.append((rng.integers(0, n_keys, rows).astype(np.int32),
                       rng.integers(0, 100, rows).astype(np.int32), ts))
    return blocks, n_keys


@pytest.mark.parametrize("win,slide", [(1000, 250), (1000, 1000), (900, 300)])
def test_dense_fold_equals_dict_fold(win, slide):
    blocks, n_keys = stream(3)
    table = dense_window_fold(blocks, n_keys, win, slide,
                              int(blocks[-1][2][-1]))
    want = dict_fold(blocks, win, slide)
    k, w, v = table_rows(table)
    got = {(int(a), int(b)): int(c) for a, b, c in zip(k, w, v)}
    assert got == {kw: s for kw, (s, _) in want.items()}
    assert int(table["count"].sum()) == sum(n for _, n in want.values())


def _exact():
    blocks, n_keys = stream(4)
    table = dense_window_fold(blocks, n_keys, 1000, 250,
                              int(blocks[-1][2][-1]))
    k, w, v = table_rows(table)
    return table, k, w, v, np.ones(len(k), bool)


def test_exact_rows_compare_clean():
    table, k, w, v, ok = _exact()
    c = compare_results(table, k, w, v, ok)
    assert c["mismatches"] == 0 and c["expected"] == len(k)
    # an empty window fired as invalid is allowed
    ek, ew = np.argwhere(table["count"] == 0)[0]
    c = compare_results(table, np.r_[k, ek], np.r_[w, ew], np.r_[v, 0],
                        np.r_[ok, False])
    assert c["mismatches"] == 0


@pytest.mark.parametrize("fault,field", [
    ("value", "wrong_value"), ("missing", "missing"),
    ("duplicate", "duplicated"), ("extra", "unexpected"),
    ("invalid", "invalid_but_held"), ("stray", "unexpected")])
def test_each_fault_is_a_mismatch(fault, field):
    table, k, w, v, ok = _exact()
    v, ok = v.copy(), ok.copy()
    if fault == "value":
        v[5] += 1
    elif fault == "missing":
        k, w, v, ok = k[1:], w[1:], v[1:], ok[1:]
    elif fault == "duplicate":
        k, w, v, ok = (np.r_[a, a[:1]] for a in (k, w, v, ok))
    elif fault == "extra":
        ek, ew = np.argwhere(table["count"] == 0)[0]
        k, w, v, ok = np.r_[k, ek], np.r_[w, ew], np.r_[v, 1], np.r_[ok, True]
    elif fault == "invalid":
        ok[3] = False
    elif fault == "stray":
        k, w, v, ok = np.r_[k, 10**6], np.r_[w, 0], np.r_[v, 1], np.r_[ok, True]
    c = compare_results(table, k, w, v, ok)
    assert c[field] >= 1 and c["mismatches"] >= 1
    if fault == "missing":
        assert c["events_unanswered"] >= 1


# -- what is due after warm-up, and how many windows hold an event --------

def _warm(cell, seed=2_147_483_659):
    """The run's state as ``wait_quiet`` finds it: every warm-up block
    offered, nothing of the window yet."""
    run = Run(cell, seed, 1.0, 0.0)
    run.stream = cell.module.make_stream(seed, cell.cfg, cell.traffic)
    run.offered = Offered(run.stream["pool"], run.clock)
    run.offered.n_warm = run.clock.warm_blocks
    return run


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    TIME_CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("name", TIME_CELLS)
def test_time_defaults_are_the_rule_the_runner_had(name):
    cell = Cell(name, rehearse=True)
    assert not hasattr(cell.module, "results_due")
    assert not hasattr(cell.module, "windows_per_event")
    run = _warm(cell)
    blocks = list(run.offered.blocks())
    table = cell.module.reference(iter(blocks), cell.cfg, run.stream,
                                  run.offered.last_ts)
    # the lines of runner.py and reductions.py before the defaults moved
    w = cell.cfg["window"]
    _, wid, _ = table_rows(table)
    end_us = wid * w["slide_us"] + w["win_us"]
    old = int((end_us <= int(blocks[-1][1][0]) - 1).sum())
    assert 0 < old < len(wid)
    assert run.warm_results_due() == old
    assert time_windows_per_event(cell.cfg) == max(
        1, w["win_us"] // w["slide_us"])


def cb8_cell():
    data = os.path.join(BENCH_DIR, "tests", "data")
    return Cell("cb8.saturated", True, os.path.join(data, "index.json"),
                os.path.join(data, "workloads"))


def test_count_based_configuration_says_both_itself():
    """``cb8``: no ``window`` key; its reference and its ``results_due``
    against a fold of one event at a time."""
    cell = cb8_cell()
    assert "window" not in cell.cfg
    win, q = cell.cfg["count_window"]["win_rows"], cell.cfg["spike_quarters"]
    run = _warm(cell)
    blocks = list(run.offered.blocks())
    seen, want, complete = {}, {}, 0
    for cols, _ in blocks:
        for k, v in zip(cols["device"].tolist(), cols["value"].tolist()):
            seen.setdefault(k, []).append(v)
    for k, vals in seen.items():
        for w in range(len(vals)):
            part = vals[w:w + win]
            if abs(part[-1] * len(part) - sum(part)) * 4 > q * sum(part):
                want[(k, w)] = sum(part)
                complete += len(part) == win
    table = cell.module.reference(iter(blocks), cell.cfg, run.stream, 0)
    k, w, v = table_rows(table)
    assert dict(zip(zip(k.tolist(), w.tolist()), v.tolist())) == want
    assert 0 < complete < len(want) < sum(len(s) for s in seen.values())
    assert run.warm_results_due() == complete
    assert cell.module.windows_per_event(cell.cfg) == win


def test_only_the_two_defaults_read_a_time_window():
    """No line of ``harness/`` or ``run.py`` reads ``cfg["window"]``,
    ``win_us`` or ``slide_us`` but the two defaults and the fold's own
    arguments, all in ``windows.py``."""
    files = [os.path.join(BENCH_DIR, "run.py")] + [
        os.path.join(BENCH_DIR, "harness", f)
        for f in sorted(os.listdir(os.path.join(BENCH_DIR, "harness")))
        if f.endswith(".py") and f != "windows.py"]
    for path in files:
        with open(path) as f:
            text = f.read()
        for word in ('["window"]', "win_us", "slide_us"):
            assert word not in text, (path, word)
