"""The dense fold against a per-event dict fold, and the comparison against
each way a result set can be wrong."""

import numpy as np
import pytest

from harness.windows import compare_results, dense_window_fold, table_rows


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
