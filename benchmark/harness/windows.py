"""Dense (key x window) tables: the fold every configuration's plain
reference uses, and the comparison that decides ``correct``.

Window ``w`` of every key is ``[w*slide, w*slide + win)`` from absolute
time 0 (the program numbers time-based windows the same way), and at end
of stream every window that holds a counted event has fired once.
``time_results_due`` and ``time_windows_per_event`` are what the harness
takes for a configuration whose module does not say them itself: the
only two places outside a configuration that read ``cfg["window"]``.
Nothing here imports the program.
"""

from __future__ import annotations

import math

import numpy as np


def dense_window_fold(blocks, n_keys: int, win_us: int, slide_us: int,
                      last_ts: int):
    """Fold ``blocks`` — an iterable of ``(keys, weights, ts)`` int arrays,
    one triple per offered block — into ``{"value", "count"}`` tables of
    shape (n_keys, n_windows), block by block through a dense pane table
    (``np.bincount`` over the few panes a block spans), never one sort
    over the stream. ``count`` is the number of events a window holds."""
    pane = math.gcd(win_us, slide_us)
    wu, su = win_us // pane, slide_us // pane
    n_panes = int(last_ts) // pane + 1
    psum = np.zeros((n_keys, n_panes), np.int64)
    pcnt = np.zeros((n_keys, n_panes), np.int64)
    for keys, weights, ts in blocks:
        if len(ts) == 0:
            continue
        p = ts // pane
        lo = int(p.min())
        span = int(p.max()) - lo + 1
        flat = keys.astype(np.int64) * span + (p - lo)
        size = n_keys * span
        pcnt[:, lo:lo + span] += np.bincount(
            flat, minlength=size).reshape(n_keys, span)
        # float64 weights are exact below 2**53
        psum[:, lo:lo + span] += np.bincount(
            flat, weights=weights, minlength=size).astype(np.int64).reshape(
                n_keys, span)
    n_win = (n_panes - 1) // su + 1          # windows that hold any pane
    starts = np.arange(n_win) * su
    ends = np.minimum(starts + wu, n_panes)
    out = {}
    for name, panes in (("value", psum), ("count", pcnt)):
        c = np.zeros((n_keys, n_panes + 1), np.int64)
        np.cumsum(panes, axis=1, out=c[:, 1:])
        out[name] = c[:, ends] - c[:, starts]
    return out


def table_rows(table):
    """The (key, wid, value) rows a correct system delivers for ``table``:
    one per window that holds an event."""
    k, w = np.nonzero(table["count"])
    return k, w, table["value"][k, w]


def time_results_due(table, blocks, cfg: dict, stream: dict,
                     wm_us: int) -> int:
    """The default ``results_due``, the rule of a time-based window: of
    ``table``'s rows, those whose window ends at or before the watermark
    ``wm_us`` (the others wait for the end-of-stream flush)."""
    w = cfg["window"]
    _, wid, _ = table_rows(table)
    return int((wid * w["slide_us"] + w["win_us"] <= wm_us).sum())


def time_windows_per_event(cfg: dict) -> int:
    """The default ``windows_per_event``: a time-based sliding window
    holds an event in ``win / slide`` windows."""
    w = cfg["window"]
    return max(1, w["win_us"] // w["slide_us"])


def compare_results(expected, key, wid, value, valid) -> dict:
    """Hold delivered rows to ``expected``: every window that holds an
    event delivered exactly once with the exact value, nothing else
    delivered as valid. An empty window may fire with ``valid`` false.
    Returns counts; ``mismatches`` is their sum and its limit is 0."""
    n_keys, n_win = expected["count"].shape
    key = np.asarray(key).astype(np.int64)
    wid = np.asarray(wid).astype(np.int64)
    valid = np.asarray(valid).astype(bool)
    inside = (key >= 0) & (key < n_keys) & (wid >= 0) & (wid < n_win)
    stray = int((valid & ~inside).sum())
    flat = key[inside] * n_win + wid[inside]
    v_in = valid[inside]
    size = n_keys * n_win
    got_n = np.bincount(flat[v_in], minlength=size).reshape(n_keys, n_win)
    got_v = np.bincount(
        flat[v_in], weights=np.asarray(value)[inside][v_in].astype(np.float64),
        minlength=size).astype(np.int64).reshape(n_keys, n_win)
    held = expected["count"] > 0
    empty_fired = np.bincount(flat[~v_in], minlength=size).reshape(
        n_keys, n_win)
    out = {
        "expected": int(held.sum()),
        "delivered": int(valid.sum()),
        "missing": int((held & (got_n == 0)).sum()),
        "duplicated": int((held & (got_n > 1)).sum()),
        "wrong_value": int((held & (got_n == 1)
                            & (got_v != expected["value"])).sum()),
        "unexpected": int((~held & (got_n > 0)).sum()) + stray,
        "invalid_but_held": int((held & (empty_fired > 0)).sum()),
    }
    out["mismatches"] = (out["missing"] + out["duplicated"]
                         + out["wrong_value"] + out["unexpected"]
                         + out["invalid_but_held"])
    # events of windows that never arrived, for ``failed``
    out["events_unanswered"] = int(
        expected["count"][held & (got_n == 0)].sum())
    return out
