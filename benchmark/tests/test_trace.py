"""The trace reduction: on a hand-written trace whose numbers are worked
out below, and on a small trace recorded on the chip."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 10**9   # picoseconds in a millisecond


def _events(rows, names):
    return "\n".join(
        f"    events {{ metadata_id: {names.index(n) + 1} "
        f"offset_ps: {int(s * MS)} duration_ps: {int(d * MS)} }}"
        for n, s, d in rows)


def _plane(pid, name, lines):
    names = sorted({n for _, rows in lines for n, _, _ in rows})
    body = "\n".join(
        f"  lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 1000\n"
        f"{_events(rows, names)}\n  }}" for i, (ln, rows) in enumerate(lines))
    meta = "\n".join(
        f"  event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
        f"name: \"{n}\" }} }}" for i, n in enumerate(names))
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}\n{meta}\n}}"


def by_hand():
    """Window [2, 12) ms. Device operations (start, length in ms): a 1.0+1.2
    (clipped to [2, 2.2)), b 2.5+1.0, c 3.4+1.1 (overlaps b), d 6+0.5.
    Busy = 0.2 + [2.5, 4.5) + 0.5 = 2.7 ms. Gaps: [2.2, 2.5) under no host
    span; [4.5, 6) of which wf:prep:win covers [4.5, 5); [6.5, 12) of
    which wf:commit:win covers [7, 8)."""
    host = _plane(1, "/host:CPU", [
        ("python3", [(trace.WINDOW_SPAN, 2.0, 10.0)]),
        ("worker", [("wf:prep:win", 3.0, 2.0), ("wf:commit:win", 7.0, 1.0),
                    ("not a program span", 2.0, 9.0)])])
    dev = _plane(2, "/device:TPU:0", [
        (trace.MODULES_LINE, [("jit_step(111)", 1.0, 3.5),
                              ("jit_fire(7)", 6.0, 0.5)]),
        (trace.OPS_LINE, [("a", 1.0, 1.2), ("b", 2.5, 1.0), ("c", 3.4, 1.1),
                          ("d", 6.0, 0.5)])])
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(host + "\n" + dev + "\n")


def test_reduction_by_hand():
    r = trace.reduce_trace(by_hand())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(2.7e-3)
    assert dict(r["modules"]) == pytest.approx(
        {"jit_step": 2.5e-3, "jit_fire": 0.5e-3})
    assert r["modules"][0][0] == "jit_step"
    assert dict(r["ops"]) == pytest.approx(
        {"a": 0.2e-3, "b": 1.0e-3, "c": 1.1e-3, "d": 0.5e-3})
    assert [g[0] for g in r["idle_gaps"]] == [
        "wf:commit:win", "wf:prep:win", "unattributed"]
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"wf:commit:win": 5.5e-3, "wf:prep:win": 1.5e-3,
         "unattributed": 0.3e-3})
    assert trace.modules_seconds(r, "^jit_(step|fire)") == pytest.approx(3e-3)
    assert trace.modules_seconds(r, "^jit_run") == 0


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(_plane(2, "/device:TPU:0", [
        (trace.OPS_LINE, [("a", 1.0, 1.0)])]) + "\n")
    with pytest.raises(ValueError):
        trace.reduce_trace(pd)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".textproto"))
    if os.path.isdir(DATA) else [])
def test_recorded_trace(name):
    """Cut once, by hand, from --trace 1 runs on a v5e (PR 24; the
    ``kw10k`` one from a sliding-window cell the benchmark does not keep,
    the trace is still a trace): the first 20 ms of the traced window,
    every device event in them."""
    r = trace.reduce_trace(trace.load(os.path.join(DATA, name)))
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.02)
    assert 0 < r["busy_s"] <= r["window_s"]
    # one stream: the operations of a module lie inside it, so module time
    # covers the busy time, and exceeds it by no more than launch gaps
    mod_s = sum(s for _, s in r["modules"])
    assert r["busy_s"] <= mod_s * 1.001 and r["busy_s"] > 0.9 * mod_s
    assert any(n == "jit_step" for n, _ in r["modules"])
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    if r["busy_s"] < 0.9 * r["window_s"]:
        # launch gaps under MIN_GAP_S are left out; where the device waits
        # on the host they are a small part of the idle time
        assert idle >= (r["window_s"] - r["busy_s"]) * 0.8
