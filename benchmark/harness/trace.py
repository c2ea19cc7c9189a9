"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

Reads with ``jax.profiler.ProfileData`` and nothing else. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per executed operation and ``XLA Modules`` one per executed program; host
threads are lines of ``/host:CPU``, where the program's
``TraceAnnotation`` spans (``wf:prep:<op>``, ``wf:commit:<op>``) and the
harness's own ``bench:window`` land on the same clock. The traced window
is the ``bench:window`` span; device events are clipped to it.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench:window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_S = 20e-6      # shorter gaps are launch spacing, not idleness


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _intervals(line, lo: float, hi: float):
    """(name, start, end) in seconds, clipped to [lo, hi]."""
    out = []
    for e in line.events:
        s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
        s, t = max(s, lo), min(t, hi)
        if t > s:
            out.append((e.name, s, t))
    return out


def _union(iv):
    """Merged (start, end) list of intervals sorted by start."""
    merged = []
    for _, s, t in sorted(iv, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            if t > merged[-1][1]:
                merged[-1][1] = t
        else:
            merged.append([s, t])
    return merged


def module_name(event_name: str) -> str:
    """``jit_step(1234567890)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_trace(pd, device_prefix: str = "/device:TPU:") -> dict:
    """``window_s``, ``busy_s`` (mean over chips), device seconds per XLA
    module and per operation, and idle gaps by the host span that overlaps
    each most. Raises when the trace holds no window span; returns
    ``busy_s`` 0 when no operation ran on a device."""
    host_spans, window = [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                    elif e.name.startswith("wf:"):
                        host_spans.append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    host_spans.sort(key=lambda x: x[1])
    busy, modules, ops, gaps, n_dev = 0.0, {}, {}, {}, 0
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines and MODULES_LINE not in lines:
            continue
        n_dev += 1
        op_iv = _intervals(lines.get(OPS_LINE) or lines[MODULES_LINE], lo, hi)
        merged = _union(op_iv)
        busy += sum(t - s for s, t in merged)
        for name, s, t in op_iv:
            ops[name] = ops.get(name, 0.0) + (t - s)
        if MODULES_LINE in lines:
            for name, s, t in _intervals(lines[MODULES_LINE], lo, hi):
                m = module_name(name)
                modules[m] = modules.get(m, 0.0) + (t - s)
        edges = [lo] + [x for st in merged for x in st] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs >= MIN_GAP_S:
                who = _dominant_span(host_spans, gs, ge)
                gaps[who] = gaps.get(who, 0.0) + (ge - gs)
    n = max(n_dev, 1)
    top = lambda d: sorted(([k, v / n] for k, v in d.items()),
                           key=lambda kv: -kv[1])
    return {"window_s": hi - lo, "busy_s": busy / n, "devices": n_dev,
            "modules": top(modules), "ops": top(ops), "idle_gaps": top(gaps)}


def _dominant_span(spans, gs: float, ge: float) -> str:
    best, best_overlap = "unattributed", 0.0
    for name, s, t in spans:
        if s >= ge:
            break
        o = min(t, ge) - max(s, gs)
        if o > best_overlap:
            best, best_overlap = name, o
    return best


def modules_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the XLA modules whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(sec for name, sec in reduced["modules"] if rx.search(name))
