"""Flight recorder: per-worker ring buffers of span events, Chrome/
Perfetto trace export, the watchdog (stalled workers under
``WF_STALL_SEC``; always, a gauge of the whole process: how late a thread
gets the interpreter, stalls with their cause, garbage-collection pauses)
and XLA compile attribution.

The stats plane (PR 2) answers "how fast is each operator on average";
this module answers "where did THIS slow batch spend its time" and "why
did throughput just collapse". Each worker thread owns one
``FlightRecorder`` — a fixed-size, single-writer ring of structured
events. The per-batch stages of the device plane reach it through the
one stage helper (``monitoring/tracing.py``: the same span, name and
batch id the profiler and the ``get_stats()`` counters get); barrier
alignment, checkpoint snapshots, jit compiles and the sampled ``svc:``
spans write it where they already take timestamps. The steady-state cost
of an enabled recorder is one clock read plus a couple of array stores
per event. The rings export as Chrome trace-event JSON (loadable in Perfetto /
``chrome://tracing``): ``tid`` = worker, ``pid`` = stage/operator,
``args`` carry batch sizes, checkpoint ids and compile signatures.

Three ways out of the ring:

- ``PipeGraph.dump_trace(path)`` — explicit dump any time;
- ``GET /trace?ms=N`` on ``MonitoringServer`` — an on-demand capture
  window over every registered in-process graph;
- automatic post-mortem — a worker that dies, or one the stall
  watchdog flags (no progress-counter advance for ``WF_STALL_SEC``),
  dumps its graph's rings plus ``sys._current_frames()`` stacks for
  every runtime thread into ``WF_LOG_DIR``.

Compile attribution: ``instrumented_jit`` wraps every ``jax.jit`` entry
point of the device plane (``tpu/ops_tpu.py`` / ``tpu/fused_ops.py``)
with an abstract-signature tracker — a call with an unseen
(shape, dtype) signature is a (re)trace and its elapsed time is the
compile cost; a seen signature is a cache hit. A retrace STORM (the
compile-cache churn that dominates fused-program cost when batch
signatures vary — Snider & Liang, arXiv:2301.13062) then shows up as a
wall of ``compile`` spans in the trace and a climbing
``windflow_compile_total`` in ``/metrics``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from .tracing import StageCounters, set_thread_recorder, thread_recorder

__all__ = ["FlightRecorder", "set_thread_recorder", "thread_recorder",
           "env_flightrec_events", "env_stall_sec", "instrumented_jit",
           "to_chrome_trace", "thread_stacks", "register_graph",
           "capture_trace", "StallWatchdog", "DEFAULT_EVENTS"]

DEFAULT_EVENTS = 4096

# threads record into their own ring only (single-writer contract);
# call sites that run on a foreign thread (a producer blocking on a
# consumer's channel, a shared compiled program) resolve the CURRENT
# thread's ring through ``thread_recorder`` (the slot lives in
# monitoring/tracing.py, re-exported here) instead of reaching for an
# owner's ring across threads


def env_flightrec_events() -> int:
    """Ring capacity from ``WF_FLIGHTREC_EVENTS`` (0/unset/malformed =
    recorder off — a bad knob must not take down the graph)."""
    try:
        return max(0, int(os.environ.get("WF_FLIGHTREC_EVENTS", "0")))
    except ValueError:
        return 0


def env_stall_sec() -> float:
    """Watchdog threshold from ``WF_STALL_SEC`` (seconds; 0/unset/
    malformed = watchdog off)."""
    try:
        return max(0.0, float(os.environ.get("WF_STALL_SEC", "0")))
    except ValueError:
        return 0.0


class FlightRecorder:
    """Fixed-size single-writer ring of ``(end_ns, name, dur_us, arg)``
    events. ``event()`` is the hot path: one clock read, one tuple, one
    slot store, one index bump — no locks, no allocation growth. The
    ring keeps the newest ``capacity`` events; wraparound drops
    oldest-first. Readers (watchdog/dump threads) take a racy snapshot:
    a torn read can at worst miss or double-see the event being written
    this instant, which trace export tolerates (events are re-sorted by
    timestamp)."""

    __slots__ = ("capacity", "pid_label", "tid_label", "_buf", "_n")

    def __init__(self, capacity: int = DEFAULT_EVENTS,
                 pid_label: str = "", tid_label: str = "") -> None:
        self.capacity = max(1, int(capacity))
        self.pid_label = pid_label
        self.tid_label = tid_label
        self._buf: List[Any] = [None] * self.capacity
        self._n = 0

    def event(self, name: str, dur_us: float = 0.0, arg: Any = None) -> None:
        """Record one span that ENDS now and lasted ``dur_us`` (0 for an
        instant event). Call sites pass durations they already measured
        for the stats plane, so no second clock base is needed."""
        i = self._n
        self._buf[i % self.capacity] = (time.perf_counter_ns(), name,
                                        dur_us, arg)
        self._n = i + 1

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def dropped(self) -> int:
        """Events lost to wraparound (oldest-first)."""
        return max(0, self._n - self.capacity)

    def snapshot(self) -> List[Any]:
        """Events oldest-first (racy vs the writer; see class doc)."""
        n = self._n
        buf = list(self._buf)  # one slice: consistent enough
        if n <= self.capacity:
            out = buf[:n]
        else:
            i = n % self.capacity
            out = buf[i:] + buf[:i]
        return [e for e in out if e is not None]


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------
def to_chrome_trace(recorders, stacks: Optional[Dict[str, Any]] = None,
                    extra: Optional[Dict[str, Any]] = None,
                    since_ns: Optional[int] = None) -> Dict[str, Any]:
    """Render rings as a Chrome trace-event JSON document (the object
    form: ``traceEvents`` plus arbitrary metadata keys, which Perfetto
    and ``chrome://tracing`` both load). Every span is a complete
    ``ph:"X"`` event; ``pid`` groups by stage/operator label and ``tid``
    by worker, with ``process_name``/``thread_name`` metadata events
    carrying the human labels. ``since_ns`` keeps only events ending at
    or after that ``perf_counter_ns`` instant (the /trace capture
    window)."""
    pids: Dict[str, int] = {}
    tids: Dict[str, int] = {}
    raw = []
    for rec in recorders:
        pid = pids.setdefault(rec.pid_label or "windflow", len(pids) + 1)
        tid = tids.setdefault(rec.tid_label or f"ring{pid}", len(tids) + 1)
        for ev in rec.snapshot():
            end_ns, name, dur_us, arg = ev
            if since_ns is not None and end_ns < since_ns:
                continue
            raw.append((end_ns, name, dur_us, arg, pid, tid))
    raw.sort(key=lambda e: e[0] - e[2] * 1e3)
    origin_ns = (raw[0][0] - raw[0][2] * 1e3) if raw else 0.0
    events: List[Dict[str, Any]] = []
    for label, pid in pids.items():
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
    for rec in recorders:
        pid = pids[rec.pid_label or "windflow"]
        tid = tids[rec.tid_label or f"ring{pid}"]
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": rec.tid_label}})
    for end_ns, name, dur_us, arg, pid, tid in raw:
        args = arg if isinstance(arg, dict) else (
            {} if arg is None else {"v": arg})
        events.append({"name": name, "ph": "X", "cat": "windflow",
                       "ts": round((end_ns - origin_ns) / 1e3 - dur_us, 3),
                       "dur": round(dur_us, 3), "pid": pid, "tid": tid,
                       "args": args})
    doc: Dict[str, Any] = {"traceEvents": events,
                           "displayTimeUnit": "ms"}
    dropped = sum(getattr(r, "dropped", 0) for r in recorders)
    if dropped:
        doc["droppedEvents"] = dropped
    if stacks is not None:
        doc["stacks"] = stacks
    if extra:
        doc.update(extra)
    return doc


def thread_stacks() -> Dict[str, List[str]]:
    """Formatted stacks for every runtime thread (the post-mortem's
    "where is everyone RIGHT NOW" section), keyed by thread name."""
    import sys
    import traceback

    names = {t.ident: t.name for t in threading.enumerate()}
    out: Dict[str, List[str]] = {}
    for ident, frame in sys._current_frames().items():
        out[names.get(ident, f"thread-{ident}")] = \
            traceback.format_stack(frame)
    return out


# ---------------------------------------------------------------------------
# in-process graph registry (feeds MonitoringServer's /trace endpoint)
# ---------------------------------------------------------------------------
_graphs: "weakref.WeakSet" = weakref.WeakSet()


def register_graph(graph) -> None:
    """Called by ``PipeGraph.start``; weak so finished graphs vanish
    with their last reference."""
    _graphs.add(graph)


def active_recorders() -> List[FlightRecorder]:
    recs: List[FlightRecorder] = []
    for g in list(_graphs):
        recs.extend(getattr(g, "_recorders", []))
    return recs


def capture_trace(window_ms: float) -> Dict[str, Any]:
    """The ``GET /trace?ms=N`` body: sleep one capture window, then
    export every registered graph's events that ended inside it."""
    window_ms = min(10_000.0, max(1.0, float(window_ms)))
    t0 = time.perf_counter_ns()
    time.sleep(window_ms / 1e3)
    return to_chrome_trace(active_recorders(), since_ns=t0,
                           extra={"captureWindowMs": window_ms})


# ---------------------------------------------------------------------------
# XLA compile attribution
# ---------------------------------------------------------------------------
def _abstract_signature(args) -> tuple:
    """Hashable abstract signature of a call: (shape, dtype) per array
    leaf, the type name for scalars. Matches jax.jit's retrace rule
    closely enough to attribute compiles: a new shape or dtype is a new
    signature (a dtype-change retrace is therefore counted), while
    value-only changes are cache hits."""
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            # ``str(dtype)`` is numpy formatting the name anew, some 10 us
            # a leaf, on every launch of every program
            name = _DTYPE_NAMES.get(dtype)
            if name is None:
                name = _DTYPE_NAMES[dtype] = str(dtype)
            parts.append((tuple(shape), name))
        else:
            parts.append(type(leaf).__name__)
    return tuple(parts)


_DTYPE_NAMES: Dict[Any, str] = {}


def instrumented_jit(fn, stats=None, label: str = "",
                     program: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with compile-vs-cache-hit attribution. ``program``
    names the XLA module (``jit_<program>`` in a device profile; default:
    the function's own name), ``label`` the operator in the compile
    signature and in the ``wf:launch:<label>`` stage that times the call
    of the jitted program on the cache-hit path (the Python-side program
    call; ``Device_launch_total_usec``). The wrapped
    callable tracks the abstract signatures it has served: an unseen
    signature means jit will trace+compile synchronously inside this
    call, so the call's elapsed time is recorded as the compile cost
    (``StatsRecord.note_compile`` -> ``Compile_*`` stats,
    ``windflow_compile_*`` metric families, and a ``compile`` span in
    the current thread's flight ring); a seen signature bumps the
    cache-hit counter only. Signature checks cost one small tree walk
    per batch — noise against the program the batch is about to run.

    Shared program caches (the grid scan, fused chains) attribute
    compiles to the stats record of the replica that built the program;
    compiles are per-program events, so counts stay exact even when
    sibling replicas hit the shared cache."""
    import jax

    if program:
        fn.__name__ = fn.__qualname__ = program
    jitted = jax.jit(fn, **jit_kwargs)
    seen = set()
    launch = (stats if stats is not None else StageCounters()).stage(
        "launch", label or getattr(fn, "__name__", "prog"))

    def wrapper(*args):
        key = _abstract_signature(args)
        if key in seen:
            if stats is not None:
                stats.compile_cache_hits += 1
            with launch():
                return jitted(*args)
        t0 = time.perf_counter()
        out = jitted(*args)
        dt_us = (time.perf_counter() - t0) * 1e6
        seen.add(key)
        sig = f"{label or getattr(fn, '__name__', 'prog')}:{key}"
        if stats is not None:
            stats.note_compile(dt_us, sig)
        rec = thread_recorder()
        if rec is not None:
            rec.event("compile", dt_us, {"op": label, "signature": sig})
        return out

    wrapper._seen_signatures = seen  # introspection / tests
    wrapper._wrapped_jit = jitted
    return wrapper


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------
# the process gauge: the watchdog sleeps TICK_NS at a time; a wake-up later
# than STALL_NS is a stall of the whole process
TICK_NS = 10_000_000
STALL_NS = 100_000_000


class StallWatchdog(threading.Thread):
    """One monitor thread a graph, started with it, with two jobs.

    **The workers** (``WF_STALL_SEC`` > 0, default off): flags live
    workers whose progress counter (channel deliveries + idle ticks +
    tuples moved) has not advanced for ``stall_sec``. Firing calls
    ``dump_fn(worker_name)`` once per stall episode (re-armed by any
    later progress) — the PipeGraph wires that to a post-mortem trace
    dump with ``sys._current_frames()`` stacks. Off by default because a
    healthy-idle worker parked in a long ``channel.get`` would otherwise
    look identical to a deadlocked one, which is why workers run their
    idle tick whenever it is armed.

    **The process** (always): the thread sleeps 10 ms at a time and
    measures how late each wake-up is. The lateness is the time a thread
    that wants the interpreter waits for it (over the timer's own slack,
    the reading of an idle graph); a wake-up later than 100 ms is a stall
    of the whole process, counted with the process's CPU time across the
    gap (near 0: the host took the process off the CPU; near the gap: a
    thread kept the interpreter) and the garbage collector's pauses in
    it, written to stderr and, as ``stall:process``, to the flight ring.
    ``gc.callbacks`` times every collection while the thread runs (a
    pause stops every Python thread). ``process_fields`` is what
    ``get_stats()`` shows, on one record of the graph."""

    def __init__(self, graph, stall_sec: float = 0.0, dump_fn=None) -> None:
        super().__init__(name=f"stallwatch:{graph.name}", daemon=True)
        self.graph = graph
        self.stall_sec = float(stall_sec)
        self.dump_fn = dump_fn
        self.fired: List[str] = []  # worker names, in firing order
        self._stopping = False
        self._seen: Dict[str, Any] = {}  # wname -> [progress, t, flagged]
        # -- the process gauge (nanoseconds; only this thread writes the
        # tick counters, only the collecting thread the gc pair) -----------
        self.ticks = 0
        self.tick_late_ns = 0
        self.stalls = 0
        self.stall_ns = 0
        self.stall_cpu_ns = 0
        self.gc_pause_ns = 0
        self.gc_full = 0
        self._gc_t0 = 0
        # (clock, process CPU, gc pause) when the thread last went to sleep
        self._asleep: Optional[tuple] = None
        self._t_run = time.perf_counter_ns()

    def stop(self) -> None:
        """Ends the thread within a tick; joined where it was started."""
        self._stopping = True
        if self.is_alive() and threading.current_thread() is not self:
            self.join(timeout=1.0)

    def run(self) -> None:
        import gc

        self._t_run = time.perf_counter_ns()
        check_every = min(1.0, max(0.05, self.stall_sec / 4.0))
        next_check = time.monotonic() + check_every
        gc.callbacks.append(self._on_gc)
        try:
            while not self._stopping:
                # armed anew before each sleep: what the loop does between
                # two of them is not lateness
                self._asleep = (time.perf_counter_ns(),
                                time.process_time_ns(), self.gc_pause_ns)
                time.sleep(TICK_NS / 1e9)
                stall = self._tick(time.perf_counter_ns(),
                                   time.process_time_ns(), self.gc_pause_ns)
                if stall is not None:
                    self._report(stall)
                now = time.monotonic()
                if now >= next_check:
                    next_check = now + check_every
                    if self.stall_sec > 0:
                        self._check(now)
                    if not self._graph_lives():
                        return  # an abandoned graph: nothing left to watch
        finally:
            gc.callbacks.remove(self._on_gc)

    # -- the process ---------------------------------------------------------
    def _tick(self, now_ns: int, cpu_ns: int,
              gc_ns: int) -> Optional[Dict[str, float]]:
        """One wake-up, as a pure function of the three clocks now and
        when the thread went to sleep (``_asleep``; the first reading
        only arms it): counts a tick, its lateness over ``TICK_NS`` and,
        past ``STALL_NS``, a stall, which it returns with its cause
        (microseconds late, of process CPU and of gc pause in the gap)."""
        before, self._asleep = self._asleep, (now_ns, cpu_ns, gc_ns)
        if before is None:
            return None
        late = max(0, now_ns - before[0] - TICK_NS)
        self.ticks += 1
        self.tick_late_ns += late
        if late < STALL_NS:
            return None
        self.stalls += 1
        self.stall_ns += late
        self.stall_cpu_ns += cpu_ns - before[1]
        return {"late_us": late / 1e3, "cpu_us": (cpu_ns - before[1]) / 1e3,
                "gc_us": (gc_ns - before[2]) / 1e3}

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0:
            self.gc_pause_ns += time.perf_counter_ns() - self._gc_t0
            self._gc_t0 = 0
            if info.get("generation") == 2:
                self.gc_full += 1

    def _report(self, stall: Dict[str, float]) -> None:
        import sys

        print(f"[windflow] {self.graph.name}: the process stood still "
              f"{stall['late_us'] / 1e3:.0f} ms, "
              f"{(time.perf_counter_ns() - self._t_run) / 1e9:.1f} s after "
              f"the graph started (process CPU in the gap "
              f"{stall['cpu_us'] / 1e3:.0f} ms, gc "
              f"{stall['gc_us'] / 1e3:.0f} ms)", file=sys.stderr)
        for rec in getattr(self.graph, "_recorders", ())[:1]:
            rec_evt_safe(rec, "stall:process", stall["late_us"], stall)

    def process_fields(self) -> Dict[str, Any]:
        """The ``Process_*`` / ``Gc_*`` fields of ``get_stats()``."""
        return {
            "Process_ticks": self.ticks,
            "Process_tick_late_total_usec": round(self.tick_late_ns / 1e3, 1),
            "Process_stalls": self.stalls,
            "Process_stall_usec": round(self.stall_ns / 1e3, 1),
            "Process_stall_cpu_usec": round(self.stall_cpu_ns / 1e3, 1),
            "Gc_pause_total_usec": round(self.gc_pause_ns / 1e3, 1),
            "Gc_collections_full": self.gc_full,
        }

    def _graph_lives(self) -> bool:
        """False for a graph whose workers have all ended and that nobody
        will restart: one that never reached ``wait_end`` must not keep a
        thread ticking (a supervised graph is always waited for)."""
        g = self.graph
        return (getattr(g, "_rescaling", False)
                or getattr(g, "_supervising", False)
                or getattr(g, "_supervisor", None) is not None
                or any(w.is_alive() for w in g._workers))

    # -- the workers ---------------------------------------------------------
    def _check(self, now: float) -> None:
        if getattr(self.graph, "_rescaling", False) \
                or getattr(self.graph, "_supervising", False):
            # a rescale parks every worker at the barrier on purpose (and
            # a supervised recovery tears the plane down mid-flight);
            # re-arm from scratch once the new plane is running
            self._seen.clear()
            return
        gov = getattr(self.graph, "_overload_governor", None)
        if gov is not None and gov.shedding:
            # active load shedding: a fully gated source emits nothing
            # BY DESIGN (and its downstream can legitimately go quiet) —
            # flagging that as a stall would dump postmortems during
            # every overload; re-arm once admission control releases
            self._seen.clear()
            return
        for w in self.graph._workers:
            if not w.is_alive():
                self._seen.pop(w.name, None)
                continue
            cur = w.progress_value()
            ent = self._seen.get(w.name)
            if ent is None or ent[0] != cur:
                self._seen[w.name] = [cur, now, False]
                continue
            if not ent[2] and now - ent[1] >= self.stall_sec:
                ent[2] = True  # one dump per stall episode
                self.fired.append(w.name)
                rec = getattr(w, "flightrec", None)
                if rec is not None:
                    rec_evt_safe(rec, "stall_detected",
                                 (now - ent[1]) * 1e6, w.name)
                if self.dump_fn is not None:
                    try:
                        self.dump_fn(w.name)
                    except Exception:
                        pass  # a dump failure must not kill the watchdog


def rec_evt_safe(rec: FlightRecorder, name: str, dur_us: float,
                 arg: Any) -> None:
    """Cross-thread event append (watchdog only): the stall marker is
    worth the single racy slot write — at worst it overwrites the event
    the stalled worker is NOT writing (it is stalled)."""
    try:
        rec.event(name, dur_us, arg)
    except Exception:
        pass


def write_trace(path: str, recorders, stacks=None, extra=None) -> str:
    """Serialize ``to_chrome_trace`` to ``path`` (dirs created)."""
    doc = to_chrome_trace(recorders, stacks=stacks, extra=extra)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path
