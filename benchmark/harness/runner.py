"""One run of one cell: set-up (stream, graph, warm-up), the measured
window, the drain, then — outside the window — the reductions and the
comparison with the configuration's plain reference.

One process, no child. The entry the window drives is the program's
``PipeGraph`` (``start`` / ``wait_end``) built by the configuration from
the public builders; what decides ``correct`` is what the sink received
from that same run."""

from __future__ import annotations

import math
import os
import shutil
import tempfile
import threading
import time

from . import stats as stats_mod
from . import trace as trace_mod
from .cell import BENCH_DIR, ROOT, Cell, load_module
from .sink import RecordingSink
from .traffic import EventClock, Offered, Pusher
from .windows import time_results_due

QUIET_S = 0.4            # no counter moved for this long: warm-up is done
QUIET_TIMEOUT_S = 1100.0  # a cell's first run compiles
REHEARSAL_TIMEOUT_S = 120.0
TRACE_S = 3.0            # traced part of the window,
TRACE_AT = 0.5           # from this share of it on


class Run:
    """State one run shares between the source thread, the main thread
    and the reductions."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 t_proc0: float):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.t_proc0 = t_proc0
        self.cfg, self.traffic = cell.cfg, cell.traffic
        self.clock = EventClock(self.cfg["batch_rows"], self.traffic)
        self.graph = self.roles = self.offered = self.sink = None
        self.window_started = threading.Event()
        self.t_start = self.t_source_end = None
        self.stats_start = self.stats_end = None
        self.source_error = None
        self.quiet_timeout_s = QUIET_TIMEOUT_S
        self.stream = None
        self.warm_due = None     # results the warm-up waited for
        self.mutate = None       # a test's fault on the window's blocks

    # -- set-up, in the source thread ---------------------------------
    def warm_results_due(self) -> int:
        """Results the warm-up's own watermarks close: of the rows of the
        configuration's reference over the warm-up blocks, those a correct
        system has delivered once the watermark stands at the newest one
        a warm-up block carried, the stream still open. The
        configuration's ``results_due`` says which where its module has
        one; else the rule of a time-based window."""
        off = self.offered
        blocks = list(off.blocks())          # warm-up blocks only, so far
        table = self.cell.module.reference(iter(blocks), self.cfg,
                                           self.stream, off.last_ts)
        due = getattr(self.cell.module, "results_due", time_results_due)
        return int(due(table, blocks, self.cfg, self.stream,
                       int(blocks[-1][1][0]) - 1))

    def wait_quiet(self, pushed_rows: int) -> None:
        """Warm-up is done when the first operator has taken every
        warm-up row, the sink holds every result the warm-up's watermarks
        close (so each program variant has run, and a compile, during
        which no counter moves, is not mistaken for quiet), and no
        counter has moved for ``QUIET_S``."""
        due = self.warm_due = self.warm_results_due()
        deadline = time.perf_counter() + self.quiet_timeout_s
        last, since = None, time.perf_counter()
        while time.perf_counter() < deadline:
            snap = stats_mod.snapshot(self.graph)
            first = stats_mod.find(snap, self.roles["first"])
            sig = (self.sink.n_calls,
                   sum(t.get("Inputs_received", 0) for t in snap.values()),
                   sum(t.get("Device_programs_run", 0)
                       for t in snap.values()))
            now = time.perf_counter()
            if sig != last:
                last, since = sig, now
            elif (first.get("Inputs_received", 0) >= pushed_rows
                  and self.sink.n_valid >= due
                  and now - since >= QUIET_S):
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"warm-up did not settle: {self.sink.n_valid} of {due} results")

    def mark_window_start(self) -> None:
        self.stats_start = stats_mod.snapshot(self.graph)
        self.t_start = time.perf_counter()
        self.window_started.set()

    def source(self, shipper, ctx=None) -> None:
        try:
            self._source(shipper)
        except BaseException as e:
            self.source_error = e
            self.window_started.set()
            raise
        finally:
            self.t_source_end = time.perf_counter()

    def _source(self, shipper) -> None:
        off, clock = self.offered, self.clock
        pusher = Pusher(shipper)
        for b in range(clock.warm_blocks):
            pusher.push(off.cols(b), clock.warm_ts(b))
            off.n_warm = b + 1
        self.wait_quiet(pusher.rows)
        # the measured window: the next block as soon as the system has
        # taken the last
        self.mark_window_start()
        pusher.mutate = self.mutate
        deadline = self.t_start + self.seconds
        b = 0
        while time.perf_counter() < deadline:
            pusher.push(off.cols(off.n_warm + b), clock.ts(b))
            b += 1
            off.n_window = b


def device_info(jax) -> dict:
    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_proc0: float, control: bool = False, faults: dict = None,
             log=print) -> dict:
    """Run the cell once and return the result object. ``faults`` is for
    the harness's own tests: ``{"sink": f(cols) -> cols | None,
    "source": f(cols, ts) -> (cols, ts)}`` break the timed path underneath
    the harness from the window's start on, and it must then report
    ``correct`` false."""
    import jax

    rehearse = cell.rehearse
    device = device_info(jax)
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] < cell.chips):
        raise SystemExit(
            f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"reports {device['count']} x {device['platform']}. There is no "
            "CPU fallback.")
    if rehearse and device["platform"] == "tpu":
        raise SystemExit("run.py: --rehearse-cpu is for the CPU backend")

    faults = faults or {}
    run = Run(cell, seed, seconds, t_proc0)
    if rehearse:
        run.quiet_timeout_s = REHEARSAL_TIMEOUT_S
    stream = cell.module.make_stream(seed, cell.cfg, cell.traffic)
    run.offered = Offered(stream["pool"], run.clock)
    run.stream = stream
    t_stream = time.perf_counter()
    sink = run.sink = RecordingSink(cell.cfg["result"])
    if "source" in faults:
        run.mutate = faults["source"]
    sink_fn = sink
    if "sink" in faults:
        def sink_fn(cols, ts, _f=faults["sink"]):
            if cols is not None and run.window_started.is_set():
                cols = _f(cols)
                if cols is None:
                    return
            sink(cols, ts)
    run.graph, run.roles = cell.module.build_graph(
        run.source, sink_fn, cell.cfg, stream)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        run.graph.with_compile_cache(os.path.join(ROOT, ".jax_cache"))

    trace_dir, reduced = None, None
    t_built = time.perf_counter()
    run.graph.start()
    try:
        run.window_started.wait()
        if trace and run.source_error is None:
            span = min(TRACE_S, run.seconds / 2)
            time.sleep(max(0.0, run.t_start + run.seconds * TRACE_AT
                           - time.perf_counter()))
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # the spans wanted are TraceMe's
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                time.sleep(span)
            jax.profiler.stop_trace()
    finally:
        run.graph.wait_end()
    t_drained = time.perf_counter()
    if run.source_error is not None:
        raise SystemExit(f"run.py: the source failed: {run.source_error!r}")
    run.stats_end = stats_mod.snapshot(run.graph)
    t_end = sink.last_delivery() or run.t_source_end
    for op, tot in run.stats_end.items():
        n = tot.get("Compile_count", 0) - run.stats_start.get(op, {}).get(
            "Compile_count", 0)
        if n:
            log(f"compiled in the window: {op} x{n}, last "
                f"{tot['_last_compile']}")
    log(f"phases: imports+stream {t_stream - t_proc0:.2f} s, build "
        f"{t_built - t_stream:.2f} s, start+warm-up "
        f"{run.t_start - t_built:.2f} s ({run.warm_due} results due), "
        f"offered "
        f"{run.t_source_end - run.t_start:.2f} s, last result "
        f"{t_end - run.t_source_end:+.2f} s, drained "
        f"{t_drained - run.t_source_end:+.2f} s after the source ended")
    device["memory_peak_bytes"] = memory_peak(jax)

    if trace_dir is not None:
        try:
            pd = trace_mod.load(trace_mod.find_xplane(trace_dir))
            reduced = trace_mod.reduce_trace(
                pd, "/device:TPU:" if not rehearse else "/device:")
            del pd
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the program's state is no longer needed ----------------------
    roles = run.roles
    win = stats_mod.StatsWindow(run.stats_start, run.stats_end, roles)
    run.graph = None

    from .reductions import Context, compared_numbers

    ctx = Context(run, win, t_end, reduced, device, stream)
    t_cmp = time.perf_counter()
    compared, cmp_counts = compared_numbers(ctx, control=control, log=log)
    log(f"phases: reference and comparison "
        f"{time.perf_counter() - t_cmp:.2f} s")
    correct = all(c["value"] <= c["limit"] for c in compared.values()
                  if c.get("decides", True))
    attempted = run.offered.n_window * run.clock.rows
    failed = int(min(attempted, ctx.dropped_or_shed
                     + math.ceil(cmp_counts["events_unanswered"]
                                 / ctx.windows_per_event)))
    suffix = ".cpu_rehearsal" if rehearse else ""
    metrics, readers = {}, {}
    for m, spec in cell.metrics("per_layer" if trace else "end_to_end"):
        name = spec["reader"]
        if name not in readers:
            readers[name] = load_module(
                os.path.join(BENCH_DIR, "metrics", name))
        v = readers[name].read(ctx, spec.get("params", {}))
        if v is not None:
            metrics[m["name"] + suffix] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["modules"][:10]
                               or reduced["ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["compared"] = compared
    return result
