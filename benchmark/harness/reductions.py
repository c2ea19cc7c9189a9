"""From what a run recorded to its numbers: the context every metric's
reader gets, and the numbers compared for ``correct``."""

from __future__ import annotations

import numpy as np

from .windows import (compare_results, table_rows,
                      time_windows_per_event)


class Context:
    """What a per-layer reader may read. Everything is of the measured
    window unless its name says otherwise."""

    def __init__(self, run, stats, t_end, trace, device, stream):
        self.cfg, self.traffic = run.cfg, run.traffic
        self.stats = stats                    # StatsWindow (counter deltas)
        self.trace = trace                    # reduce_trace() or None
        self.device = device
        self.stream = stream
        self.module = run.cell.module
        self.offered = run.offered
        self.clock = run.clock
        self.setup_s = run.t_start - run.t_proc0
        self.t_start, self.t_end = run.t_start, t_end
        self.window_s = t_end - run.t_start   # start -> last result
        self.offered_s = run.t_source_end - run.t_start
        self.events = run.offered.n_window * run.clock.rows
        self.blocks = run.offered.n_window
        # result rows one counted event contributes to (it sizes ``failed``)
        self.windows_per_event = int(getattr(
            self.module, "windows_per_event", time_windows_per_event)(
                run.cfg))
        self._run = run
        (self.at, self.key, self.wid, self.value,
         self.valid) = run.sink.columns()
        self.sink_calls = run.sink.n_calls
        self.dropped_or_shed = int(stats.total("Late_dropped")
                                   + stats.total("Shed_records"))

    # -- results of the window ----------------------------------------
    def fired_in_window(self) -> int:
        """Valid results delivered between the window's start and the end
        of the offered stream (the end-of-stream flush is not in it)."""
        m = self.valid.astype(bool) & (self.at >= self.t_start) & (
            self.at <= self._run.t_source_end)
        return int(m.sum())


def compared_numbers(ctx: Context, control: bool = False, log=print):
    """Each number compared, beside its limit. The limits are the
    configuration's (``limits`` in its file: its guarantees as numbers),
    and a cell's traffic file may state its own for a number."""
    off = ctx.offered
    expected = ctx.module.reference(off.blocks(), ctx.cfg, ctx.stream,
                                    off.last_ts)
    counts = compare_results(expected, ctx.key, ctx.wid, ctx.value,
                             ctx.valid)
    st = ctx.stats
    offered_total = (off.n_warm + off.n_window) * ctx.clock.rows
    taken = st.final("first", "Inputs_received")
    values = {
        "result_mismatches": counts["mismatches"],
        "events_unaccounted": (abs(int(offered_total - taken))
                               + ctx.dropped_or_shed),
        "late_records": int(st.total("Late_records")),
    }
    limits = {**ctx.cfg["limits"], **ctx.traffic.get("limits", {})}
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in values.items()}
    detail = {k: counts[k] for k in ("expected", "delivered", "missing",
                                     "duplicated", "wrong_value",
                                     "unexpected", "invalid_but_held")}
    log(f"compared detail: {detail}")
    if control:
        bad = ctx.module.reference(control_blocks(ctx), ctx.cfg, ctx.stream,
                                   off.last_ts)
        k, w, v = table_rows(bad)
        c = compare_results(expected, k, w, v, np.ones(len(k), bool))
        compared["control_result_mismatches"] = {
            "value": c["mismatches"], "limit": limits["result_mismatches"],
            "decides": False}
    return compared, counts


def control_blocks(ctx: Context):
    """The control: the reference in the program's place with one
    guarantee broken — one offered event that counts is lost (delivery),
    drawn from the seed among the window's blocks."""
    off = ctx.offered
    n = off.n_warm + off.n_window
    rng = np.random.default_rng(ctx._run.seed + 1)
    victim = int(rng.integers(off.n_warm, n))
    for i, (cols, ts) in enumerate(off.blocks()):
        if i == victim:
            rows = np.nonzero(ctx.module.counted_mask(cols, ctx.cfg))[0]
            keep = np.ones(len(ts), bool)
            keep[rows[int(rng.integers(len(rows)))]] = False
            cols, ts = {k: v[keep] for k, v in cols.items()}, ts[keep]
        yield cols, ts
