"""Shared fixtures for the self-validating randomized test harness.

Mirrors the reference's test strategy (SURVEY.md §4; e.g.
``tests/graph_tests_gpu/test_graph_gpu_1.cpp:191-207``): run the same
topology several times with randomized operator parallelisms and batch
sizes; every run must produce the identical checksum. Sources carve the key
space per replica (disjoint keys per source replica) so per-key order — and
therefore running-state checksums — are parallelism-invariant.
"""

from __future__ import annotations

import random
import threading
from dataclasses import dataclass

import numpy as np


@dataclass
class TupleT:
    key: int
    value: int
    ts: int = 0  # event time (µs) when EVENT_TIME sources are used


class GlobalSum:
    """Sink-side accumulator (the reference's ``atomic<long> global_sum``)."""

    def __init__(self) -> None:
        self._v = 0
        self._n = 0
        self._lock = threading.Lock()

    def add(self, v: int) -> None:
        with self._lock:
            self._v += int(v)
            self._n += 1

    @property
    def value(self) -> int:
        with self._lock:
            return self._v

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    def reset(self) -> None:
        with self._lock:
            self._v = 0
            self._n = 0


def make_ingress_source(n_keys: int, stream_len: int):
    """Riched source: replica i generates the full sequence for keys
    ``k ≡ i (mod parallelism)`` — total stream invariant under parallelism."""

    def src(shipper, ctx):
        for k in range(ctx.get_replica_index(), n_keys, ctx.get_parallelism()):
            for i in range(stream_len):
                shipper.push(TupleT(key=k, value=i + 1))

    return src


def make_event_time_source(n_keys: int, stream_len: int, seed: int = 0,
                           max_step_us: int = 500, disorder_us: int = 0):
    """EVENT_TIME source with explicit timestamps + watermarks; random ts
    increments create realistic (bounded) disorder like
    ``graph_common_gpu.hpp:95-101``."""

    def src(shipper, ctx):
        rng = random.Random(seed + ctx.get_replica_index())
        ts = 0
        for i in range(stream_len):
            for k in range(ctx.get_replica_index(), n_keys,
                           ctx.get_parallelism()):
                jitter = rng.randint(0, disorder_us) if disorder_us else 0
                t = TupleT(key=k, value=i + 1, ts=ts + jitter)
                shipper.push_with_timestamp(t, t.ts)
            shipper.set_next_watermark(max(0, ts - disorder_us))
            ts += rng.randint(1, max_step_us)

    return src


def make_sum_sink(acc: GlobalSum):
    def sink(t):
        if t is not None:
            acc.add(t.value)

    return sink


def rand_degree(rng: random.Random, lo: int = 1, hi: int = 4) -> int:
    return rng.randint(lo, hi)


def rand_batch(rng: random.Random) -> int:
    return rng.choice([0, 0, 1, 4, 32])


def expected_windows(key_seqs, win, slide, win_type_cb, agg):
    """Model of the reference windowing semantics: per key, windows
    ``w`` cover index range [w*slide, w*slide+win) where the index is the
    arrival position (CB) or the timestamp (TB); a window exists once any
    index >= w*slide was seen. Returns {(key, wid): agg(values_in_window)}."""
    import math
    out = {}
    for key, seq in key_seqs.items():
        if not seq:
            continue
        idxs = [i if win_type_cb else ts for i, (v, ts) in enumerate(seq)]
        mx = max(idxs)
        if win >= slide:
            last_w = math.ceil((mx + 1) / slide) - 1
        else:
            last_w = mx // slide
        for w in range(last_w + 1):
            lo, hi = w * slide, w * slide + win
            vals = [v for (v, ts), idx in zip(seq, idxs) if lo <= idx < hi]
            out[(key, w)] = agg(vals)
    return out


class WinCollector:
    """Sink accumulator for WinResult streams: {(key, wid): value}."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.results = {}
        self.dups = 0

    def sink(self, r):
        if r is None:
            return
        with self._lock:
            k = (r.key, r.wid)
            if k in self.results:
                self.dups += 1
            self.results[k] = r.value


class DictWinCollector:
    """WinCollector for dict-shaped window rows ({key, wid, valid,
    value}): stores value (None when invalid), counts duplicates."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.results = {}
        self.dups = 0

    def sink(self, r):
        if r is None:
            return
        with self._lock:
            k = (r["key"], r["wid"])
            if k in self.results:
                self.dups += 1
            self.results[k] = r["value"] if r["valid"] else None


class ColumnRows:
    """Columnar sink that keeps every column of every call."""

    def __init__(self):
        self.calls, self.eos = [], 0

    def __call__(self, cols, ts):
        if cols is None:
            self.eos += 1
        else:
            self.calls.append({k: np.array(v) for k, v in cols.items()})

    def columns(self):
        return {k: np.concatenate([c[k] for c in self.calls])
                for k in self.calls[0]}


def run_benchmark_config(cell_name: str, blocks: int, seed: int, **config):
    """``blocks`` blocks of a benchmark cell at its rehearsal sizes (with
    ``config`` laid over) through the configuration's own graph, on the
    harness's own clock and pusher; the delivered columns, the cell, its
    stream, what was offered, the replicas' stats by operator and the graph. The
    caller has put ``benchmark/`` on ``sys.path``."""
    from harness.cell import Cell
    from harness.traffic import EventClock, Offered, Pusher

    cell = Cell(cell_name, rehearse=True)
    cell.cfg.update(config)
    stream = cell.module.make_stream(seed, cell.cfg, cell.traffic)
    clock = EventClock(cell.cfg["batch_rows"], cell.traffic)
    offered = Offered(stream["pool"], clock)

    def source(shipper, ctx=None):
        pusher = Pusher(shipper)
        for b in range(blocks):
            pusher.push(offered.cols(b), clock.warm_ts(b))
            offered.n_warm = b + 1

    out = ColumnRows()
    graph, roles = cell.module.build_graph(source, out, cell.cfg, stream)
    graph.run()
    stats = {o["name"]: o["replicas"][0]
             for o in graph.get_stats()["Operators"]}
    return {"cols": out.columns(), "cell": cell, "stream": stream,
            "offered": offered, "stats": stats, "roles": roles,
            "eos": out.eos, "graph": graph}
