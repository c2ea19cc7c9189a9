"""The one traffic generator: how ids are drawn, the event-time rule, the
block sequence, how a block is pushed, and how blocks are offered. A
traffic mix is a data file (``workloads/<cell>.json``); nothing here knows
a cell by name.

Event time. Warm-up block ``b`` starts at ``b * (span + block_gap_us)``;
after warm-up, event ``i`` (counted from the end of warm-up) carries
``T0 + (i * 10**6) // nominal_rate`` µs, ``nominal_rate`` being the rate the
cell sustains, written into its file once, so that event time runs near
wall time and windows hold what a deployment's would. Event time never
steps back.

Blocks are offered at the pace of backpressure: the next as soon as the
system has taken the last. An open-loop generator (blocks due on a fixed
schedule, event time their due time, result latency at the sink) ran in
PR 24 and went out with the cells that used it; PERF.md section 7 says what
it needs to come back.
"""

from __future__ import annotations

import numpy as np


def draw_ids(rng, n: int, rows: int, dist: dict) -> np.ndarray:
    """``rows`` ids in [0, n) by the distribution a configuration or a
    traffic file names: ``{"distribution": "uniform"}`` or ``{"distribution":
    "zipf", "s": 1.1}`` (rank r drawn with weight 1 / r**s, the ranks dealt
    to ids by a permutation from the same generator)."""
    kind = dist["distribution"]
    if kind == "uniform":
        return rng.integers(0, n, rows)
    if kind == "zipf":
        p = 1.0 / np.arange(1, n + 1) ** float(dist["s"])
        return rng.permutation(n)[rng.choice(n, rows, p=p / p.sum())]
    raise ValueError(f"no id distribution {kind!r}")


class EventClock:
    """Event time of every block, from the traffic file alone."""

    def __init__(self, rows: int, traffic: dict):
        self.rows = rows
        self.rate = int(traffic["nominal_rate"])
        warm = traffic["warmup"]
        self.warm_blocks = int(warm["blocks"])
        self.span = (rows * 10**6) // self.rate
        self.warm_stride = self.span + int(warm.get("block_gap_us", 0))
        self.t0 = self.warm_blocks * self.warm_stride
        self._idx = np.arange(rows, dtype=np.int64)
        self._offsets = (self._idx * 10**6) // self.rate

    def warm_ts(self, b: int) -> np.ndarray:
        return b * self.warm_stride + self._offsets

    def ts(self, b: int) -> np.ndarray:
        """Timestamps of post-warm-up block ``b``."""
        i0 = b * self.rows
        return self.t0 + ((i0 + self._idx) * 10**6) // self.rate


class Offered:
    """What was offered, in order: enough to rebuild every event after the
    run (pool index and timestamps follow from the block number)."""

    def __init__(self, pool, clock: EventClock):
        self.pool, self.clock = pool, clock
        self.n_warm = 0        # warm-up blocks pushed
        self.n_window = 0      # blocks offered inside the window

    def cols(self, seq: int) -> dict:
        return self.pool[seq % len(self.pool)]

    def blocks(self):
        """Every offered block as ``(cols, ts)``, warm-up first."""
        for b in range(self.n_warm):
            yield self.cols(b), self.clock.warm_ts(b)
        for b in range(self.n_window):
            yield self.cols(self.n_warm + b), self.clock.ts(b)

    @property
    def last_ts(self) -> int:
        n = self.n_window
        return int(self.clock.ts(n - 1)[-1] if n
                   else self.clock.warm_ts(self.n_warm - 1)[-1])


class Pusher:
    """Pushes one block the way ``chip_smoke.block_source`` does: the
    watermark just below the block's first timestamp before the push (no
    row is late), its last timestamp after."""

    def __init__(self, shipper, mutate=None):
        self.shipper, self.wm, self.mutate = shipper, 0, mutate
        self.rows = 0            # rows pushed so far

    def push(self, cols, ts) -> None:
        if self.mutate is not None:
            cols, ts = self.mutate(cols, ts)
        self.wm = max(self.wm, int(ts[0]) - 1)
        self.shipper.set_next_watermark(self.wm)
        self.shipper.push_columns(cols, ts=ts)
        self.rows += len(ts)
        self.wm = max(self.wm, int(ts[-1]))
        self.shipper.set_next_watermark(self.wm)
