"""Key-partitioned FFAT scaling on one host: columnar sources feed a
keyed device windowing operator at parallelism N (the reference's
strategy 2 — KEYBY partitioning — applied to the flagship operator).

Run: JAX_PLATFORMS=cpu python examples/scaling.py [par] [batches]
(on a machine with a TPU, leave JAX_PLATFORMS unset)

Each source replica pushes whole numpy columns (`push_columns`, no
per-tuple Python); the keyed staging boundary partitions them by the
vectorized int-key router; each FFAT replica owns a key shard. Prints
tuples/s and fired windows/s. On one chip, replicas time-share the
device — the point here is exercising the multi-replica keyed path and
measuring the CPU-plane routing cost; across chips the same topology
maps onto `parallel.sharded_ffat_forest`.
"""

from __future__ import annotations

import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

N_KEYS = 1024
BATCH = 8192
WIN_US, SLIDE_US = 100_000, 25_000
TS_STEP = 50


def main(par: int = 2, n_batches: int = 48,
         columnar: bool = False) -> None:
    fired = [0]
    lock = threading.Lock()

    def make_src(seed: int):
        def src(shipper, ctx):
            rng = np.random.default_rng(seed)
            ts0 = 0
            for _ in range(n_batches):
                keys = rng.integers(0, N_KEYS, BATCH).astype(np.int32)
                vals = rng.integers(0, 100, BATCH).astype(np.int32)
                ts = ts0 + np.arange(BATCH, dtype=np.int64) * TS_STEP // 64
                ts0 = int(ts[-1]) + TS_STEP
                shipper.set_next_watermark(max(0, int(ts[0]) - 1))
                shipper.push_columns({"key": keys, "value": vals}, ts=ts)
                shipper.set_next_watermark(int(ts[-1]))
        return src

    def sink(t):
        if t is not None and t["valid"]:
            with lock:
                fired[0] += 1

    def col_sink(cols, ts):
        # the with_columns exit: one call per fired-window batch, no
        # per-row boxing — count valid windows vectorized
        if cols is not None:
            n = int(np.sum(cols["valid"]))
            with lock:
                fired[0] += n

    graph = PipeGraph("scaling", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    srcs = graph.add_source(
        Source_Builder(make_src(7)).with_output_batch_size(BATCH).build())
    ffat = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"]},
                lambda a, b: {"value": a["value"] + b["value"]})
            .with_tb_windows(WIN_US, SLIDE_US)
            .with_key_by("key").with_key_capacity(N_KEYS // par + 8)
            .with_parallelism(par).build())
    sink_b = (Sink_Builder(col_sink).with_columns() if columnar
              else Sink_Builder(sink))
    srcs.add(ffat).add_sink(sink_b.build())

    t0 = time.perf_counter()
    graph.run()
    dt = time.perf_counter() - t0
    n = n_batches * BATCH
    mode = "columnar-sink" if columnar else "row-sink"
    print(f"scaling[par={par},{mode}]: {n} tuples in {dt:.2f}s "
          f"({n / dt:,.0f} t/s), {fired[0]} windows "
          f"({fired[0] / dt:,.0f} win/s)")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 2,
         int(sys.argv[2]) if len(sys.argv) > 2 else 48,
         len(sys.argv) > 3 and sys.argv[3] == "columnar")
