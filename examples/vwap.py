"""The README quickstart, runnable: per-symbol VWAP over 1s windows
sliding by 250ms, computed on the device plane from columnar ticks.

Run: JAX_PLATFORMS=cpu python examples/vwap.py [n_ticks]
(on a machine with a TPU, leave JAX_PLATFORMS unset)
"""

from __future__ import annotations

import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

N_SYMBOLS = 16
WIN_US, SLIDE_US = 1_000_000, 250_000
BATCH = 2048


def main(n_ticks: int = 200_000) -> None:
    def feed(shipper, ctx):
        rng = np.random.default_rng(42)
        ts0 = 0
        for start in range(0, n_ticks, BATCH):
            n = min(BATCH, n_ticks - start)
            ts = ts0 + np.arange(n, dtype=np.int64) * 500  # 2k ticks/sec
            ts0 = int(ts[-1]) + 500
            shipper.set_next_watermark(max(0, int(ts[0]) - 1))
            shipper.push_columns({
                "symbol": rng.integers(0, N_SYMBOLS, n).astype(np.int32),
                "px": (100 + rng.standard_normal(n)).astype(np.float32),
                "qty": rng.integers(1, 500, n).astype(np.int32),
            }, ts=ts)  # the wm set above rides with this push; the next
            # batch advances it (EOS flushes the tail windows)

    vwap = (Ffat_Windows_TPU_Builder(
                lambda f: {"pq": f["px"] * f["qty"].astype("float32"),
                           "q": f["qty"]},
                lambda a, b: {"pq": a["pq"] + b["pq"], "q": a["q"] + b["q"]})
            .with_key_by("symbol")
            .with_tb_windows(WIN_US, SLIDE_US)
            .with_key_capacity(N_SYMBOLS).build())

    results, lock = [], threading.Lock()

    def sink(w):
        if w is not None and w["valid"] and w["q"] > 0:
            with lock:
                results.append((w["symbol"], w["wid"], w["pq"] / w["q"]))

    graph = PipeGraph("vwap", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    graph.add_source(
        Source_Builder(feed).with_output_batch_size(BATCH).build()
    ).add(vwap).add_sink(Sink_Builder(sink).build())
    graph.run()

    assert results, "no windows fired"
    sample = sorted(results)[: 3]
    print(f"vwap: {n_ticks} ticks -> {len(results)} "
          f"(symbol, window) VWAPs; e.g. "
          + ", ".join(f"s{s} w{w}={v:.3f}" for s, w, v in sample))
    # sanity: every VWAP is near the price process mean
    vals = np.array([v for _, _, v in results])
    assert (np.abs(vals - 100) < 5).all(), (vals.min(), vals.max())


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 200_000)
