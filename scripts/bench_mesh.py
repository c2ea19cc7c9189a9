#!/usr/bin/env python
"""Mesh-plane benchmark: the mesh execution plane's operator paths.

Three measurements, one protocol (drive the host replica directly with
pre-staged keyed batches — staging excluded, same as bench.py's
single-chip measurement):

- ``mesh_ffat_tuples_per_sec``  — Ffat_Windows_Mesh: all_to_all keyby
  over the mesh, segmented leaf combine, level rebuild, device-side
  fire rounds, columnar exit (the round-4 metric, unchanged);
- ``sharded_scan``   — Map_Mesh (stateful grid scan): flat-owner
  all_to_all shuffle, (k_local x M) per-key scan, inverse shuffle back
  to arrival order;
- ``sharded_reduce`` — Reduce_Mesh (keyed per-batch reduce): shuffle +
  segmented combine + per-slot harvest.

Its own command: one process owning all chips of the host (bench.py
starts no child). It uses however many chips ``jax.devices()`` gives and
exits non-zero when they are not TPUs. The exception is a caller that
set ``JAX_PLATFORMS=cpu``: the run then forces the virtual 8-device CPU
mesh the test suite uses (``windflow_tpu.mesh.ensure_virtual_devices``)
and every metric name ends in `` (cpu)``. Prints ONE JSON line:
tuples/s, windows/s, shuffle bytes/s, mesh shape, platform, device_kind.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from windflow_tpu.mesh import ensure_virtual_devices  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    ensure_virtual_devices()

N_KEYS = 64
BATCH = 16384
N_BATCHES = 12
WARMUP = 3
REPEATS = int(os.environ.get("WF_BENCH_REPEATS", "5"))
WIN_US = 100_000
SLIDE_US = 25_000
TS_STEP = 50  # aggregate stream-time µs per tuple across all keys


def _mk_batches(schema, n, value_field="value"):
    import numpy as np

    from windflow_tpu.tpu.batch import BatchTPU

    rng = np.random.default_rng(0)
    batches = []
    ts0 = 0
    for _ in range(n):
        keys = rng.integers(0, N_KEYS, BATCH)
        ts = ts0 + np.arange(BATCH, dtype=np.int64) * TS_STEP // N_KEYS
        ts0 = int(ts[-1]) + TS_STEP
        b = BatchTPU(
            {"key": keys.astype(np.int32),
             value_field: rng.random(BATCH).astype(np.float32)},
            ts, BATCH, schema, wm=max(0, int(ts[0]) - 1000),
            host_keys=keys)
        b.wm = int(ts[-1])
        batches.append(b)
    return batches


def _drive(rep, batches, state_leaf):
    """(tuples/s chunks, total shuffle bytes) over REPEATS chunks of
    N_BATCHES batches each — bench.py's chunk protocol."""
    import jax

    import bench  # counting sink + chunk aggregation: ONE protocol

    sink = bench._CountingEmitter()
    rep.emitter = sink
    for b in batches[:WARMUP]:
        rep.handle_msg(0, b)
    rep.dispatch.drain()
    jax.block_until_ready(state_leaf())
    chunks = []
    for r in range(REPEATS):
        lo = WARMUP + r * N_BATCHES
        w0 = sink.windows
        t0 = time.perf_counter()
        for b in batches[lo:lo + N_BATCHES]:
            rep.handle_msg(0, b)
        rep.dispatch.drain()
        jax.block_until_ready(state_leaf())
        el = time.perf_counter() - t0
        chunks.append((N_BATCHES * BATCH / el, (sink.windows - w0) / el))
    return chunks, sink


def main() -> None:
    import numpy as np

    import bench
    from windflow_tpu.basic import WinType
    from windflow_tpu.mesh.ffat_mesh import Ffat_Windows_Mesh
    from windflow_tpu.mesh.ops_mesh import Map_Mesh, Reduce_Mesh
    from windflow_tpu.tpu.schema import TupleSchema

    devices = bench.chip_devices("bench_mesh")
    platform = devices[0].platform
    n_dev = len(devices)
    schema = TupleSchema({"key": np.int32, "value": np.float32})
    n_total = REPEATS * N_BATCHES + WARMUP

    # ---- flagship: the sharded FFAT forest (round-4 metric) ----------
    op = Ffat_Windows_Mesh(
        lift=lambda f: {"value": f["value"]},
        combine=lambda a, b: {"value": a["value"] + b["value"]},
        key_extractor="key", win_len=WIN_US, slide_len=SLIDE_US,
        win_type=WinType.TB, key_capacity=N_KEYS, n_devices=n_dev,
        name="bench_mesh")
    op.build_replicas()
    rep = op.replicas[0]
    chunks, _ = _drive(rep, _mk_batches(schema, n_total),
                       lambda: rep._state[0])
    st = bench._chunk_stats(chunks)
    result = {
        "metric": "mesh_ffat_tuples_per_sec"
                  + ("" if platform == "tpu" else f" ({platform})"),
        "value": round(st["mean"], 1),
        "unit": "tuples/sec",
        "value_min": round(st["min"], 1),
        "value_best": round(st["best"], 1),
        "windows_per_sec": round(st["wps_mean"], 1),
        "mesh_shape": dict(rep._mesh.shape),
        "global_batch": rep._GB,
        "device_programs": rep.stats.device_programs_run,
        "shuffle_bytes_total": rep.stats.mesh_shuffle_bytes,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": n_dev,
        "throughput_aggregation": f"mean-of-{REPEATS}-chunks",
    }

    # ---- sharded stateful map (grid-scan key table over the mesh) ----
    mop = Map_Mesh(
        lambda row, s: ({"key": row["key"],
                         "value": s + row["value"]}, s + row["value"]),
        np.float32(0), "key", name="bench_mesh_scan",
        key_capacity=N_KEYS, n_devices=n_dev)
    mop.build_replicas()
    mrep = mop.replicas[0]
    chunks, _ = _drive(mrep, _mk_batches(schema, n_total),
                       lambda: mrep._table)
    ms = bench._chunk_stats(chunks)
    result["sharded_scan"] = {
        "tuples_per_sec": round(ms["mean"], 1),
        "tuples_per_sec_best": round(ms["best"], 1),
        "shuffle_bytes_total": mrep.stats.mesh_shuffle_bytes,
        "shuffle_bytes_per_sec": round(
            mrep.stats.mesh_shuffle_bytes
            / max(mrep.stats.mesh_step_total_us, 1) * 1e6, 1),
        "steps": mrep.stats.mesh_steps,
        "global_batch": mrep._GB,
    }

    # ---- sharded keyed reduce ----------------------------------------
    rop = Reduce_Mesh(
        lambda a, b: {"value": a["value"] + b["value"]}, "key",
        name="bench_mesh_reduce", key_capacity=N_KEYS, n_devices=n_dev)
    rop.build_replicas()
    rrep = rop.replicas[0]

    def reduce_ready():
        return rrep._gpos_dev if rrep._gpos_dev is not None else 0
    chunks, rsink = _drive(rrep, _mk_batches(schema, n_total),
                           reduce_ready)
    rs = bench._chunk_stats(chunks)
    result["sharded_reduce"] = {
        "tuples_per_sec": round(rs["mean"], 1),
        "tuples_per_sec_best": round(rs["best"], 1),
        "outputs_per_sec": round(rs["wps_mean"], 1),
        "shuffle_bytes_total": rrep.stats.mesh_shuffle_bytes,
        "steps": rrep.stats.mesh_steps,
        "global_batch": rrep._GB,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
