"""Pallas forest-rebuild kernel, validated with the interpreter on CPU
(chip_smoke.py stage C compiles the same kernel with Mosaic on a TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from windflow_tpu.tpu.pallas_kernels import make_forest_rebuild


def _numpy_rebuild(vals, valid, combine):
    """Oracle: level-by-level rebuild with validity pass-through."""
    K, NN = valid.shape
    F = NN // 2
    out = {k: v.copy() for k, v in vals.items()}
    ov = valid.copy()
    lvl = F // 2
    while lvl >= 1:
        for k_row in range(K):
            for i in range(lvl, 2 * lvl):
                l, r = 2 * i, 2 * i + 1
                vl, vr = ov[k_row, l], ov[k_row, r]
                a = {nm: np.asarray(out[nm][k_row, l]) for nm in out}
                b = {nm: np.asarray(out[nm][k_row, r]) for nm in out}
                m = combine(a, b)
                for nm in out:
                    out[nm][k_row, i] = (m[nm] if (vl and vr)
                                         else (a[nm] if vl else b[nm]))
                ov[k_row, i] = vl or vr
        lvl //= 2
    return out, ov


# (8, 4): fewer keys than one 128-lane row packs; (128, 8): a tree wider
# than one row
@pytest.mark.parametrize("F,K", [(8, 8), (32, 16), (64, 8), (8, 4),
                                 (128, 8)])
def test_forest_rebuild_matches_oracle(F, K):
    combine = lambda a, b: {"v": a["v"] + b["v"]}
    rng = np.random.default_rng(F * K)
    leaves = rng.integers(0, 100, (K, 2 * F)).astype(np.int32)
    valid = np.zeros((K, 2 * F), dtype=bool)
    valid[:, F:] = rng.random((K, F)) < 0.7
    leaves[:, :F] = -999  # stale internals must be fully recomputed

    rebuild = make_forest_rebuild(combine, ["v"], F, interpret=True)
    trees, tvalid = rebuild({"v": jnp.asarray(leaves)}, jnp.asarray(valid))
    got_v, got_valid = np.asarray(trees["v"]), np.asarray(tvalid)

    exp, expv = _numpy_rebuild({"v": leaves.copy()}, valid, combine)
    assert (got_valid[:, 1:] == expv[:, 1:]).all()
    live = expv[:, 1:]
    assert (got_v[:, 1:][live] == exp["v"][:, 1:][live]).all()


@pytest.mark.parametrize("F,K", [(8, 64), (32, 256), (128, 16)])
def test_forest_rebuild_bit_equal_to_xla(F, K):
    """Every lane — node 0, stale internal nodes, invalid nodes' values,
    leaves — equals the XLA rebuild the operator otherwise runs."""
    from windflow_tpu.tpu.ffat_tpu import xla_rebuild_levels
    combine = lambda a, b: {"v": a["v"] * 3 - b["v"]}  # order-sensitive
    rng = np.random.default_rng(F + K)
    trees = {"v": jnp.asarray(
        rng.integers(-50, 50, (K, 2 * F)).astype(np.int32))}
    valid = rng.random((K, 2 * F)) < 0.5  # stale internal flags too
    got_t, got_v = make_forest_rebuild(combine, ["v"], F, interpret=True)(
        trees, jnp.asarray(valid))
    want_t, want_v = xla_rebuild_levels(combine, F)(trees, jnp.asarray(valid))
    assert (np.asarray(got_v) == np.asarray(want_v)).all()
    assert (np.asarray(got_t["v"]) == np.asarray(want_t["v"])).all()


def test_forest_rebuild_multifield_noncommutative():
    """Two fields, an order-sensitive combine (concat-style encoding)."""
    combine = lambda a, b: {"x": a["x"] * 100 + b["x"], "y": a["y"] + b["y"]}
    F, K = 8, 8
    rng = np.random.default_rng(3)
    x = rng.integers(1, 9, (K, 2 * F)).astype(np.int32)  # jax x64 off
    y = rng.integers(0, 5, (K, 2 * F)).astype(np.int32)
    valid = np.zeros((K, 2 * F), dtype=bool)
    valid[:, F:] = True

    rebuild = make_forest_rebuild(combine, ["x", "y"], F, interpret=True)
    trees, tvalid = rebuild({"x": jnp.asarray(x), "y": jnp.asarray(y)},
                            jnp.asarray(valid))
    exp, expv = _numpy_rebuild({"x": x.copy(), "y": y.copy()}, valid,
                               combine)
    assert (np.asarray(tvalid)[:, 1:] == expv[:, 1:]).all()
    assert (np.asarray(trees["x"])[:, 1:] == exp["x"][:, 1:]).all()
    assert (np.asarray(trees["y"])[:, 1:] == exp["y"][:, 1:]).all()


@pytest.mark.parametrize("host_seg", [True, False])
def test_ffat_with_pallas_rebuild_end_to_end(monkeypatch, host_seg):
    """WF_PALLAS=1 routes the forest rebuild through the kernel (interpreter
    off-TPU): a full FFAT pipeline must produce identical windows — in
    BOTH segmentation modes (host_seg=False is the real-TPU shape)."""
    import threading
    import windflow_tpu.tpu.ffat_tpu as ft
    if not host_seg:
        orig_init = ft.FfatTPUReplica.__init__

        def forced(self, op, idx):
            orig_init(self, op, idx)
            self._host_seg = False

        monkeypatch.setattr(ft.FfatTPUReplica, "__init__", forced)
    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

    def run_once():
        N, K = 24, 5
        graph = PipeGraph("pallas_ffat", ExecutionMode.DEFAULT,
                          TimePolicy.EVENT_TIME)

        def src(shipper, ctx):
            for p in range(N):
                shipper.set_next_watermark(p * 1000)
                shipper.push_columns(
                    {"key": np.arange(K, dtype=np.int32),
                     "value": np.full(K, p + 1, dtype=np.int32)},
                    ts=np.full(K, p * 1000 + 5, dtype=np.int64))
            shipper.set_next_watermark(N * 1000 + 4000)

        ffat = (Ffat_Windows_TPU_Builder(
                    lambda f: {"value": f["value"]},
                    lambda a, b: {"value": a["value"] + b["value"]})
                .with_tb_windows(4000, 1000)
                .with_key_by("key").with_key_capacity(K).build())
        res, lock = {}, threading.Lock()

        def sink(t):
            if t is not None and t["valid"]:
                with lock:
                    res[(t["key"], t["wid"])] = t["value"]

        graph.add_source(
            Source_Builder(src).with_output_batch_size(K).build()
        ).add(ffat).add_sink(Sink_Builder(sink).build())
        graph.run()
        return res

    monkeypatch.delenv("WF_PALLAS", raising=False)  # XLA-path baseline
    base = run_once()
    monkeypatch.setenv("WF_PALLAS", "1")
    with_pallas = run_once()
    assert with_pallas == base and len(base) >= 5 * 20
