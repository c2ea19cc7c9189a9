"""The forest's level rebuild (``xla_rebuild_levels``: the one definition
the window step and the standalone rebuild program both trace), held to
a per-node numpy oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from windflow_tpu.tpu.ffat_tpu import xla_rebuild_levels


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


def _check_against_oracle(combine, vals, valid, F, jit=False):
    """Every internal node ``[1, F)`` of every field equals the oracle's
    (values compared where the node is valid); leaves and node 0 pass
    through untouched."""
    fn = xla_rebuild_levels(combine, F)
    trees, tvalid = (jax.jit(fn) if jit else fn)(
        {nm: jnp.asarray(v) for nm, v in vals.items()}, jnp.asarray(valid))
    exp, expv = _numpy_rebuild({nm: v.copy() for nm, v in vals.items()},
                               valid, combine)
    assert (np.asarray(tvalid)[:, 1:] == expv[:, 1:]).all()
    live = expv[:, 1:]
    for nm, before in vals.items():
        got = np.asarray(trees[nm])
        assert (got[:, 1:][live] == exp[nm][:, 1:][live]).all()
        assert (got[:, F:] == before[:, F:]).all()
        assert (got[:, 0] == before[:, 0]).all()


@pytest.mark.parametrize("F,K", [(8, 8), (32, 16), (64, 8), (8, 4),
                                 (128, 8)])
def test_forest_rebuild_matches_oracle(F, K):
    rng = np.random.default_rng(F * K)
    leaves = rng.integers(0, 100, (K, 2 * F)).astype(np.int32)
    valid = np.zeros((K, 2 * F), dtype=bool)
    valid[:, F:] = rng.random((K, F)) < 0.7
    leaves[:, :F] = -999  # stale internals must be fully recomputed
    _check_against_oracle(lambda a, b: {"v": a["v"] + b["v"]},
                          {"v": leaves}, valid, F)


@pytest.mark.parametrize("F,K", [(8, 64), (32, 256), (128, 16)])
def test_forest_rebuild_order_sensitive_stale_flags(F, K):
    """An order-sensitive combine over a forest whose INTERNAL validity
    flags and values are stale garbage (what ingest-only batches and ring
    growth leave behind): every internal node is recomputed from the
    leaves alone, operands left to right; under ``jit`` as the programs
    run it."""
    rng = np.random.default_rng(F + K)
    vals = rng.integers(-50, 50, (K, 2 * F)).astype(np.int32)
    valid = rng.random((K, 2 * F)) < 0.5  # stale internal flags too
    _check_against_oracle(lambda a, b: {"v": a["v"] * 3 - b["v"]},
                          {"v": vals}, valid, F, jit=True)


def test_forest_rebuild_multifield_noncommutative():
    """Two fields, an order-sensitive combine (concat-style encoding)."""
    combine = lambda a, b: {"x": a["x"] * 100 + b["x"], "y": a["y"] + b["y"]}
    F, K = 8, 8
    rng = np.random.default_rng(3)
    x = rng.integers(1, 9, (K, 2 * F)).astype(np.int32)  # jax x64 off
    y = rng.integers(0, 5, (K, 2 * F)).astype(np.int32)
    valid = np.zeros((K, 2 * F), dtype=bool)
    valid[:, F:] = True
    _check_against_oracle(combine, {"x": x, "y": y}, valid, F)
