"""The forest's level rebuilds, held to a per-node numpy oracle: the full
rebuild (``xla_rebuild_levels``: the one definition the window step and
the standalone rebuild program both trace) and the partial one a
time-based step takes over the dirty ring ranges its plan carries
(``rebuild_levels_by_ranges``, chosen by ``rebuild_fits``), which must
leave the forest node for node as the full rebuild does. The programs
keep the forest node-major, ``(2F, K_cap)``; the oracle goes by key
row, ``(K_cap, 2F)``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from windflow_tpu.tpu.ffat_tpu import (REBUILD_W, rebuild_fits,
                                       rebuild_levels_by_ranges,
                                       rebuilds_by_ranges,
                                       xla_rebuild_levels)


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


def _node_major(vals, valid):
    return ({nm: jnp.asarray(v.T) for nm, v in vals.items()},
            jnp.asarray(valid.T))


def _by_row(trees, tvalid):
    return ({nm: np.asarray(t).T for nm, t in trees.items()},
            np.asarray(tvalid).T)


def _check_against_oracle(combine, vals, valid, F, jit=False):
    """Every internal node ``[1, F)`` of every field equals the oracle's
    (values compared where the node is valid); leaves and node 0 pass
    through untouched."""
    fn = xla_rebuild_levels(combine, F)
    trees, tvalid = _by_row(*(jax.jit(fn) if jit else fn)(
        *_node_major(vals, valid)))
    exp, expv = _numpy_rebuild({nm: v.copy() for nm, v in vals.items()},
                               valid, combine)
    assert (tvalid[:, 1:] == expv[:, 1:]).all()
    live = expv[:, 1:]
    for nm, before in vals.items():
        got = trees[nm]
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


# ---------------------------------------------------------------------------
# the partial rebuild: the ancestors of the dirty ring ranges
# ---------------------------------------------------------------------------
ADD = lambda a, b: {"v": a["v"] + b["v"]}
SKEW = lambda a, b: {"x": a["x"] * 3 - b["x"], "y": a["y"] + b["y"]}

# name: (F, dirty ranges (start_phys, length), combine, share of the
# dirty leaves left valid, whether the ranges fit the window)
PARTIAL = {
    "one_range": (256, [(100, 9), (0, 0)], ADD, 0.9, True),
    "two_ranges_shared_ancestors": (256, [(120, 8), (129, 6)], ADD, 0.9,
                                    True),
    "far_apart_at_both_ends": (512, [(0, 5), (505, 7)], ADD, 0.9, True),
    "range_crosses_the_ring_end": (256, [(250, 12), (40, 3)], ADD, 0.9,
                                   False),
    "a_level_narrower_than_the_window": (128, [(61, 4), (3, 2)], ADD, 0.9,
                                         True),
    "span_over_the_width": (256, [(10, 2 * REBUILD_W + 1), (0, 0)], ADD,
                            0.9, False),
    "the_whole_forest_marked_full": (256, [(0, 256), (0, 0)], ADD, 0.9,
                                     False),
    "invalid_children": (256, [(64, 40), (200, 12)], ADD, 0.3, True),
    "noncommutative_multifield": (256, [(33, 17), (90, 2)], SKEW, 0.7,
                                  True),
}


def _dirty_forest(F, K, ranges, combine, keep, seed):
    """A rebuilt forest whose leaves in ``ranges`` (ring ranges, wrapping
    at ``F``) were then rewritten and partly evicted, and whose nodes
    over them hold garbage: what the replica's dirty ranges describe."""
    rng = np.random.default_rng(seed)
    names = ("x", "y") if combine is SKEW else ("v",)
    vals = {nm: rng.integers(-9, 9, (K, 2 * F)).astype(np.int32)
            for nm in names}
    valid = np.zeros((K, 2 * F), bool)
    valid[:, F:] = rng.random((K, F)) < 0.8
    vals, valid = _numpy_rebuild(vals, valid, combine)
    for s, n in ranges:
        leaf = F + (s + np.arange(n)) % F
        for nm in names:
            vals[nm][:, leaf] = rng.integers(-9, 9, (K, n))
        valid[:, leaf] = rng.random((K, n)) < keep
        node = np.unique(leaf)
        while node.size and node.max() > 1:
            node = np.unique(node // 2)
            for nm in names:
                vals[nm][:, node] = rng.integers(-999, 999, (K, node.size))
            valid[:, node] = rng.random((K, node.size)) < 0.5
    return vals, valid


def _as_the_step_rebuilds(combine, F):
    """The step's choice: by the ranges where they fit, else in full."""
    full = xla_rebuild_levels(combine, F)
    part = rebuild_levels_by_ranges(combine, F)

    def rebuild(trees, tvalid, ranges):
        return jax.lax.cond(rebuild_fits(ranges, F), part,
                            lambda t, v, _r: full(t, v), trees, tvalid,
                            ranges)

    return jax.jit(rebuild)


@pytest.mark.parametrize("case", sorted(PARTIAL))
def test_partial_rebuild_matches_the_full_rebuild_node_for_node(case):
    F, ranges, combine, keep, fits = PARTIAL[case]
    K = 8
    vals, valid = _dirty_forest(F, K, ranges, combine, keep,
                                seed=len(case))
    r = np.asarray(ranges, np.int32)
    assert rebuilds_by_ranges(F)
    assert bool(rebuild_fits(r, F)) == fits
    got, gotv = _by_row(*_as_the_step_rebuilds(combine, F)(
        *_node_major(vals, valid), jnp.asarray(r)))
    # node for node the full rebuild's forest, values of invalid nodes too
    full, fullv = _by_row(*jax.jit(xla_rebuild_levels(combine, F))(
        *_node_major(vals, valid)))
    assert (gotv == fullv).all()
    for nm in vals:
        assert (got[nm] == full[nm]).all(), nm
    # and the oracle's wherever a node holds data
    exp, expv = _numpy_rebuild(vals, valid, combine)
    assert (gotv[:, 1:] == expv[:, 1:]).all()
    for nm in vals:
        assert (got[nm][:, 1:][expv[:, 1:]] == exp[nm][:, 1:][expv[:, 1:]]
                ).all()
    if fits:
        # the partial rebuild alone does it too (the choice aside)
        alone, alonev = _by_row(*jax.jit(rebuild_levels_by_ranges(
            combine, F))(*_node_major(vals, valid), jnp.asarray(r)))
        assert (alonev == fullv).all()
        for nm in vals:
            assert (alone[nm] == full[nm]).all()


def test_partial_rebuild_leaves_what_no_range_covers_as_it_was():
    """A node over no dirty leaf is not changed, even where it disagrees
    with its leaves (the replica never lets that happen: it shows the
    rebuild goes by the ranges and by nothing else)."""
    F, K = 256, 4
    vals, valid = _dirty_forest(F, K, [(20, 4), (0, 0)], ADD, 0.9, seed=5)
    vals["v"][:, F + 200] += 1      # a stale leaf outside every range
    got, gotv = _by_row(*jax.jit(rebuild_levels_by_ranges(ADD, F))(
        *_node_major(vals, valid), jnp.asarray([[20, 4], [0, 0]],
                                               jnp.int32)))
    stale = (F + 200) >> np.arange(1, 3)      # nodes 228 and 114
    assert (got["v"][:, stale] == vals["v"][:, stale]).all()
    assert (gotv[:, stale] == valid[:, stale]).all()
    # the range's ancestors are the oracle's, up to the one they share
    # with the stale leaf (the root)
    exp, expv = _numpy_rebuild(vals, valid, ADD)
    above = (F + 20) >> np.arange(1, 8)
    assert (gotv[:, above] == expv[:, above]).all()
    assert (got["v"][:, above][expv[:, above]]
            == exp["v"][:, above][expv[:, above]]).all()


@pytest.mark.parametrize("ranges,F,fits", [
    ([(0, 0), (0, 0)], 256, True),
    ([(7, 2 * REBUILD_W - 1), (0, 0)], 256, True),    # parents: W
    ([(7, 2 * REBUILD_W), (0, 0)], 256, False),       # parents: W + 1
    ([(8, 2 * REBUILD_W), (0, 0)], 256, True),        # parents: W
    ([(200, 56), (0, 0)], 256, True),                 # ends at the ring end
    ([(200, 57), (0, 0)], 256, False),                # wraps it
    ([(0, 256), (0, 0)], 256, False),                 # marked full
])
def test_rebuild_fits_by_the_parents_of_each_range(ranges, F, fits):
    assert bool(rebuild_fits(np.asarray(ranges), F)) == fits
    assert bool(jax.jit(rebuild_fits, static_argnums=1)(
        jnp.asarray(ranges, jnp.int32), F)) == fits
