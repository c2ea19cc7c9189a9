"""The window step and the fire-only program write the node-major forest
by (node, slot) (``tpu/ffat_tpu.py``: ``set_leaves``, ``plan_lanes``):
no program reshapes a forest-sized array to rank 1, and one step's leaf
update and one fire's eviction give the forest a plain numpy reference
gives, where a batch's leaves lie within ``LEAF_W`` rows (the window)
and where they do not (the forest as a whole). Programs are built by the
replica and run on the CPU backend, as ``test_ffat_by_key.py`` runs
them."""

import re

import numpy as np
import pytest

from test_ffat_sliding_fire import comb_sd, lift_sd
from windflow_tpu.basic import WinType
from windflow_tpu.tpu.ffat_tpu import LEAF_W, Ffat_Windows_TPU

WIN, CAP = 40, 64           # 40 + 16 leaves of slack: rings of F = 64


def make_replica(win_type, key_capacity):
    op = Ffat_Windows_TPU(
        lift=lift_sd, combine=comb_sd, key_extractor="key", win_len=WIN,
        slide_len=1, win_type=win_type, num_win_per_batch=16,
        key_capacity=key_capacity, name="win")
    op.build_replicas()
    rep = op.replicas[0]
    assert rep.F == 64 and rep.K_cap == key_capacity and LEAF_W < rep.F
    for k in range(8):
        rep._keymap.slot(k)
    return rep


def forest(rep, rng):
    """A forest with some leaves valid already (whole numbers: every
    combine is exact)."""
    shape = (2 * rep.F, rep.K_cap)
    trees = {"sum": rng.integers(0, 50, shape).astype(np.float32),
             "count": rng.integers(1, 5, shape).astype(np.int32),
             "last": rng.integers(0, 50, shape).astype(np.float32)}
    return trees, rng.random(shape) < 0.3


def comb(a, b):
    return {"sum": a["sum"] + b["sum"], "count": a["count"] + b["count"],
            "last": b["last"]}


def batch(rep, rng, spread):
    """``CAP`` rows over slots 0..7 (``v`` whole numbers) and what the
    host ships the step: the packed composite (time-based) or the rows'
    slots and the plan's per-slot words (count-based). Rows repeat their
    (slot, leaf) pairs; some are late, the last four are padding. Their
    leaves lie within ``spread`` panes. Returns ``(v, comp, keyrows,
    pairs)``: ``pairs`` the (slot, leaf) each row writes, None for a row
    that writes nothing."""
    F, K_cap = rep.F, rep.K_cap
    v = rng.integers(0, 100, CAP).astype(np.float32)
    slots = rng.integers(0, 8, CAP)
    keyrows = np.zeros((2, K_cap), np.int32)
    if rep.op.win_type is WinType.TB:
        panes = 130 + rng.integers(0, spread, CAP)
        late = rng.random(CAP) < 0.1
        M, cdt = rep._comp_dtype()
        comp = np.where(late, M, slots * F + panes % F)
        comp[-4:] = M
        pairs = [None if c == M else (c // F, c % F) for c in comp]
        return v, comp.astype(cdt), keyrows, pairs
    # count-based: a slot's rows take its leaves in arrival order from
    # its count before the batch, ``base``; its first ``skip`` are late
    base = rng.integers(0, 3 * F, K_cap)
    base[:8] = 200 + np.arange(8) * max(0, spread - 16) // 7
    skip = rng.integers(0, 3, K_cap)
    keyrows[0], keyrows[1] = base, skip
    slots[-4:] = K_cap
    rank = np.zeros(K_cap, np.int64)
    pairs = []
    for s in slots:
        if s == K_cap:
            pairs.append(None)
            continue
        r = rank[s]
        rank[s] += 1
        pairs.append((s, (base[s] + r) % F) if r >= skip[s] else None)
    return v, slots.astype(rep._comp_dtype()[1]), keyrows, pairs


def leaf_update(rep, trees, tvalid, v, pairs):
    """The reference: each (slot, leaf)'s rows combined in arrival order,
    then into the leaf, or in its place where the leaf is not valid."""
    trees = {k: a.copy() for k, a in trees.items()}
    tvalid = tvalid.copy()
    seg = {}
    for x, p in zip(v, pairs):
        if p is None:
            continue
        row = {"sum": np.float32(x), "count": np.int32(1),
               "last": np.float32(x)}
        seg[p] = comb(seg[p], row) if p in seg else row
    for (slot, leaf), row in seg.items():
        node = rep.F + leaf
        if tvalid[node, slot]:
            row = comb({k: a[node, slot] for k, a in trees.items()}, row)
        for k in trees:
            trees[k][node, slot] = row[k]
        tvalid[node, slot] = True
    return trees, tvalid, {p[1] for p in seg}


def plan(rep, W, keyrows, chunks=None):
    """A plan of width ``W``: the count-based per-slot words, and the
    chunks a fire takes (None: it fires nothing)."""
    if chunks is None:
        pack = np.zeros(rep._plan_len(W), np.int32)
    else:
        pack = rep._pack_fire_arrays(chunks, W, rep._chunk_keys(chunks[0]),
                                     None)[0]
    if rep.op.win_type is WinType.CB:
        pack[1:1 + 2 * rep.K_cap] = keyrows.reshape(-1)
    return pack


def rank1_forest_reshapes(text, n):
    """The reshapes in a lowered program's text of an operand of ``n``
    elements or more to rank 1."""
    out = []
    for m in re.finditer(r"stablehlo\.reshape[^\n]*?\(tensor<([0-9x]+)x"
                         r"\w+>\) -> tensor<(\d+)x\w+>", text):
        dims = [int(d) for d in m.group(1).split("x")]
        if int(np.prod(dims)) >= n:
            out.append(m.group(0))
    return out


@pytest.mark.parametrize("spread", [6, 48], ids=["window", "whole"])
@pytest.mark.parametrize("K_cap", [64, 256])
@pytest.mark.parametrize("win_type", [WinType.TB, WinType.CB],
                         ids=["time", "count"])
def test_step_and_fire_update_the_forest_by_node_and_slot(
        win_type, K_cap, spread):
    import jax.numpy as jnp

    rep = make_replica(win_type, K_cap)
    F, W = rep.F, rep.W_cap
    rng = np.random.default_rng(K_cap + spread + (win_type is WinType.CB))
    trees0, tvalid0 = forest(rep, rng)
    v, comp, keyrows, pairs = batch(rep, rng, spread)
    want_t, want_v, leaves = leaf_update(rep, trees0, tvalid0, v, pairs)
    assert (max(leaves) - min(leaves) < LEAF_W) == (spread < LEAF_W)
    assert sum(p is not None for p in pairs) > len(leaves)   # repeats
    assert any(tvalid0[F + leaf, s] for s, leaf in
               (p for p in pairs if p is not None))     # a leaf valid

    def dev(trees, tvalid):
        return {k: jnp.asarray(a) for k, a in trees.items()}, \
            jnp.asarray(tvalid)

    fields = {"v": jnp.asarray(v)}
    n_forest = 2 * F * K_cap
    for ingest_only in (True, False):
        step = rep._make_step(CAP, ingest_only=ingest_only,
                              W=W)._wrapped_jit
        args = (fields, jnp.asarray(comp), *dev(trees0, tvalid0),
                jnp.asarray(plan(rep, W, keyrows)))
        assert rank1_forest_reshapes(step.lower(*args).as_text(),
                                     F * K_cap) == []
        got_t, got_v = step(*args)[:2]
        # the full step rebuilds the internal nodes: its leaves compared
        rows = slice(0, 2 * F) if ingest_only else slice(F, 2 * F)
        assert (np.asarray(got_v)[rows] == want_v[rows]).all()
        for k in want_t:
            assert (np.asarray(got_t[k])[rows] == want_t[k][rows]).all(), k

    # one fire: chunks of slots 0..3 from panes ``spread`` apart, the
    # second clipped by its data, lanes past the plan's masked
    start0 = np.array([200, 203, 200 + spread, 205])
    k = np.array([3, 4, 2, 1])
    max_leaf = start0 + np.array([9, 1, 5, 0])
    chunks = (np.arange(4), start0, k, start0, max_leaf)
    assert int(k.sum()) < W
    fire = rep._make_fire_step(W)._wrapped_jit
    args = (*dev(want_t, want_v),
            jnp.asarray(plan(rep, W, keyrows, chunks)))
    assert rank1_forest_reshapes(fire.lower(*args).as_text(), F * K_cap) \
        == []
    got_v = np.asarray(fire(*args)[0])
    want = want_v.copy()
    for s, a, n, m in zip(range(4), start0, k, max_leaf):
        for p in range(a, min(a + n, m + 1)):
            want[F + p % F, s] = False
    assert (got_v == want).all()
    assert want_v.sum() - want.sum() > 0 and got_v.size == n_forest
