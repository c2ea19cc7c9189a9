"""Pallas TPU kernel for the FFAT forest level rebuild.

The rebuild is the per-batch fixed cost of the flagship operator: for
every key row, internal node ``i`` is ``combine(node[2i], node[2i+1])``
with validity (an invalid child passes the other through). The XLA
lowering writes every level back to HBM; this kernel loads a block of
rows into VMEM once, folds all ``log2(F)`` levels there and writes the
finished rows back (reference counterpart: ``wf/flatfat_gpu.hpp:338-395``,
one ``Update_TreeLevel_Kernel`` launch per level).

Layout. A tree row is ``2F`` nodes along the LANE axis, which is what
the rest of the operator indexes. The TPU has no cheap way to split
lanes into (even, odd) pairs, so the kernel never reshapes: it works on
full ``(rows, L)`` 32-bit tiles, ``L = max(128, 2F)``, with several keys
packed side by side in one 128-lane row when ``2F < 128``. Every step
is a lane rotate, a lane-index compare and a select:

- children pair up by rotating the level one lane left;
- the parents (now on even lanes) are compacted to the low lanes by a
  butterfly of ``log2(w/2)`` masked rotates (order-preserving, so a
  non-commutative combine keeps its operand order);
- each finished level is rotated to its heap position ``[w/2, w)`` and
  selected into the output row by lane index.

Validity travels as an int32 0/1 plane (no ``bool`` refs). Rotates wrap
around a row and across the keys packed in it, but every lane that is
selected was rotated from inside its own key's segment.

``WF_PALLAS=1`` selects the kernel for ``Ffat_Windows_TPU``: compiled
on a TPU, in interpret mode elsewhere (tests). The user ``combine`` is
inlined into the kernel body — any elementwise jax-traceable combine
works.
"""

from __future__ import annotations

import os
from typing import Callable, Dict


def pallas_enabled() -> bool:
    return os.environ.get("WF_PALLAS", "0") == "1"


_ROW_BLOCK = 256  # kernel rows per grid step: 128 KiB per 32-bit plane


def make_forest_rebuild(combine: Callable, field_names, F: int,
                        interpret: bool = False):
    """Returns ``rebuild(trees: dict, tvalid) -> (trees, tvalid)`` where
    trees values and tvalid are (K_cap, 2F) arrays whose leaf half
    ``[F:2F)`` is current; internal nodes ``[1:F)`` are recomputed and
    node 0 and the leaves pass through. ``K_cap`` and ``F`` are powers
    of two (the forest's own invariant)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    names = list(field_names)
    n = len(names)
    S = 2 * F                 # lanes of one key's tree
    L = max(128, S)           # lanes of one kernel row
    LOG_F = F.bit_length() - 1

    def kernel(*refs):
        vals = {nm: refs[i][...] for i, nm in enumerate(names)}  # (RB, L)
        valid = refs[n][...]                                     # int32 0/1
        seg = jax.lax.broadcasted_iota(jnp.int32, valid.shape, 1) & (S - 1)

        def left(a, k):       # lane j <- lane j + k
            return pltpu.roll(a, L - k, 1)

        def right(a, k):      # lane j <- lane j - k
            return pltpu.roll(a, k, 1)

        out = dict(vals)
        out_valid = valid
        # current level on segment lanes [0, w); other lanes are junk
        cur = {nm: left(v, F) for nm, v in vals.items()}
        cv = left(valid, F)
        w = F
        for _ in range(LOG_F):
            rc = {nm: left(v, 1) for nm, v in cur.items()}
            vr = left(cv, 1)
            merged = combine(cur, rc)
            both = (cv & vr) != 0
            has_l = cv != 0
            par = {nm: jnp.where(both, merged[nm],
                                 jnp.where(has_l, cur[nm], rc[nm]))
                   for nm in names}
            pv = cv | vr
            # parent p sits on lane 2p: move it to lane p, one bit of p
            # per step, low bit first (see the module doc)
            half = w // 2
            b = 0
            while (1 << b) < half:
                step = 1 << b
                dest = (((seg >> (b + 1)) & 1) == 0) & ((seg & step) != 0)
                par = {nm: jnp.where(dest, left(v, step), v)
                       for nm, v in par.items()}
                pv = jnp.where(dest, left(pv, step), pv)
                b += 1
            place = (seg >= half) & (seg < w)
            out = {nm: jnp.where(place, right(par[nm], half), out[nm])
                   for nm in names}
            out_valid = jnp.where(place, right(pv, half), out_valid)
            cur, cv, w = par, pv, half
        for i, nm in enumerate(names):
            refs[n + 1 + i][...] = out[nm]
        refs[2 * n + 1][...] = out_valid

    def rebuild(trees: Dict, tvalid):
        K_cap = tvalid.shape[0]
        # tiny forests pad their key axis up to one full 128-lane row
        Kp = max(K_cap, L // S)
        rows = Kp * S // L

        def pack(a):
            if Kp != K_cap:
                a = jnp.pad(a, ((0, Kp - K_cap), (0, 0)))
            return a.reshape(rows, L)

        rb = min(_ROW_BLOCK, rows)
        blk = pl.BlockSpec((rb, L), lambda i: (i, 0))
        planes = [pack(trees[nm]) for nm in names]
        planes.append(pack(tvalid.astype(jnp.int32)))
        outs = pl.pallas_call(
            kernel, grid=(rows // rb,),
            in_specs=[blk] * (n + 1), out_specs=[blk] * (n + 1),
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in planes],
            interpret=interpret,
        )(*planes)
        unpack = lambda a: a.reshape(Kp, S)[:K_cap]
        new_trees = {nm: unpack(o) for nm, o in zip(names, outs[:n])}
        return new_trees, unpack(outs[n]) != 0

    return rebuild
