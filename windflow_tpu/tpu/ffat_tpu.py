"""Ffat_Windows_TPU: the flagship device operator — sliding-window
lift+combine aggregation over a batched FlatFAT forest in HBM.

Reference: ``wf/ffat_windows_gpu.hpp`` + ``wf/ffat_replica_gpu.hpp`` +
``wf/flatfat_gpu.hpp`` (see SURVEY.md §3.5). The reference's per-batch GPU
flow is: lift kernel -> thrust sort/reduce by (key, pane) -> small D2H of
the unique (key, pane) arrays -> host loop per key pushing panes into a
device ring and firing watermark-complete windows through a per-key FlatFAT
(``Compute_Results_Kernel`` combines O(log B) nodes per window).

TPU-first redesign:
- the control plane runs on HOST METADATA ONLY: keys and timestamps are
  already host-side on ``BatchTPU``, so per-key pane bookkeeping,
  window-fire decisions and eviction ranges are numpy — no D2H of data
  at all (the reference pays a D2H of its unique arrays every batch,
  ``ffat_replica_gpu.hpp:945-988``). Segmentation (sort order + run
  detection) happens IN-PROGRAM, on one packed composite column the
  host ships per batch, so it overlaps the host control plane. A fire
  plan goes by the key slot: a slot's fired windows are consecutive, so
  the host ships a few words a firing slot, its key among them
  (``plan_views``), and the program expands its own fire lanes
  (``plan_lanes``). For COUNT-BASED windows the host's half of ingest
  goes by the key too (a slot's rows are numbered from its count in
  arrival order): it ships the rows' slots and two words a slot, and
  the program numbers its own rows in that sort (``cb_number_rows``);
- the data plane is ONE jitted XLA program per batch:
    lift(columns) -> sort of the packed (slot, leaf) composite ->
    gather(sort order) -> segmented associative scan with
    the user combine -> gather segment tails -> scatter-combine into the
    leaves of a FlatFAT FOREST (one segment tree per key slot, circular
    leaf addressing ``pane mod F``, stored NODE-MAJOR: a (2F, K_cap)
    array a field, node ``i`` of every slot's tree one row) -> level
    rebuild, bottom up, of the internal nodes over the panes written
    and evicted since the last rebuild (a window of each level around
    their ancestors), or of every node where those ranges are wide ->
    iterative
    range queries for the program's fired windows (each walks <= 2 log F
    nodes with ordered left/right accumulators, safe for non-commutative
    combines): ONE walk per distinct ring range over every key slot at
    once where the program's windows share a few ranges (time-based
    windows planned by rounds do), else one walk a window under vmap;
    count-based windows, whose ranges are per key but CONSECUTIVE within
    a key, leave the walk where a program holds many of them: two block
    scans over every ring's leaves answer every window of every key at
    once (a sliding aggregate; ``fire_slides`` is the rule, by the
    program's lanes against the forest's leaves)
    -> leaf eviction;
- ONE planner fires every operator's windows (_programs, _plan_program),
  at one width rule: a program is ``W_cap`` lanes wide, the user's
  ``num_win_per_batch`` where one was given (a cap: no program is
  wider), else the key capacity's default. Only time-based windows with
  no budget given grow it (``W_wide``, _fit_width): they answer by range
  and cost by the range, not by the lane, so a batch's whole plan leaves
  in ONE program, the step itself, at the capacity bucket that holds
  it, up to the capacity of the input batch. A time-based plan over
  ``G_CAP`` ranges is cut at a whole round and stays by range; only a
  ragged plan walks by lane, at ``W_cap``;
- all shapes are static per (cap, K_cap, F) bucket and fire width;
  key capacity and ring length grow by doubling with a device-side rebuild
  (the reference resizes its pending-pane ring on demand,
  ``ffat_replica_gpu.hpp:219-260``).

Window semantics match the CPU ``Ffat_Windows``: pane = gcd(win, slide)
time units (TB) or one tuple (CB, leaf = per-key arrival index); TB windows
fire when the watermark minus lateness passes their end; empty windows
between two of a key's windows that hold events fire with ``valid=False``;
late tuples behind the eviction frontier are counted as ignored; EOS
flushes partial windows.

A key of a TIME-BASED operator holds its slot only while one of its
windows holds an event: once the fires have passed its last event
(``max_leaf < next_fire``: every leaf of its row evicted) the slot goes
back to a free list (_reclaim) and the next new key takes it, so the
forest is sized by the keys that are live, not by the keys ever seen. A
key that comes back is a new key, anchored at the first window that
holds its first event, and never below ``_reclaimed_wid``, the furthest
window any forgotten key had reached: a late event of a forgotten key
is counted and dropped like any event behind its key's fired windows,
and no (key, window) is delivered twice. Count-based operators keep
their keys (a key's arrival index is its state).

Output batches carry one row per fired window: the combined value columns,
``wid`` (per-key window id), ``valid`` (False for empty windows), and the
key column when the key is a field name. A time-based window's row is
stamped with the last instant inside the window (``wid * slide + win -
1``), and a batch's watermark stays below the earliest window end among
its own rows and the rows its drain still owes (_emit_windows), so a
window operator downstream takes every row in time; count-based rows
carry the watermark at the fire.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..basic import OpType, RoutingMode, WinType, WindFlowError
from ..monitoring.tracing import next_batch_id
from .batch import BatchTPU, ChunkedKeys, bucket_capacity, field_dtype
from .ops_tpu import TPUOperatorBase, TPUReplicaBase, own_key_spec
from .schema import TupleSchema, broadcast_scalar_fields


# XLA module names of the window operator's programs (``jit_step``,
# ``jit_fire``, ``jit_rebuild`` in a device profile: the benchmark's
# window_step_roofline metric finds them by ``^jit_(step|fire|rebuild)``,
# and tests/test_stage_spans.py pins them; the ingest-only variant is a
# ``step`` too) and the named scopes of the step's phases
PROG_STEP, PROG_FIRE, PROG_REBUILD = "step", "fire", "rebuild"
SCOPE_SORT, SCOPE_SCAN, SCOPE_SCATTER = ("ingest_sort", "segmented_scan",
                                         "leaf_scatter")
SCOPE_REBUILD, SCOPE_FIRE, SCOPE_EVICT = "level_rebuild", "fire", "evict"

# the most distinct ring ranges (start_phys, length) whose windows one
# program answers by range (one tree walk over every key slot at once,
# see _query_fns). A program planned by the plan's own width is closed
# at the last whole round that keeps it within G_CAP ranges
# (_plan_program); any other program whose lanes hold more takes the lane
# walk. The answers are a (G_CAP, K_cap) table per tree field. 32 (PR 30,
# probe at a 302 MB forest, PERF.md section 6): a block of eight slides
# of every key is ~19 ranges at 0.075 ms each; at 64 the end-of-stream
# flush needs half the programs, and every step takes 0.35 ms longer
G_CAP = 32

# count-based windows: a program of W lanes over a forest of K_cap rings
# answers BY SLIDING SCAN (two block scans over every leaf, see
# _query_fns) where ``W * SLIDE_X >= K_cap * F``, else by lane. The walk
# costs by the lane, the scan by the forest's leaves; SLIDE_X is how many
# leaves the scan passes in the time of one lane's walk. 2048 (PR 33,
# probe of the fire-only program on the chip, three tree fields, PERF.md
# section 6; ms a call, by lane / by scan): 64 slots x 2,048 leaves at
# 64 / 1,024 / 16,384 lanes 0.83 / 2.44 / 46.2 against 1.05 / 0.94 /
# 1.03 (even at ~150 lanes: X ~900, mostly the programs' fixed costs);
# 4,096 slots x 2,048 at 64 / 4,096 / 16,384 lanes 0.87 / 12.0 / 47.7
# against 12.7 / 13.0 / 13.5 (even at ~4,250 lanes: X ~2,000). The walk
# is 2.8-2.9 us a lane, the scan 1.5-1.6 ns a leaf over a big forest. At
# 2048 the worse choice costs x1.26 at most (0.2 ms, a 64-lane program
# over the small forest); at 1024 an 8,000-lane program over the big
# forest would walk for 23 ms where the scan takes 13
SLIDE_X = 2048

# time-based windows: the static width, in nodes of one tree level, of
# the window in which a step's partial level rebuild recomputes a dirty
# ring range's ancestors (rebuild_levels_by_ranges): rows of the
# node-major forest, every key slot at once, so a window costs by its
# rows, four sublane tiles of the device's (8, 128) registers here. It
# holds the parents of a range of up to 2 * REBUILD_W - 1 leaves; a
# wider range, or one that wraps the ring, takes the full rebuild. What
# a range spans is the event time of the batches since the last
# rebuild, which no static shape tells (a batch's capacity bounds its
# rows, not its panes): 32 holds a few firing batches of an in-order
# stream with room to spare (sg2: a 32,768-row batch writes ~9 of its
# 4,096 leaves and its fire evicts ~8), and the partial rebuild pays
# only over a ring with levels wider than two windows
# (rebuilds_by_ranges), whose full rebuild passes every node
REBUILD_W = 32
# the rows of a time-based plan's head (plan_views) that carry the
# step's two dirty ring ranges, ``(start_phys, length)``: the panes
# written and the panes evicted since the last rebuild
RANGE_ROWS = slice(G_CAP + 1, G_CAP + 3)


def fire_slides(W: int, K_cap: int, F: int) -> bool:
    """Whether a count-based program of ``W`` lanes over ``K_cap`` rings
    of ``F`` leaves answers by sliding scan (``SLIDE_X``): one comparison
    of static shapes, made where the program is traced and where its
    counters are kept."""
    return W * SLIDE_X >= K_cap * F


def plan_len(W: int, K_cap: int, timed: bool, key_words: int) -> int:
    """Words of the ONE int32 buffer that carries a program's fire plan
    (see ``plan_views``) at a width of ``W`` lanes."""
    head = 2 * (G_CAP + 3) if timed else 2 * K_cap
    return 1 + head + (5 + key_words) * min(K_cap, W)


def plan_views(pack, K_cap: int, timed: bool, key_words: int):
    """``(head, chunks, total)`` views of a program's flat fire plan, on
    the host (numpy, to fill it) and inside the program (static
    slices). Everything in it is per KEY slot; what is per lane of the
    fire block the program derives (``plan_lanes``), for both window
    types:

    - ``head``: a TIME-based plan's (G_CAP + 3, 2) rows: its group
      table, the distinct ring ranges ``(start_phys, length)`` of its
      lanes in ascending order and, in row ``G_CAP``, their count (0:
      the program walks by lane); then, in ``RANGE_ROWS``, the two
      dirty ring ranges a step's level rebuild goes by (the panes
      written and the panes evicted since the last rebuild, a length of
      ``F`` where the whole forest is; read by a step only, 0 in any
      other plan); a COUNT-based plan's ``keyrows`` (2,
      K_cap), read by the step's ingest: ``base``, a slot's arrival
      count before the batch (mod ``F``: the ring place of its next
      leaf), and ``skip``, how many of its first arrivals in the batch
      lie behind its ``next_fire`` and are dropped (gap windows, a
      re-registered key). A fire-only program leaves them 0;
    - ``chunks`` (5 + key_words, C) rows slot, start0 (mod ``F``), k,
      wid0, span, then the chunk's key in ``key_words`` int32 words,
      low word first (``join_key_words``): a slot's ``k`` consecutive
      windows from ring place ``start0`` and window id ``wid0``, over
      ``span`` leaves of data from ``start0`` on (``max_leaf + 1 -
      start0``). A program holds one chunk a slot and a lane a window,
      so ``C = min(K_cap, W)``; rows past the plan's chunks are 0 (``k``
      0: no lane);
    - ``total`` (1,), the buffer's first word: the plan's windows, the
      sum of ``k``.

    One buffer, so one transfer a program: a launch pays for every host
    argument it is handed."""
    n_h = 2 * (G_CAP + 3) if timed else 2 * K_cap
    rows = 5 + key_words
    C = (pack.shape[0] - 1 - n_h) // rows
    head = pack[1:1 + n_h].reshape((G_CAP + 3, 2) if timed else (2, K_cap))
    return head, pack[1 + n_h:1 + n_h + rows * C].reshape(rows, C), pack[:1]


def plan_lanes(fire_plan, W: int, K_cap: int, F: int, win_units: int,
               slide_units: int, timed: bool, key_words: int):
    """In a program: the lanes of a plan, expanded from its chunk rows
    (``plan_views``) at the static width ``W``: ``(slots, starts, lens,
    wids, mask, group, eflat, keys)``, a lane each but ``eflat``, the
    flat forest indices of the ``W * slide_units`` leaves evicted (out
    of bounds where there is none), and ``keys``, the ``(key_words, W)``
    words of each lane's key. ``group`` is a time-based lane's row in
    the group table (a count of the rows whose range sorts below its
    own: at most ``G_CAP`` compares a lane) and a count-based lane's
    round, its window's place in its slot's chunk. Lane ``i`` of chunk
    ``c``, round ``r = i - (windows of the chunks before c)``: start
    ``start0 + r * slide``, length ``min(win, span - r * slide)``,
    window id ``wid0 + r``; it evicts the ``slide`` leaves from its
    start that lie inside ``span``. A lane finds its chunk through a
    mark at each chunk's first lane and one cumulative sum. Masked lanes
    read 0 in every row."""
    import jax.numpy as jnp

    head, chunks, total = plan_views(fire_plan, K_cap, timed, key_words)
    c_k = chunks[2]
    before = jnp.cumsum(c_k) - c_k
    marks = jnp.zeros((W,), jnp.int32).at[
        jnp.where(c_k > 0, before, W)].add(1, mode="drop")
    chunk = jnp.maximum(jnp.cumsum(marks) - 1, 0)
    lane = jnp.arange(W, dtype=jnp.int32)
    mask = lane < total[0]
    # ONE gather of a lane's words (what a gather from a small 1-D table
    # costs in program text: cb_number_rows)
    c_slot, c_start0, _k, c_wid0, c_span, *c_key, c_before = jnp.where(
        mask[None, :], jnp.concatenate([chunks, before[None]])[:, chunk], 0)
    rounds = jnp.where(mask, lane - c_before, 0)
    off = rounds * slide_units
    starts = (c_start0 + off) & (F - 1)
    lens = jnp.minimum(win_units, c_span - off)
    if timed:
        # the table is sorted by (start, length): a lane's row is the
        # count of the live rows below its range (a masked lane's range
        # (0, 0) has none below it)
        g_s, g_l = head[:G_CAP, 0][None, :], head[:G_CAP, 1][None, :]
        s, ln = starts[:, None], lens[:, None]
        below = (jnp.arange(G_CAP) < head[G_CAP, 0])[None, :] & (
            (g_s < s) | ((g_s == s) & (g_l < ln)))
        group = below.sum(axis=1, dtype=jnp.int32)
    else:
        group = rounds
    # a lane evicts the ``slide`` leaves from its start, as far as the
    # chunk's data goes: over a chunk's rounds, the range [start0,
    # start0 + k * slide) clipped to the data
    e_off = off[:, None] + jnp.arange(slide_units, dtype=jnp.int32)[None, :]
    eflat = jnp.where(
        mask[:, None] & (e_off < c_span[:, None]),
        (F + ((c_start0[:, None] + e_off) & (F - 1))) * K_cap
        + c_slot[:, None], K_cap * 2 * F).reshape(-1)
    return (c_slot, starts, lens, c_wid0 + rounds, mask, group, eflat,
            jnp.stack(c_key) if c_key else None)


def key_words_of(keys: np.ndarray, key_words: int) -> np.ndarray:
    """``(key_words, n)`` int32 words of int keys, low word first: the
    rows a plan carries them in (``plan_views``)."""
    keys = np.asarray(keys, np.int64)
    words = [keys.astype(np.int32), (keys >> 32).astype(np.int32)]
    return np.stack(words[:key_words])


def join_key_words(words, kd):
    """In a program: the key column, in the key column's dtype ``kd``,
    from the ``(key_words, W)`` words of ``key_words_of``: one word for a
    key of up to 32 bits, two (reassembled) for a wider one."""
    import jax
    import jax.numpy as jnp

    kd = jnp.dtype(kd)
    lo = jax.lax.bitcast_convert_type(words[0], jnp.uint32)
    if words.shape[0] == 2:
        return (words[1].astype(kd) << 32) | lo.astype(kd)
    return (lo if kd.kind == "u" else words[0]).astype(kd)


def cb_number_rows(slots, keyrows, K_cap: int, F: int):
    """In a step: ``(order, sc)`` of a count-based batch, the stable
    sort order of its rows' ``slots`` (sentinel ``K_cap``: padding, rows
    a fused filter dropped) and the SORTED packed composite ``slot * F +
    leaf`` (sentinel ``K_cap * F``: those rows and the late ones) that
    the host ships ready-made for time-based windows. The stable sort
    leaves a slot's rows in arrival order; a row's rank in its slot's
    run numbers it from ``base``, the slot's count before the batch, and
    the slot's first ``skip`` rows are late (``keyrows``:
    ``plan_views``)."""
    import jax
    import jax.numpy as jnp

    order = jnp.argsort(slots, stable=True)
    ss = slots[order].astype(jnp.int32)
    pos = jnp.arange(ss.shape[0], dtype=jnp.int32)
    run_start = jnp.concatenate([jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    rank = pos - jax.lax.cummax(jnp.where(run_start, pos, 0))
    # ONE gather of both words: a gather from a small 1-D table unrolls
    # to seven times the program text of this one (PERF.md section 6)
    base, skip = keyrows[:, jnp.minimum(ss, K_cap - 1)]
    return order, jnp.where(
        (ss < K_cap) & (rank >= skip),
        ss * F + ((base + rank) & (F - 1)), K_cap * F)


def _parents(combine: Callable, lc, rc, vlc, vrc):
    """In a program: the nodes over the left children ``lc`` and the
    right children ``rc`` (fields alike) and their validity: an invalid
    child passes the other through. The one combine of every rebuild,
    so a node comes out the same whichever rebuild computed it."""
    import jax
    import jax.numpy as jnp

    node = jax.tree_util.tree_map(
        lambda m, a, b: jnp.where(vlc & vrc, m, jnp.where(vlc, a, b)),
        combine(lc, rc), lc, rc)
    return node, vlc | vrc


def xla_rebuild_levels(combine: Callable, F: int):
    """``rebuild(trees, tvalid) -> (trees, tvalid)``: recompute internal
    nodes ``[1, F)`` of every tree of a (2F, K_cap) node-major forest
    from its leaves, one fused XLA pass per level (an invalid child
    passes the other through). The ONE full rebuild, traced by the
    step's in-program rebuild and by the standalone settle program
    (divergence would make deferred batches aggregate differently from
    direct ones)."""
    import jax

    tmap = jax.tree_util.tree_map

    def rebuild_levels(trees, tvalid):
        lvl = F >> 1
        while lvl >= 1:
            node, nv = _parents(
                combine, tmap(lambda t: t[2 * lvl:4 * lvl:2], trees),
                tmap(lambda t: t[2 * lvl + 1:4 * lvl:2], trees),
                tvalid[2 * lvl:4 * lvl:2], tvalid[2 * lvl + 1:4 * lvl:2])
            trees = tmap(lambda t, nd: t.at[lvl:2 * lvl].set(nd),
                         trees, node)
            tvalid = tvalid.at[lvl:2 * lvl].set(nv)
            lvl >>= 1
        return trees, tvalid

    return rebuild_levels


def rebuilds_by_ranges(F: int) -> bool:
    """Whether a time-based step over rings of ``F`` leaves holds the
    partial rebuild (``rebuild_levels_by_ranges``): where some level is
    wider than ``REBUILD_W``, and so wider than a window of it."""
    return F > 2 * REBUILD_W


def rebuild_fits(ranges, F: int):
    """Whether a step rebuilds by its plan's dirty ring ranges (``ranges``,
    rows ``(start_phys, length)``, numpy on the host or traced in the
    program): where no range wraps the ring's end and the parents of
    each, ``(start + length - 1) // 2 - start // 2 + 1`` nodes, fit a
    window of ``REBUILD_W``. A range the host marks full has length
    ``F``, which fits no window of a ring that rebuilds by ranges."""
    s, n = ranges[:, 0], ranges[:, 1]
    return ((s + n <= F) & ((s + n - 1) // 2 - s // 2 < REBUILD_W)).all()


def rebuild_levels_by_ranges(combine: Callable, F: int):
    """``rebuild(trees, tvalid, ranges) -> (trees, tvalid)``: recompute
    the internal nodes over the leaves of the ring ranges ``ranges``
    (rows ``(start_phys, length)`` that ``rebuild_fits``) of every tree
    of a node-major forest, bottom up, level by level, each range's
    ancestors a window of ``REBUILD_W`` rows (``dynamic_slice`` of its
    children, written back in place), and every node of a level no
    wider than the window.

    Sound where every node with no leaf of a range below it agrees with
    its leaves (the replica keeps the ranges so: ``_dirty_in``,
    ``_dirty_ev``): a node recomputed here is an ancestor of a range's
    leaves, whose children a lower level of this sweep has recomputed,
    or a node whose children agree already and which comes out as it
    was. Two ranges that share ancestors near the root are one sweep:
    a level's windows read only the level below, which is current for
    both. Every node is computed by ``_parents``, as the full rebuild
    computes it, so the forest comes out node for node the same."""
    import jax
    import jax.numpy as jnp

    tmap = jax.tree_util.tree_map
    W = REBUILD_W

    def window(trees, tvalid, j0, n):
        # nodes ``[j0, j0 + n)`` of every tree from their ``2 n``
        # children, a child pair two rows
        def pairs(x):   # (2 n, K_cap) children -> (n, 2, K_cap)
            return jax.lax.dynamic_slice_in_dim(x, 2 * j0, 2 * n).reshape(
                n, 2, -1)
        ch, cv = tmap(pairs, trees), pairs(tvalid)
        node, nv = _parents(combine, tmap(lambda c: c[:, 0], ch),
                            tmap(lambda c: c[:, 1], ch), cv[:, 0], cv[:, 1])
        return (tmap(lambda t, nd: jax.lax.dynamic_update_slice_in_dim(
            t, nd, j0, axis=0), trees, node),
            jax.lax.dynamic_update_slice_in_dim(tvalid, nv, j0, axis=0))

    def rebuild(trees, tvalid, ranges):
        lvl, h = F >> 1, 1
        while lvl >= 1:
            if lvl <= W:
                trees, tvalid = window(trees, tvalid, lvl, lvl)
            else:
                for r in range(ranges.shape[0]):
                    # the window of the level's nodes that holds the
                    # range's ancestors, ``lvl + (leaf >> h)``
                    j0 = jnp.clip(lvl + (ranges[r, 0] >> h), lvl,
                                  2 * lvl - W)
                    trees, tvalid = window(trees, tvalid, j0, W)
            lvl >>= 1
            h += 1
        return trees, tvalid

    return rebuild


class Ffat_Windows_TPU(TPUOperatorBase):
    op_type = OpType.WIN_TPU

    def __init__(self, lift: Callable, combine: Callable, key_extractor,
                 win_len: int, slide_len: int,
                 win_type: WinType = WinType.TB, lateness: int = 0,
                 num_win_per_batch: Optional[int] = None,
                 name: str = "ffat_windows_tpu", parallelism: int = 1,
                 output_batch_size: int = 0,
                 schema: Optional[TupleSchema] = None,
                 key_capacity: int = 16) -> None:
        if key_extractor is None:
            raise WindFlowError(f"{name}: requires a key extractor")
        if win_len <= 0 or slide_len <= 0:
            raise WindFlowError(f"{name}: win/slide must be > 0")
        super().__init__(name, parallelism, RoutingMode.KEYBY, key_extractor,
                         output_batch_size, schema)
        self.lift = lift
        self.combine = combine
        self.win_len = win_len
        self.slide_len = slide_len
        self.win_type = win_type
        self.lateness = lateness
        self.key_capacity = max(1, key_capacity)
        # a budget the user gave caps the width of every fire program;
        # without one, time-based windows size their fire programs by
        # what their plans hold (FfatTPUReplica._fit_width) and start
        # from the default below
        self.budget_given = num_win_per_batch is not None
        if num_win_per_batch is None:
            # fired windows per step scale with key count (each key slides
            # its own windows): default the fire-batch budget to the key
            # capacity so high-cardinality streams don't drain through
            # many tiny programs (the reference leaves numWinPerBatch
            # manual, builders_gpu.hpp:576)
            num_win_per_batch = max(16, min(8192, self.key_capacity))
        self.num_win_per_batch = max(1, num_win_per_batch)
        self.pane_len = math.gcd(win_len, slide_len)
        # compiled programs shared ACROSS replicas: cache keys carry every
        # shape parameter (cap, K_cap, F), so equal-config
        # replicas reuse one compile instead of paying parallelism x
        # (lock: replica worker threads race their first batch)
        import threading
        self._prog_cache: Dict[Any, Any] = {}
        self._prog_lock = threading.Lock()
        # (program cache key, fire width) pairs some replica has run
        # once: a width is a traced shape of a cached program
        self._warm_shapes: set = set()

    @property
    def fusion_role(self) -> Optional[str]:
        """``"window_terminator"``: the window op may END a fused device
        chain (its step program absorbs a stateless map/filter prefix —
        ``fused_ops.FusedFfatReplica``) but can never sit mid-chain: it
        changes the row domain (tuples -> fired windows), so nothing can
        compose after it inside one program."""
        return "window_terminator"

    def build_replicas(self) -> None:
        self.replicas = [FfatTPUReplica(self, i)
                         for i in range(self.parallelism)]


class FfatTPUReplica(TPUReplicaBase):
    def __init__(self, op: Ffat_Windows_TPU, idx: int) -> None:
        super().__init__(op, idx)
        if op.win_type is WinType.CB:
            self.win_units = op.win_len
            self.slide_units = op.slide_len
        else:
            self.win_units = op.win_len // op.pane_len
            self.slide_units = op.slide_len // op.pane_len
        # ring length: window + slack for panes ahead of the watermark
        self.F = 1 << max(3, math.ceil(math.log2(
            self.win_units + max(2 * self.slide_units, 16))))
        # pre-sizing the key table avoids growth recompiles
        # (wf/builders_gpu.hpp has no analog; growth still works past it)
        self.K_cap = 1 << max(2, math.ceil(math.log2(op.key_capacity)))
        # The widths (lanes) of the fire programs. W_cap is the budget:
        # the user's (a cap), or the key capacity's default. W_wide, the
        # width a program is planned at first, is W_cap, and only
        # time-based windows with no budget given grow it to the
        # capacity bucket that holds their plan (_fit_width); a program
        # that walks by lane is W_cap wide (_plan_program)
        self.W_cap = self.W_wide = op.num_win_per_batch
        self._cap_seen = 0  # widest input batch so far: W_wide's bound
        from .keymap import KeySlotMap
        # wf:keys: key turnover (admission, slots given back)
        self._st_keys = self.stats.stage("keys")
        self._bid = 0  # the batch in prep: its id names the wf:keys spans
        self._keymap = KeySlotMap(
            on_new=self._on_new_key, on_new_many=self._on_new_keys,
            admit_span=lambda: self._st_keys(self._bid))
        self.slot_of_key = self._keymap.slot_of_key  # shared dict
        # time-based windows give a dead key's slot back (_reclaim);
        # _reclaimed_wid: the furthest next window of any forgotten key,
        # the floor of a new key's first window
        self._reclaims = op.win_type is WinType.TB
        self._reclaimed_wid = 0
        # original keys by slot where some key is no int (else _keys_np
        # holds them: see _out_keys_by_slot)
        self._obj_keys: Optional[List[Any]] = None
        # per-slot host bookkeeping (numpy, grown with K_cap)
        self.next_fire = np.zeros(self.K_cap, dtype=np.int64)
        self.fired = np.zeros(self.K_cap, dtype=np.int64)  # == next gwid
        self.max_leaf = np.full(self.K_cap, -1, dtype=np.int64)
        self.count = np.zeros(self.K_cap, dtype=np.int64)  # CB arrivals
        # integer key values per slot (fast emit path; falls back to the
        # _out_keys_by_slot python list for non-int keys)
        self._keys_np = np.zeros(self.K_cap, dtype=np.int64)
        self._keys_all_int = True
        self._key_dtype = np.dtype(np.int32)
        self._saw_new_key = False
        self._leaf_frontier = 0  # max leaf ever accepted (fast-path guard)
        # device-resident constant program args (avoid re-transferring
        # numpy zeros every batch)
        self._zero_fire_cache: Dict[int, Any] = {}
        # deferred-rebuild flag: True while internal tree levels are
        # stale w.r.t. leaves (ingest-only batches ran since the last
        # rebuild); every fire path rebuilds first (see _make_step)
        self._rebuild_dirty = False
        # what a time-based step's rebuild goes by (_plan_rebuild): the
        # panes written (_dirty_in) and evicted (_dirty_ev) since the
        # last rebuild, each ``(lo, hi)`` in absolute panes or None, and
        # _dirty_full where the forest was rewritten wholesale (growth,
        # first allocation, restore). Kept in PREP order, which is the
        # device order of the programs (a batch's prep plans every
        # program of the batch and the commits run them in that order),
        # not in commit order as _rebuild_dirty is: a later batch's prep
        # runs before an earlier commit lands. Invariant: a node with no
        # leaf of these below it agrees with its leaves
        self._dirty_in = self._dirty_ev = None
        self._dirty_full = True
        self.ignored = 0
        # incremental checkpointing (WF_CKPT_DELTA): host-side dirty
        # slot set — ingest and fire mark the rows they touch, and a
        # delta snapshot ships only those rows of the per-slot arrays +
        # forest. Any executed level REBUILD rewrites internal tree
        # rows forest-wide, so it conservatively forces the next
        # snapshot FULL via _dirty_all (ingest-only stretches between
        # fires — the realistic accumulation regime — still delta).
        self._ckpt_dirty: set = set()
        self._dirty_all = False
        self._delta_base = None  # epoch id of the last full snapshot
        self._snaps_since_full = 0
        self._dir_version = 0  # bumped by every admission and reclaim
        self._base_dirver = None  # ... as it stood at the last full one
        self._base_geom = None  # (K_cap, F, trees-allocated) at base
        # device forest (lazily shaped once the lift output is known)
        # node-major: row ``i`` is node ``i`` of every slot's tree (a
        # level is a slice of rows; node ``i``'s children are rows 2i,
        # 2i + 1, its leaves rows F..2F-1 by ``pane mod F``). A
        # snapshot holds it slot-major, (K_cap, 2F)
        self.trees = None  # dict field -> (2F, K_cap)
        self.tvalid = None  # (2F, K_cap) bool
        self._prog_cache = op._prog_cache  # shared across replicas
        self._warm_shapes = op._warm_shapes
        # wf:fireplan: the host's fire planning inside wf:prep
        self._st_fireplan = self.stats.stage("fireplan")
        self._check_index_plane()

    def _comp_dtype(self):
        """(sentinel M, dtype) of the one batch-sized column the host
        ships a step: the packed composite (time-based windows) or the
        rows' slots (count-based: the step forms the composite itself) —
        the SINGLE definition shared by staging, warm-up, and the driver
        entry (the traced and runtime dtypes must stay bit-identical)."""
        M = self.K_cap * (1 if self.op.win_type is WinType.CB else self.F)
        return M, (np.int16 if M < 2**15 - 1 else np.int32)

    def _check_index_plane(self, k_cap: int = 0, f: int = 0) -> None:
        """Every forest index (packed composite, device scatter/evict
        flat ids) lives in int32; enforced at init and BEFORE any growth
        commits. ``k_cap``/``f`` check a PROSPECTIVE capacity/ring
        before mutating toward it (growth must
        raise-before-mutate: a caught refusal mid-growth would leave a
        wrapped index plane that no later per-batch guard re-checks)."""
        k = k_cap or self.K_cap
        ff = f or self.F
        if k * 2 * ff >= 2**31 - 1:
            raise WindFlowError(
                f"{self.op.name}: K_cap*2F = {k * 2 * ff} "
                "overflows the int32 index plane; reduce key_capacity or "
                "the window/slide ratio")

    # ==================================================================
    # fused-chain seams (overridden by fused_ops.FusedFfatReplica)
    # ==================================================================
    def _lift_fn(self) -> Callable:
        """The lift entry every program traces. The fused-chain replica
        overrides it to compose the chain's stateless map/filter prefix
        IN FRONT of the user lift, so ``source -> map -> filter ->
        Ffat_Windows`` runs as ONE program per batch. The user's lift
        gets a dict of its own (a staged batch's packed mapping, returned
        as it is, would not be a dict of columns)."""
        lift = self.op.lift
        return lambda fields: lift(dict(fields))

    def _prefix_mask(self, batch: BatchTPU):
        """Keep mask of a fused prefix filter over ``batch`` (None when
        no prefix filters exist — the base replica never has any). MUST
        be resolved at PREP time: the host control plane's liveness
        quantities (max_leaf/next_fire/count) are exact, so a row the
        prefix drops may never register a key, advance a leaf, or count
        toward a CB window."""
        return None

    def _chain_tag(self):
        """Cache-key discriminator for composed programs (the fused
        replica returns the prefix signature; programs traced through a
        different prefix must never collide)."""
        return None

    # ==================================================================
    # the per-batch device program
    # ==================================================================
    def _query_fns(self, W: Optional[int] = None):
        """``fire_block(trees, tvalid, fire_plan) -> (tvalid, values,
        valid, wid column, key column)``: what the full step and the
        fire-only step do with a program's fire plan: answer its fired
        windows, evict the leaves they consumed, build the ``wid`` and
        key columns. A plan is chunk rows (``plan_views``) that the
        program expands into its lanes (``plan_lanes``) at ``W``, its
        static width, which the plan's shape does not tell (None: the
        operator's budget); a lane's key is its chunk's. Three queries
        answer the windows. A program holds the ones its operator can
        take: time-based windows the first two, chosen inside the
        program by the count in the plan's group table (see
        _pack_fire_arrays); count-based windows ONE of the first and the
        third, chosen where the program is traced by its static shapes
        (``fire_slides``):

        - the LANE walk (``window_query`` under ``vmap``): every lane
          walks its own slot's tree, a node read is a gather of one
          scalar a lane, so it costs by the lane, live or masked;
        - the GROUP walk (``group_query``): the same walk once per
          distinct ring range ``(start_phys, length)`` among the lanes,
          over every key slot at once: ``l``, ``r`` and the take
          predicates are scalars of the range, the accumulators
          ``(K_cap,)`` vectors, a node read one column slice of the
          forest. Lane ``i`` then picks ``table[group[i], slot[i]]``.
          Time-based windows number from absolute time 0, so the lanes
          of a program planned by rounds (_clip) share one to three
          ranges;
        - the SLIDING SCAN (``by_scan``), count-based windows only. No
          two keys share a ring range, but a program holds ONE chunk a
          slot, ``k`` consecutive windows ``start0 + j * slide``
          (_take): a sliding aggregate over the slot's leaves (van Herk,
          Gil-Werman). Every ring is rotated so that its chunk's first
          leaf is column 0, the row is cut into blocks of ``win_units``
          columns, and two block scans give the prefix aggregate ``P``
          and the suffix aggregate ``S`` of every column within its
          block; round ``j``'s window ``[o, o + win)``, ``o = j *
          slide``, is ``S[o]`` then ``P[o + win - 1]``, or one of them
          alone where the window is a block or the row's end cuts it.
          Lane ``i`` picks ``table[slot[i], round[i]]``. It costs by the
          forest's leaves, not by the lane, and reads LEAVES only: the
          leaves past a slot's data are invalid (evicted, or never
          written: _grow_ring keeps a slot's live span under ``F``), so
          validity bounds a partial window and the plan's lengths are
          not read.

        The walks run the same ``l``/``r`` recurrence with the same left
        and right accumulators through ``comb_valid``: the same order of
        combination, so the same bits for any combine, commutative or
        not (where ``valid`` is False the values are whatever the walk
        left, in both). The scan combines the same leaves in the same
        ORDER under another parenthesisation, which an associative
        combine allows: exact for exact arithmetic (whole-number float32
        sums too), within rounding for float sums."""
        import jax
        import jax.numpy as jnp

        combine = self.op.combine
        F = self.F
        K_cap = self.K_cap
        slide_units = self.slide_units
        kd, key_words = self._key_dtype, self._key_words()
        NNODES = 2 * F
        LOGQ = NNODES.bit_length()  # enough iterations for the tree walk
        tmap = jax.tree_util.tree_map
        # count-based windows start at a per-key arrival index: no two
        # keys share a ring range, so their programs hold no group walk
        grouped = self.op.win_type is WinType.TB
        win_units = self.win_units
        if W is None:
            W = self.W_cap

        def comb_valid(va, a, vb, b):
            """Ordered combine with validity: an invalid side passes the
            other through (None-as-identity, like the CPU FlatFAT)."""
            both = va & vb
            merged = combine(a, b)
            out = tmap(lambda m, x, y: jnp.where(both, m, jnp.where(va, x, y)),
                       merged, a, b)
            return va | vb, out

        def range_query(node, lo, length):
            """Ordered combine of physical leaf range [lo, lo+length):
            iterative segment-tree walk, left/right accumulators keep
            combine order (reference prefix/suffix arrays,
            ``wf/flatfat.hpp:85-132``). ``node(i) -> (valid, values)``
            reads tree node ``i``: of one tree row (scalars) or of every
            row at once (``(K_cap,)`` vectors)."""
            none, zero = tmap(jnp.zeros_like, node(0))

            def body(_, st):
                l, r, lv, la, rv, ra = st
                take_l = ((l & 1) == 1) & (l < r)
                vl, node_l = node(jnp.clip(l, 0, NNODES - 1))
                lv, la = comb_valid(lv, la, vl & take_l, node_l)
                l = jnp.where(take_l, l + 1, l)
                take_r = ((r & 1) == 1) & (l < r)
                vr, node_r = node(jnp.clip(r - 1, 0, NNODES - 1))
                rv, ra = comb_valid(vr & take_r, node_r, rv, ra)
                r = jnp.where(take_r, r - 1, r)
                return (l >> 1, r >> 1, lv, la, rv, ra)

            init = (lo + F, lo + length + F, none, zero, none, zero)
            st = jax.lax.fori_loop(0, LOGQ, body, init)
            return comb_valid(st[2], st[3], st[4], st[5])

        def window_query(trees, tvalid, slot, start_phys, length):
            """One lane: logical ring range of its slot's tree -> <=2
            physical ranges, combined in order (a node read is one
            element of the forest)."""
            def node(i):
                return tvalid[i, slot], tmap(lambda a: a[i, slot], trees)

            len1 = jnp.minimum(length, F - start_phys)
            v1, r1 = range_query(node, start_phys, len1)
            v2, r2 = range_query(node, jnp.zeros_like(start_phys),
                                 length - len1)
            return comb_valid(v1, r1, v2, r2)

        def group_query(trees, tvalid, start_phys, length):
            """One ring range (scalars) of EVERY tree: the lane's walk
            with rows of the node-major forest for node reads. The
            second walk of a range that does not wrap the ring is
            skipped, not walked masked (it could only add to an invalid
            answer)."""
            def row(t, i):
                return jax.lax.dynamic_slice_in_dim(t, i, 1)[0]

            def node(i):
                return row(tvalid, i), tmap(lambda t: row(t, i), trees)

            len1 = jnp.minimum(length, F - start_phys)
            v1, r1 = range_query(node, start_phys, len1)
            return jax.lax.cond(
                length > len1,
                lambda: comb_valid(v1, r1, *range_query(
                    node, jnp.zeros_like(start_phys), length - len1)),
                lambda: (v1, r1))

        def by_lane(trees, tvalid, slots, starts, lens):
            return jax.vmap(window_query, in_axes=(None, None, 0, 0, 0))(
                trees, tvalid, slots, starts, lens)

        def by_group(trees, tvalid, slots, group, g_table):
            def one(g, tabs):
                v, r = group_query(trees, tvalid, g_table[g, 0],
                                   g_table[g, 1])
                return (tabs[0].at[g].set(v),
                        tmap(lambda t, x: t.at[g].set(x), tabs[1], r))

            tabs = (jnp.zeros((G_CAP, K_cap), bool),
                    tmap(lambda t: jnp.zeros((G_CAP, K_cap), t.dtype),
                         trees))
            tv, tr = jax.lax.fori_loop(0, g_table[G_CAP, 0], one, tabs)
            return tv[group, slots], tmap(lambda t: t[group, slots], tr)

        def by_scan(trees, tvalid, slots, rounds, starts, mask):
            # 1. every ring in the order of its chunk: column ``o`` of
            # row ``slot`` is leaf ``start0 + o`` (the leaves turned
            # slot-major: the scan runs along a ring). ``base`` is the
            # chunk's first window's place in the ring, from its lane of
            # round 0 (0 for a slot that fires nothing here); the
            # rotation is log2 F conditional rolls, dense passes
            first = mask & (rounds == 0)
            base = jnp.zeros((K_cap,), jnp.int32).at[
                jnp.where(first, slots, K_cap)].set(starts, mode="drop")
            rows = tmap(lambda t: t[F:].T, trees)
            rvalid = tvalid[F:].T
            bit = 1
            while bit < F:
                turn = ((base & bit) != 0)[:, None]
                rows, rvalid = tmap(
                    lambda t: jnp.where(turn, jnp.roll(t, -bit, axis=1), t),
                    (rows, rvalid))
                bit <<= 1
            # 2. the two block scans, blocks of ``win_units`` columns
            # from column 0 (the last one short: F is no multiple of
            # win), by doubling: in step ``d`` a column takes in the fold
            # that ends (starts) ``d`` columns before (after) it, where
            # that column is still in its block. The blocks are static,
            # so the masks are constants and no edge flag rides a carry
            # (log2 win passes; an unrolled ``associative_scan`` does
            # less work and is x1.3 faster over a 109 MB forest, but its
            # program is four times the size: sd's warm-up loaded it
            # 2.4 s longer at each ring, PERF.md section 6)
            col = np.arange(F)
            before = col % win_units
            after = np.minimum((col // win_units + 1) * win_units,
                               F) - 1 - col

            def shifted(t, d):  # column i holds column i - d
                pad = jnp.zeros((K_cap, abs(d)), t.dtype)
                return jnp.concatenate(
                    [pad, t[:, :-d]] if d > 0 else [t[:, -d:], pad], axis=1)

            pv, pre, sv, suf = rvalid, rows, rvalid, rows
            d = 1
            while d < win_units:
                reach_b, reach_a = (jnp.asarray(r >= d)[None, :]
                                    for r in (before, after))
                pv, pre = comb_valid(
                    shifted(pv, d) & reach_b,
                    tmap(lambda t: shifted(t, d), pre), pv, pre)
                sv, suf = comb_valid(
                    sv, suf, shifted(sv, -d) & reach_a,
                    tmap(lambda t: shifted(t, -d), suf))
                d *= 2
            # 3. round j's window [o, e], o = j * slide (every o < F: a
            # slot's live span is under F), static: strided slices
            o = np.arange(0, F, slide_units)
            e = np.minimum(o + win_units - 1, F - 1)
            edge = o % win_units == 0        # a block (or the cut last)
            split = e // win_units != o // win_units  # S[o] then P[e]
            n_cut = int((o + win_units - 1 >= F).sum())

            def at_e(t):
                return jnp.concatenate(
                    [t[:, win_units - 1::slide_units],
                     jnp.broadcast_to(t[:, F - 1:], (K_cap, n_cut))], axis=1)

            def at_o(t):
                return t[:, ::slide_units]

            tv, tr = comb_valid(
                at_o(sv) & jnp.asarray(~edge)[None, :], tmap(at_o, suf),
                at_e(pv) & jnp.asarray(edge | split)[None, :],
                tmap(at_e, pre))
            # 4. lane i picks (slot_i, round_i)
            pick = slots * o.size + rounds
            return tv.reshape(-1)[pick], tmap(lambda t: t.reshape(-1)[pick],
                                              tr)

        def fire_block(trees, tvalid, fire_plan):
            slots, starts, lens, wids, mask, group, eflat, keys = \
                plan_lanes(fire_plan, W, K_cap, F, win_units, slide_units,
                           grouped, key_words)
            with jax.named_scope(SCOPE_FIRE):
                if grouped:
                    g_table = plan_views(fire_plan, K_cap, True,
                                         key_words)[0]
                    qv, qr = jax.lax.cond(
                        g_table[G_CAP, 0] > 0,
                        lambda: by_group(trees, tvalid, slots, group,
                                         g_table),
                        lambda: by_lane(trees, tvalid, slots, starts, lens))
                elif fire_slides(W, K_cap, F):
                    # a count-based plan's ``group`` is the lanes' rounds
                    qv, qr = by_scan(trees, tvalid, slots, group, starts,
                                     mask)
                else:
                    qv, qr = by_lane(trees, tvalid, slots, starts, lens)
                qv = qv & mask
            # evict leaves consumed by the fired windows (masked lanes:
            # out of bounds)
            with jax.named_scope(SCOPE_EVICT):
                tvalid = tvalid.reshape(-1).at[eflat].set(
                    False, mode="drop").reshape(tvalid.shape)
            # output wid/key columns built ON DEVICE from the plan's
            # chunk rows: no device_put of their own at emit time
            key_out = (jnp.zeros((1,), jnp.int32) if keys is None
                       else join_key_words(keys, kd))
            return tvalid, qr, qv, wids, key_out

        return fire_block

    def _make_step(self, cap: int, donate: bool = True,
                   ingest_only: bool = False, W: Optional[int] = None):
        """``W``: the width of the program's fire block (_query_fns).

        The full program rebuilds the internal levels before its fire
        block. A time-based step over a ring that rebuilds by ranges
        (``rebuilds_by_ranges``) chooses, by the dirty ring ranges its
        plan carries (``RANGE_ROWS``: the panes written and evicted since
        the last rebuild, kept by the host in device order), between the
        rebuild of their ancestors alone (``rebuild_levels_by_ranges``)
        and the full rebuild, where a range is wider than the window,
        wraps the ring or is marked full (``rebuild_fits``); every other
        step rebuilds the whole forest.

        ``ingest_only=True`` builds the DEFERRED-REBUILD variant: lift
        + segmented scan + leaf scatter only — no level rebuild, no
        window queries, no eviction. Used for batches the host control
        plane already knows fire NOTHING (chunks empty): leaves stay
        current and the next firing program's rebuild covers every
        deferred batch at once (the panes they wrote widen the dirty
        range it goes by), so the per-batch rebuild cost is paid per
        FIRING batch only. Soundness: internal nodes are only ever read
        by fire queries, and every fire path rebuilds first (the full
        program in-program; the dataless path via _ensure_rebuilt)."""
        import jax
        import jax.numpy as jnp

        lift = self._lift_fn()
        combine = self.op.combine
        F = self.F
        K_cap = self.K_cap
        NNODES = 2 * F
        OOB = K_cap * NNODES  # scatter target for masked lanes (mode=drop)

        tmap = jax.tree_util.tree_map
        fire_block = self._query_fns(W)
        rebuild_levels = xla_rebuild_levels(combine, F)
        counted = self.op.win_type is WinType.CB
        key_words = self._key_words()
        by_ranges = not counted and rebuilds_by_ranges(F)
        rebuild_part = rebuild_levels_by_ranges(combine, F)

        def step(fields, comp, trees, tvalid, fire_plan):
            # 1. lift + sort + segmented scan. The host ships ONE
            # batch-sized column in the narrowest int dtype (_comp_dtype);
            # the sort order and the run boundaries are computed here, so
            # they overlap the host control plane of the next batch.
            # Time-based windows: the packed composite slot*F+leaf,
            # sentinel K_cap*F for late and padding lanes. Count-based
            # windows: the rows' SLOTS, and the composite is formed here
            # (cb_number_rows).
            vals = broadcast_scalar_fields(
                lift(fields), next(iter(fields.values())).shape[0])
            with jax.named_scope(SCOPE_SORT):
                big = jnp.int32(K_cap * F)  # sentinel: late + padding
                if counted:
                    order, sc = cb_number_rows(
                        comp, plan_views(fire_plan, K_cap, False,
                                         key_words)[0], K_cap, F)
                else:
                    order = jnp.argsort(comp, stable=True)
                    sc = comp[order].astype(jnp.int32)
                same_prev = jnp.concatenate(
                    [jnp.zeros((1,), bool), sc[1:] == sc[:-1]])
                is_end = jnp.concatenate(
                    [sc[1:] != sc[:-1],
                     jnp.ones((1,), bool)]) & (sc < big)
                # decode slot/leaf from the sorted composite (F is a
                # power of two, so these lower to shift/mask)
                flat_idx = (F + sc % F) * K_cap + sc // F
            svals = tmap(lambda a: a[order], vals)

            def seg_op(a, b):
                fa, sa = a
                fb, same_b = b
                merged = combine(fa, fb)
                out = tmap(lambda m, y: jnp.where(same_b, m, y), merged, fb)
                return out, sa & same_b

            with jax.named_scope(SCOPE_SCAN):
                scanned, _ = jax.lax.associative_scan(
                    seg_op, (svals, same_prev))

            # 2. scatter-combine segment tails into forest leaves
            with jax.named_scope(SCOPE_SCATTER):
                safe_idx = jnp.where(is_end, flat_idx, OOB)
                gather_idx = jnp.where(is_end, flat_idx, 0)
                leaf_valid = tvalid.reshape(-1)[gather_idx] & is_end
                cur_leaves = tmap(lambda t: t.reshape(-1)[gather_idx],
                                  trees)
                merged_all = combine(cur_leaves, scanned)
                new_leaves = tmap(
                    lambda m, sv: jnp.where(leaf_valid, m, sv),
                    merged_all, scanned)
                trees = tmap(
                    lambda t, nl: t.reshape(-1).at[safe_idx].set(
                        nl, mode="drop").reshape(t.shape),
                    trees, new_leaves)
                tvalid = tvalid.reshape(-1).at[safe_idx].set(
                    True, mode="drop").reshape(tvalid.shape)

            if ingest_only:
                # deferred rebuild: leaves are current, internal nodes
                # stale until the next firing/rebuild program; dummies
                # keep the output arity (callers never read them — the
                # host knew n_out == 0 before choosing this program)
                dummy = tmap(lambda a: jnp.zeros((1,), a.dtype), vals)
                return (trees, tvalid, dummy, jnp.zeros((1,), bool),
                        jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1,), jnp.int32))

            # 3. rebuild the internal levels: over the dirty ranges'
            # ancestors where they fit the window, else the whole forest
            with jax.named_scope(SCOPE_REBUILD):
                if by_ranges:
                    ranges = plan_views(fire_plan, K_cap, True,
                                        key_words)[0][RANGE_ROWS]
                    trees, tvalid = jax.lax.cond(
                        rebuild_fits(ranges, F), rebuild_part,
                        lambda t, v, _r: rebuild_levels(t, v),
                        trees, tvalid, ranges)
                else:
                    trees, tvalid = rebuild_levels(trees, tvalid)

            # 4.-6. fired-window queries, eviction of the leaves they
            # consumed, output wid/key columns (_query_fns)
            return (trees,) + fire_block(trees, tvalid, fire_plan)

        # trees/tvalid are DONATED: the leaf scatter and level rebuild
        # update the forest in place in HBM instead of copying the whole
        # forest every step (at 10k keys the forest is tens of MB per
        # field). Every caller reassigns self.trees/self.tvalid from the
        # program outputs — including the warm-up no-op runs.
        # donate=False is for surfaces that re-execute on fixed example
        # args (the driver's __graft_entry__.entry()).
        # instrumented_jit: (re)traces land on Compile_count with the
        # step signature, so the prewarm soak can assert compile-flat
        # streams for window stages exactly like fused map chains
        from ..monitoring.flightrec import instrumented_jit
        return instrumented_jit(
            step, self.stats, label=f"{self.stats.op_name}:step",
            program=PROG_STEP, donate_argnums=(2, 3) if donate else ())

    def _make_fire_step(self, W: Optional[int] = None):
        """Fire-only program: window queries (_query_fns) + leaf eviction,
        no lift/scan/scatter/rebuild. Used for drain iterations after the
        first per-batch step and for data-less firing (punctuation/EOS).

        Soundness of skipping the level rebuild: internal nodes are stale
        only where leaves were evicted after the last rebuild (a program
        runs after a step, or after _ensure_rebuilt, so no pane written
        since is unrebuilt: the replica's dirty written range is empty
        here, its dirty EVICTED range, ``_dirty_ev``, is what is stale),
        and those panes satisfy p_evicted >= next_fire_at_rebuild. The
        last rebuild may have been partial: it left every node agreeing
        with its leaves all the same (rebuild_levels_by_ranges), since
        a node over no dirty pane agrees already. Every queried
        pane satisfies p <= max_leaf < next_fire_at_rebuild + F (the
        _grow_ring span guard enforces this at arrival), so an evicted
        pane's ring slot can only be re-queried at pane p_evicted + F >
        max_leaf — excluded because every lane's length is clipped to
        its chunk's data, ``span`` (plan_lanes). The clip is also what
        keeps the invariant robust if F sizing ever changes
        (regression-tested).

        The argument is about a slot's RANGES, not about which program
        or which walk answers them, so it holds for the plan by rounds
        and for the walk by range as it did for slot order and the lane
        walk: a walk reads only nodes wholly inside its clipped range,
        a slot's window ``w + 1`` starts past every pane its window ``w``
        evicted (a program earlier, where the rounds split them; in the
        SAME program, which a width sized by the plan makes the common
        case, all queries read the forest as it stood before the
        program's one eviction scatter), and the walk by range reads
        the other slots' nodes of the same columns only into table rows
        that no lane of theirs picks.

        None of it concerns a count-based program that answers by
        sliding scan (_query_fns): the scan reads leaves and their
        validity, never an internal node, so no stale node can reach it,
        and validity alone ends a partial window."""
        # tvalid donated (in-place eviction); trees is read-only here
        from ..monitoring.flightrec import instrumented_jit
        return instrumented_jit(self._query_fns(W), self.stats,
                                label=f"{self.stats.op_name}:fire",
                                program=PROG_FIRE, donate_argnums=(1,))

    def _make_rebuild_step(self):
        """Standalone full-forest level rebuild: settles deferred
        (ingest-only) batches before a DATALESS fire — the fire-only
        program skips the rebuild by design and is only sound over a
        freshly rebuilt forest (see _make_fire_step). Traces the same
        ``xla_rebuild_levels`` as the full program."""
        from ..monitoring.flightrec import instrumented_jit
        return instrumented_jit(xla_rebuild_levels(self.op.combine, self.F),
                                self.stats,
                                label=f"{self.stats.op_name}:rebuild",
                                program=PROG_REBUILD,
                                donate_argnums=(0, 1))

    def _ensure_rebuilt(self) -> None:
        """Run the standalone rebuild iff ingest-only batches deferred
        it (idempotent: rebuilding from current leaves is always safe).
        Both the dirty flag and the forest belong to the commit stage,
        so in-flight commits must land before reading either."""
        self.dispatch.drain(forced=True)
        if not self._rebuild_dirty or self.trees is None:
            return
        from .ops_tpu import cached_compile
        prog = cached_compile(self._prog_cache, self.op._prog_lock,
                              ("rebuild", self.K_cap, self.F),
                              self._make_rebuild_step)
        self.trees, self.tvalid = prog(self.trees, self.tvalid)
        self.stats.device_programs_run += 1
        self.stats.rebuild_programs += 1
        self._rebuild_dirty = False
        self._rebuilt()
        self._dirty_all = True  # rebuild rewrote internal rows forest-wide

    def _rebuilt(self) -> None:
        """A rebuild was planned (a step's, in prep order) or has run
        (the standalone one, after a drain): no node is stale."""
        self._dirty_in = self._dirty_ev = None
        self._dirty_full = False

    @staticmethod
    def _widen(rng, lo: int, hi: int):
        return (lo, hi) if rng is None else (min(rng[0], lo), max(rng[1], hi))

    def _note_evicted(self, chunks) -> None:
        """Widen the evicted range by a program's chunks: a chunk evicts
        from its first window's start on, ``k`` slides at most (as far as
        its data goes; a wider range is only more to rebuild)."""
        if self.op.win_type is WinType.TB:
            _slots, start0, k, _wid0, _ml = chunks
            self._dirty_ev = self._widen(
                self._dirty_ev, int(start0.min()),
                int((start0 + k * self.slide_units).max()) - 1)

    def _plan_rebuild(self, pack) -> None:
        """The rebuild of a batch's step, planned in prep: a time-based
        plan carries the dirty ranges (``RANGE_ROWS``: the physical
        ``(start, length)`` of each, length ``F`` where the whole forest
        is dirty, ``(0, 0)`` where nothing is), and the ranges start
        anew, since the step rebuilds before it fires. Counts the
        rebuild, and whether the step goes by the ranges."""
        st = self.stats
        st.rebuild_programs += 1
        if self.op.win_type is WinType.TB:
            F = self.F
            ranges = plan_views(pack, self.K_cap, True,
                                self._key_words())[0][RANGE_ROWS]
            for row, rng in zip(ranges, (self._dirty_in, self._dirty_ev)):
                if self._dirty_full or (rng is not None
                                        and rng[1] - rng[0] >= F):
                    row[:] = (0, F)
                elif rng is not None:
                    row[:] = (rng[0] & (F - 1), rng[1] - rng[0] + 1)
            if rebuilds_by_ranges(F) and rebuild_fits(ranges, F):
                st.rebuild_partial_programs += 1
        self._rebuilt()

    # ==================================================================
    # host control plane
    # ==================================================================
    @property
    def _out_keys_by_slot(self) -> List[Any]:
        """The original key of every slot below the high-water mark (a
        free slot: the key that held it last)."""
        if self._obj_keys is not None:
            return self._obj_keys
        return self._keys_np[:self._keymap.n_slots].tolist()

    def _on_new_key(self, key, s: int) -> None:
        """KeySlotMap callback: per-slot bookkeeping for a fresh key (the
        per-key path: keys that are no ints, direct ``slot`` calls).
        RAISE-BEFORE-MUTATE: KeySlotMap.slot registers the key only when
        this returns, so a refusal (index-plane overflow on growth) must
        fire before any bookkeeping mutates — a caught-and-retried batch
        would otherwise find the key table shifted."""
        if s >= self.K_cap:
            # ``s`` is a free slot or the high-water mark, so one
            # doubling always covers it; validate the doubled plane
            # FIRST, and grow BEFORE any bookkeeping mutates (growth
            # itself can fail, e.g. device OOM reallocating the forest)
            self._check_index_plane(self.K_cap * 2)
            self._grow_keys()
        self._saw_new_key = True
        if self._keys_all_int and isinstance(key, int):
            self._keys_np[s] = key
        else:
            if self._obj_keys is None:   # the first key that is no int
                self._obj_keys = self._out_keys_by_slot
                self._keys_all_int = False
            self._place_obj_key(s, key)
        self._note_admitted(1)

    def _place_obj_key(self, s: int, key) -> None:
        keys = self._obj_keys
        if s < len(keys):
            keys[s] = key
        else:
            keys.extend([None] * (s - len(keys)) + [key])

    def _on_new_keys(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """KeySlotMap callback for a batch's new int keys, all at once
        (no Python call a key): grows the key table until it holds the
        highest slot, refusing BEFORE anything mutates, then writes the
        keys into the slot table."""
        cap = self.K_cap
        while cap <= int(slots.max()):
            cap *= 2
        if cap > self.K_cap:
            self._check_index_plane(cap)
            while self.K_cap < cap:
                self._grow_keys()
        self._saw_new_key = True
        if self._obj_keys is not None:
            for s, k in zip(slots.tolist(), keys.tolist()):
                self._place_obj_key(s, k)
        self._keys_np[slots] = keys
        self._note_admitted(len(keys))

    def _note_admitted(self, n: int) -> None:
        self.stats.keys_admitted += n
        self.stats.key_slots_live = len(self.slot_of_key) + n
        self._dir_version += 1

    def _reclaim(self, slots: np.ndarray) -> None:
        """Give back the slots among ``slots`` (just fired) none of whose
        windows that hold an event is left: ``max_leaf < next_fire``,
        _eligible's own ``has_data``. The fires that consumed its events
        evicted every leaf of such a row (a chunk evicts ``[start0,
        start0 + k * slide)`` up to ``max_leaf``), in programs that run
        before any step that could write a new key's leaves there, so
        nothing on the device needs clearing. ``fired``/``next_fire``
        stay as they are: a new key's registration re-anchors them."""
        dead = slots[self.max_leaf[slots] < self.next_fire[slots]]
        if dead.size:
            with self._st_keys(self._bid):
                self._reclaimed_wid = max(self._reclaimed_wid,
                                          int(self.fired[dead].max()))
                self._give_back(dead)

    def _give_back(self, dead: np.ndarray) -> None:
        """Forget the keys of ``dead`` slots and free the slots (rows a
        delta snapshot already holds dirty: a batch's rows, a fire's)."""
        self.max_leaf[dead] = -1
        self._keymap.release(
            self._keys_np[dead] if self._obj_keys is None
            else [self._obj_keys[s] for s in dead.tolist()])
        self.stats.keys_reclaimed += int(dead.size)
        self.stats.key_slots_live = len(self.slot_of_key)
        self._dir_version += 1

    def _chunk_keys(self, c_slots: np.ndarray):
        """The original keys of a plan's chunks, read when the plan is
        made: by the time its commit emits them a later batch's new key
        may hold a slot the plan gave back."""
        if self._obj_keys is None:
            return self._keys_np[c_slots]
        return [self._obj_keys[s] for s in c_slots.tolist()]

    def _slots_of(self, keys, keys_arr: np.ndarray, n: int) -> np.ndarray:
        return self._keymap.slots_of(keys, keys_arr, n)

    def _grow_keys(self) -> None:
        """BUILD-THEN-COMMIT: every fallible step (including the device
        reallocation of the doubled forest) runs into locals first; the
        replica mutates only after all of them succeeded, so a caught
        growth failure leaves fully consistent pre-growth state for the
        retry (which re-enters growth from scratch)."""
        import jax
        import jax.numpy as jnp
        # growth reads the CURRENT forest: deferred commits reassign
        # trees/tvalid (donation), so they must land first
        self.dispatch.drain(forced=True)
        old = self.K_cap
        new_cap = old * 2
        grown = {}
        for name, fill in (("next_fire", 0), ("fired", 0),
                           ("max_leaf", -1), ("count", 0),
                           ("_keys_np", 0)):
            arr = getattr(self, name)
            g = np.full(new_cap, fill, dtype=arr.dtype)
            g[:old] = arr
            grown[name] = g
        new_trees = new_tvalid = None
        if self.trees is not None:
            new_trees = jax.tree_util.tree_map(
                lambda t: jnp.zeros((2 * self.F, new_cap), t.dtype)
                .at[:, :old].set(t), self.trees)
            new_tvalid = jnp.zeros((2 * self.F, new_cap), bool
                                   ).at[:, :old].set(self.tvalid)
        self.K_cap = new_cap
        for name, g in grown.items():
            setattr(self, name, g)
        if new_trees is not None:
            self.trees, self.tvalid = new_trees, new_tvalid
        self._dirty_all = True  # geometry changed under the delta base
        self._dirty_full = True
        self.stats.key_capacity_growths += 1

    def _grow_ring(self, needed_span: int) -> None:
        """BUILD-THEN-COMMIT, like ``_grow_keys`` (F and the migrated
        forest commit together, after the fallible allocations)."""
        import jax
        import jax.numpy as jnp
        # same ordering rule as _grow_keys: the migration reads the
        # current forest, so deferred commits must land first
        self.dispatch.drain(forced=True)
        old_F = self.F
        new_F = old_F
        while needed_span >= new_F:
            new_F *= 2
        # prospective check BEFORE mutating F or the forest: a caught
        # refusal after mutation would leave a wrapped index plane that
        # no later per-batch guard re-checks
        self._check_index_plane(f=new_F)
        if self.trees is None:
            self.F = new_F
            return
        old_trees, old_valid = self.trees, self.tvalid
        new_trees = jax.tree_util.tree_map(
            lambda t: jnp.zeros((2 * new_F, self.K_cap), t.dtype), old_trees)
        new_tvalid = jnp.zeros((2 * new_F, self.K_cap), bool)
        src_rows, src_cols, dst_cols = [], [], []
        for _, s in self.slot_of_key.items():
            for p in range(int(self.next_fire[s]), int(self.max_leaf[s]) + 1):
                src_rows.append(s)
                src_cols.append(old_F + (p % old_F))
                dst_cols.append(new_F + (p % new_F))
        if src_rows:
            sr, sc, dc = (np.asarray(src_rows), np.asarray(src_cols),
                          np.asarray(dst_cols))
            new_trees = jax.tree_util.tree_map(
                lambda new, old: new.at[dc, sr].set(old[sc, sr]),
                new_trees, old_trees)
            new_tvalid = new_tvalid.at[dc, sr].set(old_valid[sc, sr])
        self.F = new_F
        self.trees, self.tvalid = new_trees, new_tvalid
        # only leaves were carried over: internal levels need a rebuild
        # before any fire-only program may query them
        self._rebuild_dirty = True
        self._dirty_full = True
        self._dirty_all = True  # geometry changed under the delta base

    def _ensure_forest(self, sample_fields) -> None:
        if self.trees is not None:
            return
        import jax
        import jax.numpy as jnp
        shapes = jax.eval_shape(self._lift_fn(), sample_fields)
        if not isinstance(shapes, dict):
            raise WindFlowError(f"{self.op.name}: lift must return a dict "
                                "of columns")
        self.trees = {name: jnp.zeros((2 * self.F, self.K_cap), sh.dtype)
                      for name, sh in shapes.items()}
        self.tvalid = jnp.zeros((2 * self.F, self.K_cap), bool)
        self._dirty_full = True

    # ------------------------------------------------------------------
    def prep_device_batch(self, batch: BatchTPU):
        """HOST-PREP stage of the dispatch pipeline: everything here runs
        on host metadata only (slot resolution, leaf bookkeeping, window
        fire decisions, fire-pack assembly) and never waits on a device
        result — so it overlaps the deferred device commits of earlier
        batches. Paths that must touch the replica's device forest
        (growth, program warm-up) drain the pipeline first."""
        op = self.op
        n = batch.size
        if op.win_type is WinType.CB:
            self.stats.prep_by_key_batches += 1     # see _prep_by_key
        if n == 0:
            return None
        self._ensure_forest(batch.fields)
        if op.key_field is not None and op.key_field in batch.fields:
            self._key_dtype = field_dtype(batch.fields, op.key_field)
        # fused prefix filter (FusedFfatReplica): rows it drops must not
        # exist for the control plane AT ALL — no key registration, no
        # max_leaf/next_fire advance, no CB count — exactly the rows the
        # unfused topology's filter stage compacts away before the
        # window operator ever sees them. Resolved here (prep time, one
        # small D2H of the mask) because the liveness quantities are
        # exact: deferring the mask to commit time would let phantom
        # rows fire windows early and mis-index CB leaves.
        keep = self._prefix_mask(batch)
        rowsel = None
        if keep is not None:
            n_kept = int(keep.sum())
            if n_kept < n:
                self.stats.inputs_ignored += n - n_kept
                if n_kept == 0:
                    return None
                rowsel = np.nonzero(keep)[0]
        self._bid = batch.bid
        keys, keys_arr = self.batch_keys_np(batch)
        if rowsel is not None:
            sub_arr = np.asarray(keys_arr)[rowsel]
            keys = (sub_arr if isinstance(keys, np.ndarray)
                    else [keys[i] for i in rowsel])
            keys_arr = sub_arr
            n_rows = len(rowsel)
        else:
            n_rows = n
        slots = self._slots_of(keys, keys_arr, n_rows)
        from ..checkpoint.delta import env_ckpt_delta
        if env_ckpt_delta() and n_rows:
            # every row this batch touches is dirty vs the delta base
            self._ckpt_dirty.update(np.unique(slots).tolist())
        if op.win_type is WinType.CB:
            return self._prep_by_key(batch, slots, rowsel)
        ts_rows = batch.ts_host[:n]
        if rowsel is not None:
            ts_rows = ts_rows[rowsel]
        leaves = ts_rows // op.pane_len
        # align brand-new keys to the first window containing their first
        # leaf: without this, an epoch-scale first timestamp would demand a
        # ring spanning all of absolute time (OOM via _grow_ring).
        # Gated on _saw_new_key when slide <= win: then registration sets
        # next_fire at or below the registering tuple's leaf (w0*slide <=
        # first_leaf - win + slide <= first_leaf), so that tuple is live
        # and max_leaf goes >= 0 in the same batch — a slot can only be
        # "fresh" (max_leaf<0) in its registration batch and steady state
        # skips the 16k-gather entirely. With GAP windows (slide > win)
        # the registering tuple can land in a gap and stay late, so the
        # alignment must re-run every batch (pre-gate behavior; regression
        # test: gap_windows_late_first_key_reanchor).
        born = None     # slots registered (or still untouched) here
        if self._saw_new_key or self.slide_units > self.win_units:
            self._saw_new_key = False
            fresh = self.max_leaf[slots] < 0
            if fresh.any():
                fslots = slots[fresh]
                fleaves = leaves[fresh]
                first_leaf = np.full(self.K_cap, np.iinfo(np.int64).max,
                                     dtype=np.int64)
                np.minimum.at(first_leaf, fslots, fleaves)
                sel = np.unique(fslots)
                new_mask = self.max_leaf[sel] < 0  # still untouched slots
                sel = sel[new_mask]
                # never below the furthest window a forgotten key had
                # reached (_reclaim): were this key one of them, come
                # back with a late event, its windows below are
                # delivered already and the event is late for them
                w0 = np.maximum(
                    self._reclaimed_wid, (first_leaf[sel] - self.win_units)
                    // self.slide_units + 1)
                self.next_fire[sel] = w0 * self.slide_units
                self.fired[sel] = w0
                born = sel
        nf = self.next_fire[slots]
        live = leaves >= nf
        n_live = int(live.sum())
        n_late = n_rows - n_live
        # unified late accounting: this host-side mask is the SAME
        # late/sentinel classification the packed composite below encodes
        # for the device program — export it instead of discarding it.
        # Every dropped row sits behind the fired-window frontier,
        # hence behind the watermark, so late_records ⊇ late_dropped and
        # Late_admitted = records - dropped stays exact
        st = self.stats
        late_mask = ts_rows < batch.wm
        if n_late:
            late_mask = late_mask | ~live
        n_late_seen = int(late_mask.sum())
        if n_late_seen:
            st.note_late(n_late_seen, n_late,
                         batch.wm - ts_rows[late_mask]
                         if st.hist_lateness is not None else None)
        if n_late:
            self.ignored += n_late
            self.stats.inputs_ignored += n_late
        if n_live:
            if (n_late == 0 and n_rows
                    and int(leaves[0]) >= self._leaf_frontier
                    and bool((leaves[1:] >= leaves[:-1]).all())):
                # monotone event time at or past every previously seen
                # leaf (the common in-order source pattern): the last
                # occurrence per slot carries its max leaf AND cannot
                # undercut an older per-slot max, so a plain fancy
                # assignment (last-write-wins for duplicate indices,
                # np.put semantics) replaces the much slower
                # np.maximum.at buffered scatter
                span = int((leaves - nf).max())
                if span >= self.F:
                    self._grow_ring(span)
                self.max_leaf[slots] = leaves
                lo = int(leaves[0])
                hi = self._leaf_frontier = int(leaves[-1])
            else:
                # masked forms avoid boolean fancy-index allocations; the
                # -1 sentinel is a no-op under maximum (max_leaf starts
                # at -1)
                masked_leaves = np.where(live, leaves, -1)
                span = int(np.where(live, leaves - nf, -1).max())
                if span >= self.F:
                    self._grow_ring(span)
                np.maximum.at(self.max_leaf, slots, masked_leaves)
                hi = int(masked_leaves.max())
                self._leaf_frontier = max(self._leaf_frontier, hi)
                lo = int(np.where(live, leaves, hi).min())
            # the panes this batch writes: its step's rebuild, or the
            # next one's where it fires nothing, goes by them
            self._dirty_in = self._widen(self._dirty_in, lo, hi)
        if born is not None and n_late:
            # a key none of whose rows is live (all behind the floor of
            # a new key's first window, or in a gap between windows)
            # holds no event: it keeps no slot, and registers anew with
            # its next row (the floor stays: no window of it fired)
            still = born[self.max_leaf[born] < 0]
            if still.size:
                with self._st_keys(self._bid):
                    self._give_back(still)

        cap = batch.capacity
        # packed composite (slot*F + leaf, sentinel M = late/padding) in
        # the narrowest int dtype: ONE array instead of separate
        # slot/leaf/live planes, the only batch-sized argument the host
        # builds for the program (int32 always holds it:
        # _check_index_plane at init/growth); the program sorts it.
        M, cdt = self._comp_dtype()
        comp_p = np.full(cap, M, dtype=cdt)
        packed = slots * self.F + (leaves & (self.F - 1))  # F is pow-2
        if n_late:
            packed = np.where(live, packed, M)
        if rowsel is None:
            comp_p[:n] = packed
        else:
            # prefix-dropped rows keep the sentinel: the in-program
            # segment plane treats them exactly like late/padding lanes
            comp_p[rowsel] = packed

        frontier = max(0, batch.wm - op.lateness) // op.pane_len
        return self._prep_step(batch.fields, batch.wm, cap, comp_p, frontier,
                               batch.bid)

    def _prep_by_key(self, batch: BatchTPU, slots: np.ndarray, rowsel):
        """The rest of a COUNT-BASED operator's prep, by the key: a
        slot's rows are numbered ``count .. count + n - 1`` in arrival
        order and the first of them that lie behind its ``next_fire``
        (gap windows, a re-registered key) are dropped, so two words a
        slot say everything of the batch's rows that the step needs
        (the plan's ``keyrows``, ``plan_views``: ``base``, ``skip``), and
        the step numbers the rows itself, in the sort it does anyway.
        The one pass by
        row here is the count of each slot's rows; ``slots`` (the
        surviving rows', where ``rowsel`` names the rows a fused prefix
        filter kept) is the one batch-sized plane built, with the
        sentinel ``K_cap`` on padding and on dropped rows, which so take
        no rank, no leaf and no count."""
        n_k = np.bincount(slots, minlength=self.K_cap)
        skip = np.clip(self.next_fire - self.count, 0, n_k)
        n_late = int(skip.sum())
        if n_late:
            # order-based drops: behind no watermark, all of them dropped
            self.stats.note_late(n_late, n_late)
            self.ignored += n_late
            self.stats.inputs_ignored += n_late
        live = np.nonzero(n_k > skip)[0]
        if live.size:
            last = self.count[live] + n_k[live] - 1
            span = int((last - self.next_fire[live]).max())
            if span >= self.F:
                self._grow_ring(span)
            self.max_leaf[live] = last
        keyrows = np.stack([self.count & (self.F - 1), skip]
                           ).astype(np.int32)
        self.count += n_k
        cap = batch.capacity
        M, cdt = self._comp_dtype()
        slots_p = np.full(cap, M, dtype=cdt)
        slots_p[slice(batch.size) if rowsel is None else rowsel] = slots
        return self._prep_step(batch.fields, batch.wm, cap, slots_p, None,
                               batch.bid, keyrows)

    # ------------------------------------------------------------------
    def _eligible(self, frontier, partial: bool):
        """``(slots, k)``: the slots with windows to fire now and the
        count of consecutive eligible windows of each, from its
        ``next_fire`` on. One numpy pass over the live slot table
        (C-speed even at 10^5 keys; the reference instead walks its key
        descriptor map in a host loop,
        ``ffat_replica_gpu.hpp:870-1019``). Reads, advances nothing."""
        ns = self._keymap.n_slots    # free slots read as holding no data
        empty = (np.zeros(0, np.int64),) * 2
        if ns == 0:
            return empty
        nf = self.next_fire[:ns]
        ml = self.max_leaf[:ns]
        has_data = ml >= nf
        if partial:
            k = (ml - nf) // self.slide_units + 1
        elif self.op.win_type is WinType.TB:
            if frontier is None:
                return empty
            k_front = ((int(frontier) - self.win_units - nf)
                       // self.slide_units + 1)
            k = np.minimum((ml - nf) // self.slide_units + 1, k_front)
        else:  # CB fires purely by count
            k_cnt = ((self.count[:ns] - self.win_units - nf)
                     // self.slide_units + 1)
            k = np.minimum((ml - nf) // self.slide_units + 1, k_cnt)
        k = np.where(has_data, k, 0)
        slots = np.nonzero(k > 0)[0]
        return slots, k[slots]

    def _take(self, slots, k):
        """Take the next ``k`` windows of each of ``slots``: advances
        ``next_fire``/``fired`` and returns the chunk ARRAYS (slots,
        start0, k, wid0, max_leaf), a chunk a slot with ``k > 0``."""
        keep = k > 0
        if not keep.all():
            slots, k = slots[keep], k[keep]
        start0 = self.next_fire[slots]
        wid0 = self.fired[slots]
        self.next_fire[slots] += k * self.slide_units
        self.fired[slots] += k
        if self._ckpt_dirty or self._delta_base is not None:
            # firing advances bookkeeping and evicts ring panes
            self._ckpt_dirty.update(slots.tolist())
        chunks = slots, start0, k, wid0, self.max_leaf[slots]
        if self._reclaims:
            self._reclaim(slots)
        return chunks

    def _clip(self, k: np.ndarray, budget: int) -> np.ndarray:
        """Windows taken of each slot's ``k`` eligible ones under a
        budget, a slot's windows in ``wid`` order either way:

        - time-based windows by ROUNDS: every firing slot gives its
          first ``min(k, r)`` windows for the largest ``r`` that fits,
          and what is left of the budget goes to round ``r + 1`` in slot
          order (so a program is full while windows remain, and where
          even one round overflows, this is the slot-order clip within
          it). Window ``w`` of every key is the same ring range, so a
          program holds a few distinct ranges, in the steady state and
          in the end-of-stream flush alike, and the fire query walks
          each once (_query_fns);
        - count-based windows in slot order, clipped where the
          cumulative sum crosses the budget (their ranges are per key
          anyway)."""
        if int(k.sum()) <= budget:
            return k
        if self.op.win_type is WinType.TB:
            return self._take_by_rounds(k, budget)
        # clip the chunk sequence where the cumsum crosses
        return np.maximum(0, np.minimum(k, budget - (np.cumsum(k) - k)))

    @staticmethod
    def _take_by_rounds(k: np.ndarray, budget: int) -> np.ndarray:
        """Windows taken of each slot's ``k`` eligible ones when their
        sum exceeds ``budget``: ``min(k, r)`` for the largest ``r`` with
        ``sum(min(k, r)) <= budget``, then one more for the first slots
        that still have one, until the budget is full."""
        ks = np.sort(k)
        n = ks.size
        csum = np.cumsum(ks)
        # sum(min(k, ks[i])): non-decreasing in i
        full = csum + (n - 1 - np.arange(n)) * ks
        i = int(np.searchsorted(full, budget, side="right"))  # < n
        r = (budget - (int(csum[i - 1]) if i else 0)) // (n - i)
        take = np.minimum(k, r)
        more = np.nonzero(k > r)[0][:budget - int(take.sum())]
        take[more] += 1
        return take

    def _range_words(self, start0, k, end):
        """``(words, rounds)`` of the lanes of chunks that start at pane
        ``start0``, hold ``k`` windows and end their data before pane
        ``end``: a lane's ring range ``(start_phys, length)`` as ONE word,
        ``start_phys * F + length`` (lengths <= win_units < F: the words
        sort as the pairs do), and its round (0 for a chunk's first
        window). A length is clipped to the slot's data, as the program
        clips it (plan_lanes). Where every chunk takes one window the
        lanes are the chunks, their rounds 0, and nothing is repeated."""
        su, F = self.slide_units, self.F
        if int(k.max()) == 1:
            if not k.all():
                start0, end = start0[k > 0], end[k > 0]
            rnd, starts = 0, start0
        else:
            rnd = np.arange(int(k.sum())) - np.repeat(np.cumsum(k) - k, k)
            starts = np.repeat(start0, k) + rnd * su
            end = np.repeat(end, k)
        return ((starts & (F - 1)) * F
                + np.minimum(self.win_units, end - starts)), rnd

    def _pack_fire_arrays(self, chunks, W: int, keys, pairs):
        """The flat int32 fire plan (``plan_views``) of a program of
        width ``W``: its chunk rows, with their original ``keys``
        (_chunk_keys) where the program builds the key column
        (_plan_keys), and for a time-based plan the ring ranges to answer
        it by, ``pairs`` (sorted words of _range_words; None: a blank
        table, its count 0, and the program walks by lane). Pure numpy,
        a row a firing slot; ONE buffer, so one program argument from
        the host and one transfer a launch. Returns the buffer and the
        count of ranges in its table."""
        c_slots, c_start0, c_k, c_wid0, c_ml = chunks
        F, n, kw = self.F, c_k.size, self._key_words()
        pack = np.zeros(self._plan_len(W), dtype=np.int32)
        head, rows, total = plan_views(
            pack, self.K_cap, self.op.win_type is WinType.TB, kw)
        total[0] = c_k.sum()
        rows[:5, :n] = (c_slots, c_start0 & (F - 1), c_k, c_wid0,
                        c_ml + 1 - c_start0)
        if kw and isinstance(keys, np.ndarray):
            rows[5:, :n] = key_words_of(keys, kw)
        if pairs is None:
            return pack, 0
        head[:pairs.size, 0] = pairs // F
        head[:pairs.size, 1] = pairs % F
        head[G_CAP, 0] = pairs.size
        return pack, pairs.size

    def _plan_program(self, slots, k):
        """ONE program, from the eligible windows ``k`` of ``slots``:
        ``(chunks, n_out, pack, n_groups, W, keys)`` (_programs), the
        windows taken advanced past, and how many of each slot's it
        took.

        The program is ``W_wide`` lanes and takes all that is eligible,
        clipped to that width where it overflows (_clip). A count-based
        program carries no ranges: it walks by lane or answers by
        sliding scan, by its static shapes (``fire_slides``). A
        time-based one stays a program BY RANGE: where its lanes hold
        more than ``G_CAP`` distinct ranges it is closed at the last
        WHOLE round that keeps it within ``G_CAP`` (a round: window
        ``j`` of every firing slot; the rounds left make the next
        program), and ``Fire_range_cuts`` counts it. Only where the
        first round alone holds more (a ragged plan: sparse keys, every
        ``max_leaf`` different, about as many ranges as lanes) does the
        program walk by lane, and then at ``W_cap``: a lane walk costs
        by the lane, masked or live, so it is never widened.
        The ranges are reckoned from the chunks, never by the lane:
        where every chunk takes one window (a slide that closes once a
        batch) the chunks are their own lanes, else from the CLASSES of
        chunks whose lanes hold the same ranges round for round.

        The costs behind the rule (my chip runs, PR 30, PERF.md section
        6; a 302 MB forest): a program by range 1.4 ms whatever it
        holds, 0.075 ms a range, and its picks and eviction 0.05 us a
        lane of its width, masked or live (1.6 ms at 32,768); by lane
        2.5 us a lane (PR 28). So one whole round by range beats the
        lane walk from about a thousand firing slots on, and below that
        the device's difference (under 3 ms a program) is less than a
        program costs the host (2 to 4 ms of Python): fewer programs
        win either way."""
        su = self.slide_units
        start0, end = self.next_fire[slots], self.max_leaf[slots] + 1
        for W in dict.fromkeys((self.W_wide, self.W_cap)):
            take = self._clip(k, W)
            if self.op.win_type is WinType.CB:
                pairs = None    # ranges per key: no range table
                break
            if int(take.max()) == 1:
                words, rnd = self._range_words(start0, take, end)
            else:
                # CLASSES of chunks whose lanes hold the same ranges
                # round for round: same start, same data end (as far as
                # it clips a lane), same count. Ranges are counted over
                # the classes' lanes, a few hundred where the plugs fire
                # in step, not over the program's tens of thousands
                reach = (int(take.max()) - 1) * su + self.win_units
                _, i_s = np.unique(start0, return_inverse=True)
                _, i_e = np.unique(np.minimum(end - start0, reach),
                                   return_inverse=True)
                _, rep = np.unique(
                    (i_s * (int(i_e.max()) + 1) + i_e) * (W + 1) + take,
                    return_index=True)
                words, rnd = self._range_words(start0[rep], take[rep],
                                               end[rep])
            pairs = np.unique(words)
            if pairs.size <= G_CAP:
                break
            # the round in which each range first appears: rounds below
            # the (G_CAP + 1)-th smallest hold at most G_CAP ranges
            first = np.full(pairs.size, W, dtype=np.int64)
            np.minimum.at(first, np.searchsorted(pairs, words), rnd)
            r = int(np.partition(first, G_CAP)[G_CAP])
            if r:
                pairs = pairs[first < r]
                take = np.minimum(take, r)
                self.stats.fire_range_cuts += 1
                break
        else:
            pairs = None  # ragged: by lane, at W_cap
        chunks = self._take(slots, take)
        keys = self._chunk_keys(chunks[0])
        return (chunks, int(take.sum())) + self._pack_fire_arrays(
            chunks, W, keys, pairs) + (W, keys), take

    def _key_words(self) -> int:
        """int32 words a key takes in a plan's chunk rows (``plan_views``):
        none where the operator has no named key field, else by the key
        column's dtype (two for a key wider than 32 bits)."""
        if self.op.key_field is None:
            return 0
        return 2 if np.dtype(self._key_dtype).itemsize > 4 else 1

    def _plan_keys(self) -> bool:
        """Whether a fired batch's key column is the one its program
        builds from the keys its plan carries (int keys with a named key
        field); keys that are no ints are built on the host."""
        return self._keys_all_int and self.op.key_field is not None

    def _fit_width(self, total: int) -> bool:
        """Grow ``W_wide`` for a plan of ``total`` windows that has
        outgrown it, where the operator is time-based with no budget
        given (a budget is a cap): to the capacity bucket that holds the
        plan, never past the capacity of the widest input batch (a
        result batch no wider than the batch that made it). Like
        ``K_cap`` and ``F`` it only grows, and the caller warms the new
        shapes (a width is a compiled shape). True where it grew."""
        if self.op.budget_given or self.op.win_type is WinType.CB:
            return False
        W = min(bucket_capacity(total), max(self.W_cap, self._cap_seen))
        if W <= self.W_wide:
            return False
        self.W_wide = W
        return True

    def _plan_len(self, W: int) -> int:
        """Words of the plan buffer of a program ``W`` lanes wide."""
        return plan_len(W, self.K_cap, self.op.win_type is WinType.TB,
                        self._key_words())

    def _zero_fire(self, W: int):
        """Device-resident all-zero fire plan (cached per length: zero
        steady-state transfer): a time-based step that fires nothing,
        and every warm-up run. A count-based step's buffer is never it:
        it carries the batch's ``keyrows`` whatever fires."""
        n = self._plan_len(W)
        z = self._zero_fire_cache.get(n)
        if z is None:
            import jax
            z = self._zero_fire_cache[n] = jax.device_put(
                np.zeros(n, dtype=np.int32))
        return z

    def _fire_key(self):
        return ("fire", self.K_cap, self.F, str(self._key_dtype))

    # a program's fire width is a constant it is built with (the chunk
    # rows of its plan do not tell it): its cache key ends with it
    def _fire_step(self, W: int):
        from .ops_tpu import cached_compile
        return cached_compile(self._prog_cache, self.op._prog_lock,
                              self._fire_key() + (W,),
                              lambda: self._make_fire_step(W))

    def _full_step(self, ckey, cap: int, W: int):
        from .ops_tpu import cached_compile
        return cached_compile(self._prog_cache, self.op._prog_lock,
                              ckey + (W,), lambda: self._make_step(cap, W=W))

    def _warm_fire_step(self) -> None:
        """Compile the fire-only program EAGERLY (masked no-op runs) at
        the widths it is run at, W_cap and W_wide: its first real use is
        mid-stream on a fire burst, and a ~0.5s compile there would land
        inside the measured/latency-critical path instead of startup."""
        if self.trees is None:
            return
        fkey = self._fire_key()
        for W in sorted({self.W_cap, self.W_wide}):
            if (fkey, W) in self._warm_shapes:
                continue  # e.g. a new batch-capacity bucket
            # all-masked no-op run (both queries compile with the
            # program, whichever runs); tvalid is DONATED: reassign it
            self.tvalid, *_ = self._fire_step(W)(
                self.trees, self.tvalid, self._zero_fire(W))
            self._warm_shapes.add((fkey, W))

    def _warm_programs(self, cap, ckey, ikey, fields) -> None:
        """Compile every program variant of a capacity bucket with no-op
        sentinel runs (every lane the composite's sentinel, zero fire
        args): the full step at each fire width (W_cap, W_wide), the
        ingest-only deferred-rebuild step, the
        fire-only drain step, and the standalone rebuild; called again
        when W_wide has grown, it runs the new shapes alone. All runs
        are semantic no-ops on the forest (sentinel rows drop, rebuild
        is idempotent); trees/tvalid are DONATED, so each run reassigns
        them."""
        from .ops_tpu import cached_compile
        istep = cached_compile(
            self._prog_cache, self.op._prog_lock, ikey,
            lambda: self._make_step(cap, ingest_only=True))
        self._warm_fire_step()
        rkey = ("rebuild", self.K_cap, self.F)
        rb = None if rkey in self._prog_cache else cached_compile(
            self._prog_cache, self.op._prog_lock, rkey,
            self._make_rebuild_step)  # cap-independent: a later capacity
        # bucket must not pay a redundant full-forest rebuild execution
        M, cdt = self._comp_dtype()
        comp_s = np.full(cap, M, dtype=cdt)  # all-sentinel lanes
        for W in sorted({self.W_cap, self.W_wide}):
            if (ckey, W) in self._warm_shapes:
                continue
            (self.trees, self.tvalid, *_) = self._full_step(ckey, cap, W)(
                fields, comp_s, self.trees, self.tvalid,
                self._zero_fire(W))
            self._warm_shapes.add((ckey, W))
        if (ikey, self.W_cap) not in self._warm_shapes:
            (self.trees, self.tvalid, *_) = istep(
                fields, comp_s, self.trees, self.tvalid,
                self._zero_fire(self.W_cap))
            self._warm_shapes.add((ikey, self.W_cap))
        if rb is not None:
            self.trees, self.tvalid = rb(self.trees, self.tvalid)

    def _prewarm_entry(self):
        """The operator whose schema and input edge this replica's batches
        have (the fused-chain replica receives the CHAIN ENTRY's, not the
        window op's declared one)."""
        return self.op

    def prewarm(self, caps) -> Optional[int]:
        """``PipeGraph.with_prewarm`` hook: compile every program
        variant (full step at its fire width, ingest-only, fire-only,
        standalone rebuild) per bucket capacity BEFORE the stream
        starts, so ragged streams hopping between capacity buckets never
        pay a mid-stream compile. Needs a declared schema — the forest
        shape comes from ``eval_shape`` of the lift over schema-dtyped
        zeros, and the key dtype from the schema's key column."""
        entry = self._prewarm_entry()
        sch = entry.schema
        if sch is None:
            return None
        from .ops_tpu import prewarm_zero_fields
        kf = self.op.key_field
        if kf is not None and kf in sch.fields:
            # the dtype the column has on the device (field_dtype's):
            # the programs build their key column in it
            from jax.dtypes import canonicalize_dtype
            self._key_dtype = np.dtype(canonicalize_dtype(sch.fields[kf]))
        warmed = 0
        for cap in caps:
            fields = prewarm_zero_fields(entry, cap)
            self._ensure_forest(fields)
            ckey, ikey = self._step_keys(cap)
            if (ckey + (self.W_cap,) in self._prog_cache
                    and ikey in self._prog_cache):
                continue
            self._warm_programs(cap, ckey, ikey, fields)
            warmed += 1
        return warmed

    def _step_keys(self, cap: int):
        """(full-step, ingest-only) program cache keys for one capacity
        bucket — the SINGLE definition shared by the per-batch path and
        ``prewarm`` (a key drift between them would compile a program
        nobody reuses and defeat the compile-flat guarantee). The chain
        tag pins fused-prefix variants to their own cache rows."""
        tag = self._chain_tag()
        ckey = ("step", cap, self.K_cap, self.F, str(self._key_dtype), tag)
        ikey = ("ingest", cap, self.K_cap, self.F, tag)
        return ckey, ikey

    def _prep_step(self, fields, wm, cap, comp_p, frontier, bid: int = 0,
                   keyrows=None):
        """Host half of the per-batch step: program warm-up, the ENTIRE
        fire plan — every program's chunk arrays and packed plan,
        computed up front because the planner reads host metadata
        only (no control decision ever waits on a device result).
        ``keyrows``: a count-based batch's per-slot words
        (_prep_by_key), which ride the step's plan buffer, an empty plan
        where the step fires nothing (so the cached all-zero plan never
        serves a count-based step). Returns the device-commit thunk for
        the dispatch pipeline."""
        ckey, ikey = self._step_keys(cap)
        self._cap_seen = max(self._cap_seen, cap)

        def warm():
            # the warm-up's no-op runs consume the live forest
            # (donation), so in-flight commits land first
            self.dispatch.drain(forced=True)
            self._warm_programs(cap, ckey, ikey, fields)

        if (ikey not in self._prog_cache
                or (ckey, self.W_wide) not in self._warm_shapes):
            # first batch of this capacity bucket (or of a width grown
            # by a dataless fire): compile EVERY program variant now
            # (full at each width, ingest-only, fire-only, standalone
            # rebuild) so no later batch — firing or not — pays a
            # mid-stream compile
            warm()
        with self._st_fireplan(bid):
            plan = []
            for prog in self._programs(frontier, False, warm):
                if not plan:     # the step: it rebuilds, then it fires
                    self._plan_rebuild(prog[2])
                self._note_evicted(prog[0])
                plan.append((not plan,) + prog)
        if not plan:
            # nothing fireable: the ingest-only program (its entry in
            # the plan is the buffer it takes, no tuple), its rebuild
            # DEFERRED to the next firing/rebuild program. Fire args are
            # unused in that variant but still traced: pin the W_cap
            # shape so a grown W_wide never retraces it
            plan = [self._zero_fire(self.W_cap) if keyrows is None
                    else np.zeros(self._plan_len(self.W_cap), np.int32)]
        if keyrows is not None:
            first = plan[0]
            plan_views(first[3] if isinstance(first, tuple) else first,
                       self.K_cap, False, self._key_words())[0][:] = keyrows
        return lambda: self._commit_step(fields, wm, comp_p, ckey, ikey,
                                         plan, bid)

    def _programs(self, frontier, partial: bool, warm):
        """The programs that fire what is eligible now, one ``(chunks,
        n_out, pack, n_groups, W, keys, owed)`` at a time, each advanced
        past as it is yielded (so a caller may run one, and a snapshot
        between two is consistent). ``keys``: the chunks' original keys
        (_chunk_keys). ``owed``: the lowest window id, over all slots,
        that this drain still has to fire AFTER this program, None where
        it is the last (what bounds the watermark of the batch the
        program emits: _emit_windows; always None for count-based
        windows, whose rows carry no window time). ``warm()`` compiles
        the shapes of a width that has just grown.

        The eligible windows are found once; everything leaves in ONE
        program where it fits the width and, time-based, ``G_CAP``
        ranges (_plan_program), in the step itself where there is one,
        and a time-based width with no budget follows the plans
        (_fit_width).

        Soundness of many rounds in one program (eight consecutive
        windows of every slot, each evicting what the next would have
        read): a program's queries ALL read the forest as it stood
        before the program's one eviction scatter, and past programs'
        evictions are no concern of window ``w + 1`` whichever program
        holds it, because its clipped range starts a slide past where
        ``w`` started and ``w`` evicted only the panes before that
        (see _make_fire_step: the argument is about ranges, not about
        widths)."""
        slots, k = self._eligible(frontier, partial)
        total = int(k.sum())
        # the end-of-stream flush keeps the width it has: nothing follows
        # it that could use a new compiled shape, here or downstream (a
        # wider result batch is a new shape of every program after this
        # operator too), and the compiles would be the flush's whole cost
        if not partial and total > self.W_wide and self._fit_width(total):
            warm()
        timed = self.op.win_type is WinType.TB
        while slots.size:
            prog, take = self._plan_program(slots, k)
            k = k - take
            slots, k = slots[k > 0], k[k > 0]
            yield prog + (int(self.fired[slots].min())
                          if timed and slots.size else None,)

    def _commit_step(self, fields, wm, comp_p, ckey, ikey, plan,
                     bid: int) -> None:
        """Device half: runs the planned program sequence in order and
        emits each iteration's windows. Reads ``self.trees``/
        ``self.tvalid`` at COMMIT time — earlier queued commits reassign
        them through donation — and owns the ``_rebuild_dirty`` flag
        updates: they must land in DEVICE order (a later batch's prep
        running before this commit must not see, or clobber, a stale
        flag)."""
        for entry in plan:
            if not isinstance(entry, tuple):
                # ingest-only (the entry is its plan buffer: _prep_step):
                # leaves current, internal nodes stale until the next
                # firing/rebuild program (the rebuild cost is
                # batch-size-independent — the dominant per-batch term of
                # the low-cardinality small-batch regime)
                (self.trees, self.tvalid, *_) = self._prog_cache[ikey](
                    fields, comp_p, self.trees, self.tvalid, entry)
                self._rebuild_dirty = True
                self.stats.device_programs_run += 1
                continue
            is_first, chunks, n_out, pack, n_groups, budget, keys, owed = entry
            if is_first:
                # full program: lift + scan + scatter + rebuild + fire
                (self.trees, self.tvalid, qr, qv, wid_dev,
                 key_dev) = self._prog_cache[ckey + (budget,)](
                    fields, comp_p, self.trees, self.tvalid, pack)
                self._rebuild_dirty = False  # in-program rebuild covers
                # every deferred ingest-only batch (the panes they wrote
                # are in the dirty range its plan carries: _plan_rebuild)
                self._dirty_all = True  # ... and rewrote internal rows
            else:
                # drain iterations: fire-only program (no rebuild)
                self.tvalid, qr, qv, wid_dev, key_dev = self._fire_step(
                    budget)(self.trees, self.tvalid, pack)
            self.stats.device_programs_run += 1
            self._emit_windows(wm, chunks, keys, owed, n_out, qr, qv,
                               wid_dev, key_dev, budget, n_groups, bid)

    def _emit_windows(self, wm, chunks, c_keys, owed, n_out, qr, qv,
                      wid_dev, key_dev, W: int, n_groups: int,
                      cause: int = 0) -> None:
        """``c_keys``, ``owed``: see _programs. ``W``: the width of the
        program that ran (its lanes, live or masked). ``n_groups``: the
        distinct ring ranges it answered by range, 0 where it walked by
        lane or answered by sliding scan (count-based windows: by the
        rule the program was traced by, ``fire_slides``). ``cause``: the
        id of the input batch whose commit fired these windows (0 for a
        dataless fire: a punctuation or EOS made them).

        Event time of what leaves (this plane's own rule, PARITY.md): a
        row of a time-based window carries the last instant inside its
        window, ``wid * slide + win - 1``, and the batch's watermark is
        ``wm`` lowered to one less than the earliest window END among
        the batch's own rows and the rows the drain still owes: a row
        is never behind the watermark it travels with, and no watermark
        passes a window whose rows are still to come (rounds split over
        programs, a budget given, ``G_CAP`` cuts), so a window operator
        downstream drops none as late. The consumer learns ``wm`` itself
        with the next batch or punctuation. Count-based rows have no
        window time: they carry ``wm``."""
        import jax

        op = self.op
        self.stats.fire_programs += 1
        self.stats.fire_lanes += W
        self.stats.windows_fired += n_out
        _slots, _st, c_k, c_w0, _ml = chunks
        # the host's plan is the chunk rows; the program expands them
        self.stats.fire_plan_rows += c_k.size
        if int(c_k.max()) == 1:
            self.stats.fire_one_round_plans += 1
        if op.win_type is WinType.CB and fire_slides(W, self.K_cap, self.F):
            self.stats.fire_sliding_programs += 1
        if n_groups:
            self.stats.fire_grouped_programs += 1
            self.stats.fire_groups += n_groups
        fields = dict(qr)
        fields["valid"] = qv
        fields["wid"] = wid_dev  # built in-program: no device_put here
        # a row's key is its chunk's: the batch carries them by chunk
        # and a consumer that reads ``host_keys`` expands them then
        # (most read their own column, or none)
        out_keys = ChunkedKeys(c_keys, c_k)
        if op.key_field is not None:
            if self._plan_keys():
                fields[op.key_field] = key_dev  # from the plan's keys
            else:
                # build directly in the key column's dtype (float keys
                # must not round-trip through int64)
                kd = self._key_dtype
                key_col = np.zeros(W, dtype=kd)
                key_col[:n_out] = out_keys.expand()
                fields[op.key_field] = jax.device_put(key_col)
        out_schema = TupleSchema(
            {name: np.dtype(v.dtype) for name, v in fields.items()})
        if op.win_type is WinType.TB:
            first = int(c_w0.min()) if owed is None else min(
                int(c_w0.min()), owed)
            wm = min(wm, first * op.slide_len + op.win_len - 1)
        ts = np.full(W, wm, dtype=np.int64)
        if op.win_type is WinType.TB:
            # lane i of chunk c is its window wid0 + (i - the windows of
            # the chunks before c)
            wids = np.arange(n_out) + np.repeat(c_w0 - (np.cumsum(c_k) - c_k),
                                                c_k)
            ts[:n_out] = wids * op.slide_len + (op.win_len - 1)
        out = BatchTPU(fields, ts, n_out, out_schema, wm, out_keys)
        # the keys are this operator's: a consumer keyed by another
        # field reads its own column (BatchTPU.keys_for)
        out.key_origin = own_key_spec(op)
        out.bid = next_batch_id()  # a new batch: the fire made it
        out.cause = cause
        self._emit_batch(out)

    # ------------------------------------------------------------------
    def _fire_dataless(self, frontier, partial: bool) -> None:
        """Watermark/EOS made windows fireable without new data: run ONLY
        the fire-only program (no lift/scan/rebuild at all) — after
        settling any rebuild deferred by ingest-only batches, since the
        fire-only program is sound only over a rebuilt forest."""
        if self.trees is None:
            return
        # ordering: windows of deferred batches must emit before any
        # dataless firing (handle_msg/terminate drain already, but
        # direct drivers — bench, profile scripts — reach here too)
        self.dispatch.drain(forced=True)
        self._bid = 0
        for chunks, n_out, pack, n_groups, W, keys, owed in self._programs(
                frontier, partial, self._warm_fire_step):
            self._ensure_rebuilt()
            self._note_evicted(chunks)
            self.tvalid, qr, qv, wid_dev, key_dev = self._fire_step(W)(
                self.trees, self.tvalid, pack)
            self.stats.device_programs_run += 1
            self._emit_windows(self.cur_wm, chunks, keys, owed, n_out, qr,
                               qv, wid_dev, key_dev, W, n_groups)

    def on_punctuation(self, wm: int) -> None:
        if self.op.win_type is WinType.TB:
            frontier = (max(0, self.cur_wm - self.op.lateness)
                        // self.op.pane_len)
            self._fire_dataless(frontier, partial=False)
        super().on_punctuation(wm)

    def flush_on_termination(self) -> None:
        self._fire_dataless(None, partial=True)

    # ------------------------------------------------------------------
    # checkpointing (windflow_tpu.checkpoint): the replica's whole
    # processing state is the key map, the per-slot host bookkeeping
    # arrays, and the device forest — one device_get per tree field
    # (array-shaped state keeps the snapshot a transfer, not a
    # serializer). Device-side caches (zero-fire constants) and
    # compiled programs rebuild lazily after restore.
    def snapshot_state(self) -> dict:
        import jax
        from ..checkpoint import delta as ckpt_delta

        st = super().snapshot_state()  # drains the dispatch queue
        ctx = ckpt_delta.snapshot_ctx()
        if (self.trees is not None and not self._dirty_all
                and self._base_geom == (self.K_cap, self.F, True)
                and ckpt_delta.delta_eligible(
                    self._delta_base, self._snaps_since_full, ctx)):
            self._snaps_since_full += 1
            st["ffat"] = self._snapshot_ffat_delta()
            return st
        st["ffat"] = {
            "slot_of_key": dict(self.slot_of_key),
            "out_keys_by_slot": list(self._out_keys_by_slot),
            "free_slots": list(self._keymap.free),
            "reclaimed_wid": self._reclaimed_wid,
            "K_cap": self.K_cap, "F": self.F,
            "next_fire": self.next_fire.copy(),
            "fired": self.fired.copy(),
            "max_leaf": self.max_leaf.copy(),
            "count": self.count.copy(),
            "keys_np": self._keys_np.copy(),
            "keys_all_int": self._keys_all_int,
            "key_dtype": self._key_dtype,
            "saw_new_key": self._saw_new_key,
            "leaf_frontier": self._leaf_frontier,
            "rebuild_dirty": self._rebuild_dirty,
            "ignored": self.ignored,
            # slot-major, as a snapshot holds the forest
            "trees": (None if self.trees is None else
                      jax.tree_util.tree_map(
                          lambda v: np.ascontiguousarray(np.asarray(v).T),
                          jax.device_get(self.trees))),
            "tvalid": (None if self.tvalid is None
                       else np.ascontiguousarray(
                           np.asarray(jax.device_get(self.tvalid)).T)),
        }
        if ctx is not None and ckpt_delta.env_ckpt_delta():
            # this full capture is the new delta baseline (capture runs
            # post-drain, so no in-flight commit can race the reset)
            self._delta_base = ctx.ckpt_id
            self._base_geom = (self.K_cap, self.F, self.trees is not None)
            self._base_dirver = self._dir_version
            self._snaps_since_full = 0
            self._ckpt_dirty = set()
            self._dirty_all = False
        return st

    def _snapshot_ffat_delta(self) -> dict:
        """Delta against the last full snapshot: only the dirty slot
        rows of every per-slot array + forest plane, plus the (small)
        replaced bookkeeping fields."""
        import jax
        import jax.numpy as jnp
        from ..checkpoint import delta as ckpt_delta

        sl = np.asarray(sorted(self._ckpt_dirty), dtype=np.int64)
        rows = {
            name: {"slots": sl, "leaves": [getattr(self, attr)[sl].copy()]}
            for name, attr in (("next_fire", "next_fire"),
                               ("fired", "fired"),
                               ("max_leaf", "max_leaf"),
                               ("count", "count"),
                               ("keys_np", "_keys_np"))}
        jsl = jnp.asarray(sl)
        leaves, _ = jax.tree_util.tree_flatten(self.trees)
        rows["trees"] = {"slots": sl, "leaves": [
            np.asarray(jax.device_get(lf[:, jsl].T)) for lf in leaves]}
        rows["tvalid"] = {"slots": sl, "leaves": [
            np.asarray(jax.device_get(self.tvalid[:, jsl].T))]}
        repl = {"K_cap": self.K_cap, "F": self.F,
                "keys_all_int": self._keys_all_int,
                "key_dtype": self._key_dtype,
                "saw_new_key": self._saw_new_key,
                "leaf_frontier": self._leaf_frontier,
                "rebuild_dirty": self._rebuild_dirty,
                "ignored": self.ignored,
                "reclaimed_wid": self._reclaimed_wid}
        carry = []
        if self._dir_version == self._base_dirver:
            # no key admitted and no slot given back since the base:
            # an unchanged directory, a zero-byte carry
            carry += ["slot_of_key", "out_keys_by_slot", "free_slots"]
        else:
            repl["slot_of_key"] = dict(self.slot_of_key)
            repl["out_keys_by_slot"] = list(self._out_keys_by_slot)
            repl["free_slots"] = list(self._keymap.free)
        return ckpt_delta.make_delta(
            self._delta_base, rows=rows, replace=repl,
            carry=carry or None)

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        # restored state starts a fresh delta lineage
        self._ckpt_dirty = set()
        self._dirty_all = False
        self._delta_base = None
        self._snaps_since_full = 0
        self._base_geom = None
        self._base_dirver = None
        d = state.get("ffat")
        if d is None:
            return
        import jax
        import jax.numpy as jnp

        # capacity/ring geometry first: the arrays below are shaped by it
        self.K_cap = d["K_cap"]
        self.F = d["F"]
        self._check_index_plane()
        self.slot_of_key.clear()  # shared alias with the KeySlotMap
        self.slot_of_key.update(d["slot_of_key"])
        self._keymap.reset_index()
        self._keys_all_int = d["keys_all_int"]
        self._obj_keys = (None if self._keys_all_int
                          else list(d["out_keys_by_slot"]))
        # the free list as it stood, so that a restored run hands out
        # the slots an uninterrupted one would; a state without one
        # (scaling/repartition.py packs the live keys from 0): every
        # slot below the highest live one that no key holds
        free = d.get("free_slots")
        if free is None:
            live = set(d["slot_of_key"].values())
            free = [s for s in range(max(live, default=-1), -1, -1)
                    if s not in live]
        self._keymap.free = list(free)
        self._reclaimed_wid = d.get("reclaimed_wid", 0)
        self.stats.key_slots_live = len(self.slot_of_key)
        self.next_fire = d["next_fire"].copy()
        self.fired = d["fired"].copy()
        self.max_leaf = d["max_leaf"].copy()
        self.count = d["count"].copy()
        self._keys_np = d["keys_np"].copy()
        self._key_dtype = d["key_dtype"]
        self._saw_new_key = d["saw_new_key"]
        self._leaf_frontier = d["leaf_frontier"]
        self._rebuild_dirty = d["rebuild_dirty"]
        # the dirty ranges are no part of a snapshot: the next step
        # rebuilds the whole forest
        self._dirty_in = self._dirty_ev = None
        self._dirty_full = True
        self.ignored = d["ignored"]
        # a snapshot holds the forest slot-major: node-major again
        self.trees = (None if d["trees"] is None else
                      jax.tree_util.tree_map(lambda v: jnp.asarray(v.T),
                                             d["trees"]))
        self.tvalid = (None if d["tvalid"] is None
                       else jnp.asarray(np.asarray(d["tvalid"]).T))
        # device-side caches are stale for the restored geometry
        self._zero_fire_cache = {}
