"""Interval_Join_TPU: the keyed interval join on the device plane.

The contract is upstream's (``wf/interval_join.hpp:60-558``, the per-tuple
``operators/join.py``): inputs A (``stream_tag`` 0, the first merged
pipe) and B (1) join on equal keys where ``ts_b ∈ [ts_a - lower, ts_a +
upper]``; every matching pair is delivered exactly once, by the step of
whichever row is taken LATER (it finds the earlier one archived); the
output row is ``join_func(a_fields, b_fields)`` over COLUMNS (a dict of
arrays each, a dict out, traced once like ``Map_TPU``'s function), stamped
``max(ts_a, ts_b)``; A is purged where ``ts_a < wm - upper``, B where
``ts_b < wm - lower``, ``wm`` the aligned watermark of both inputs. KP
mode only (a key's archives on one replica, KEYBY routing).

**An archive** (one an input, a replica) is ONE int32 device array, a row
a column of the input (32-bit values as their bits, narrower ones
widened) and a last row of event times, its columns a ring of SLOTS of
one batch each. Nothing in it ever moves: a batch is written whole into
the next slot (one ``dynamic_update_slice``), and a row is purged by its
time alone (dead where it lies behind its side's purge line; pad rows and
rows that arrived behind that line are written dead). The host keeps the
newest time of every slot in use, frees a slot once that lies behind the
purge line, and doubles the ring BEFORE a batch would meet a slot still
in use (``Join_archive_growths``): it never reads the device to decide.
What this avoids was measured on the chip: compacting a 131,072-row
archive of twelve columns is 13.6 ms of gathers a step, a column at a
time; one packed gather of 16,384 lanes is 0.2 ms. A ring starts with
``RING_SLOTS`` slots, or, where its input was given a capacity
(``with_archive_capacity``), with the slots that hold that many rows,
allocated once at the input's first batch: a ring sized for the
deployment does not double inside a run, and one that must still does
and is counted. ``Join_archive_capacity_rows`` is the rows both rings
have room for.

**One step a batch** of either input, one program (``jit_join_<op>``):

- *probe* (``SCOPE_JOIN_PROBE``): of the batch and the other archive the
  SMALLER is sorted by (key, time) (one unstable three-operand
  ``lax.sort``: 0.5 ms at any size, but its compile grows with the size);
  every row of the LARGER takes its two ranks in that order, the rows
  sorted before its interval's start and before its end, by comparing
  with all of them (a fused compare-and-count: 1 ms for 16,384 x 16,384 or
  131,072 x 2,048); the difference is its matches, a prefix sum numbers
  the pairs, and output lane ``j`` belongs to the larger side's row whose
  running count first passes ``j`` (the rows whose count has not, counted
  in two levels) and to that row's ``j - before``-th match in the sorted
  order. Integer keys
  compare by value: no host key table;
- *exit*: the first output batch, as wide as the widest input batch seen:
  two packed gathers, ``join_func``, the stamps;
- *insert* (``SCOPE_JOIN_INSERT``): the batch into its own ring.

The step is DONATED its own archive and returns it: the insert writes in
place, and so does the shift of the archive's times the few times the
base has moved. The other archive is read and never returned: each
archive keeps its times against a base of its own (``abase``), moved by
its own side's steps, and a step shifts the other's times to the current
base as it reads them. So no step copies a ring.

A batch's pairs may outnumber that first batch (any fan-out: the full
product of a key's rows inside the interval): the total is one scalar
read back in the commit's FINISH half (``runtime/dispatch.py``: one
launch later, a copy that has landed), and what lies past it is gathered
by ``jit_join_more_<op>``, a batch a call, from the same ranks and the
other archive. The replica's next launch is donated that archive where
it is of the other side: before it, the pending step's count is read
(where the device keeps up, a copy that has landed) and, only where its
pairs pass one output batch, the further batches are gathered then
(``_resolve``); its finish emits them.

What is the host's, per BATCH and never per row, key or pair (the
``join`` stage inside ``wf:prep``): the batch's event times as int32
offsets from a base (one host array with the step's few scalars, which
the launch carries over with its other operands), the purge lines, the
ring's slots; and of a step's outputs it reads ONE array, its counts in
front of the pairs' stamps.

**An A batch that comes ahead of input B waits.** Where the newest event
of B taken so far has not passed an A batch's interval (``its newest
time + upper``), B batches still to come hold rows it will meet: taken at
once, it would deliver what is there and each of those B batches a few
pairs more, every time in an output batch of its own, which costs the
stage after the join a whole batch's launch and read for a hundred rows
(measured on the chip: a fifth more output batches a block, 4.5% of the
events a second, in the runs where the two inputs reached the join
abreast, and none where A came a block behind). So it waits, at most
``HOLD_MAX`` batches and no longer than the next idle tick, end of stream
or snapshot, until a B batch has passed it; its step then delivers all of
its pairs at once. Until then the watermark this replica acts on and sends
on stays at the waiting batch's oldest event (the purge keeps what it will
meet, nothing downstream sees its pairs late). Whatever is taken when,
each pair is still found once, by the later of its two steps.

Event time on the device is int32 offsets from ``base``. After a step
every live row lies at or above ``wm - max(lower, upper)``, so the base
moves there whenever the newest offset passes ``2**28``, and each archive
follows at its own side's next step. A stream whose watermark stays more
than ``2**29`` µs behind its newest event cannot be held and is refused
by name.

**A window's rows.** A row whose ``valid`` column is a False boolean (the
row of an empty window, as ``Ffat_Windows_TPU`` fires one) is not there:
it neither probes nor is archived. So a window operator feeds either
input as it stands.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..basic import JoinMode, OpType, RoutingMode, WindFlowError
from ..monitoring.flightrec import instrumented_jit
from ..monitoring.tracing import program_name
from ..runtime.dispatch import split_commit
from .batch import (BatchTPU, async_host_copy, bucket_capacity, field_dtype,
                    row_schema)
from .ops_tpu import (TPUOperatorBase, TPUReplicaBase,
                      prewarm_zero_fields)
from .schema import TupleSchema

# XLA module names (``jit_join_<op>``, ``jit_join_more_<op>``) and the
# named scopes inside the step
_PROG_JOIN, _PROG_MORE = "join", "join_more"
SCOPE_JOIN_PURGE, SCOPE_JOIN_PROBE, SCOPE_JOIN_INSERT = (
    "join_purge", "join_probe", "join_insert")

# offsets of live rows lie in [-T_LIM, T_LIM]; the base moves once one
# passes T_MOVE; a bound stays below T_BOUND; a dead row (a pad row, a row
# written behind its purge line, an empty slot) has time T_DEAD
T_LIM, T_MOVE, T_BOUND, T_DEAD = 1 << 29, 1 << 28, 1 << 27, -(1 << 30)
_I32_MAX = (1 << 31) - 1
# the step's scalars ride in front of the batch's time offsets: rows, the
# shift of its own archive's times to the base and whether they outlive
# it, purge lines of A and of B, the column of its own archive the batch
# is written at, and the other archive's shift and whether its times
# outlive that
N_PARAMS = 8
(_P_ROWS, _P_SHIFT, _P_KEEP, _P_CUT_A, _P_CUT_B, _P_AT, _P_SHIFT_O,
 _P_KEEP_O) = range(N_PARAMS)
# what a step reports, in front of its pairs' stamps: pairs, live rows of
# A and of B after it
_M_PAIRS, _M_LIVE_A, _M_LIVE_B, N_META = range(4)
# a ring given no capacity starts with this many slots and doubles when
# full. What the aligned watermark has not passed is a few batches by
# nature, but how many is the other input's lag: under backpressure, what
# the slower input's path can hold more than the faster one's (a stage
# more between a split and the join, at 16 a channel: 20 batches where
# every channel is full and both inputs share the join's own evenly, up
# to ~55 where the faster input's path has run empty; one run in thirty
# passed 32, measured). A doubling recompiles the step, so the start is
# generous; the price is a probe that compares with more dead rows. A
# capacity given for an input (``with_archive_capacity``: the span of
# stream a deployment holds) takes the start's place
RING_SLOTS = 64
# A batches that may wait for input B to pass them (module doc)
HOLD_MAX = 2


class Interval_Join_TPU(TPUOperatorBase):
    """``join_func(a_fields, b_fields) -> fields`` over columns; keyed by
    one integer field both inputs carry; added right after ``merge`` of
    exactly two pipes, A first (``MultiPipe.add`` holds every ``JOIN``
    operator to that)."""

    op_type = OpType.JOIN    # two tail groups, A/B tags by the collector
    join_mode = JoinMode.KP
    is_device_join = True    # rescale() refuses it by name

    def __init__(self, join_func: Callable, key_field: str,
                 lower_bound: int, upper_bound: int,
                 name: str = "interval_join_tpu", parallelism: int = 1,
                 schemas: Tuple[Optional[TupleSchema],
                                Optional[TupleSchema]] = (None, None),
                 capacity: Tuple[Optional[int], Optional[int]] = (None, None)
                 ) -> None:
        if not isinstance(key_field, str):
            raise WindFlowError(
                f"{name}: Interval_Join_TPU is keyed by ONE integer field "
                "that both inputs carry (with_key_by('field')): the probe "
                "compares key columns on the device; a callable or "
                "composite key needs the per-tuple Interval_Join")
        lower_bound, upper_bound = int(lower_bound), int(upper_bound)
        if lower_bound < 0 or upper_bound < 0 \
                or max(lower_bound, upper_bound) >= T_BOUND:
            raise WindFlowError(
                f"{name}: boundaries must lie in [0, 2**27) microseconds "
                f"(got {lower_bound}, {upper_bound}): event time on the "
                "device is an int32 offset")
        capacity = tuple(None if c is None else int(c) for c in capacity)
        if any(c is not None and c <= 0 for c in capacity):
            raise WindFlowError(
                f"{name}: an archive's capacity is a number of rows > 0 "
                f"(got {capacity})")
        # the two inputs' schemas are the operator's own: ``schema`` (the
        # one a one-input stage declares) stays None, so each staging
        # edge infers its side's from its first payload
        super().__init__(name, parallelism, RoutingMode.KEYBY, key_field,
                         0, None)
        self.join_func = join_func
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.schemas = tuple(schemas)
        # rows a replica's archive of each input is allocated for at its
        # first batch (None: RING_SLOTS slots)
        self.capacity = capacity
        # which inputs arrive staged from the host (packed), by side: the
        # graph's wiring says (``PipeGraph._wire_edge``)
        self.staged_sides = [False, False]

    def build_replicas(self) -> None:
        self.replicas = [IntervalJoinTPUReplica(self, i)
                         for i in range(self.parallelism)]


def _to_i32(col):
    """A column as the int32 row an archive keeps: 32-bit values by their
    bits, narrower ones widened."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(col.dtype)
    if dt == jnp.int32:
        return col
    if dt.kind == "f":
        col = col.astype(jnp.float32)
    elif dt.itemsize < 4:
        return col.astype(jnp.int32)
    return jax.lax.bitcast_convert_type(col, jnp.int32)


def _from_i32(row, dtype):
    """``_to_i32`` back."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    if dt == jnp.int32:
        return row
    if dt.kind == "f":
        return jax.lax.bitcast_convert_type(row, jnp.float32).astype(dt)
    if dt.itemsize < 4:
        return row.astype(dt)
    return jax.lax.bitcast_convert_type(row, dt)


def _live(t, cutoff):
    """The rows of an archive (or of a batch) with times ``t`` that are
    there: at or above the purge line, and not dead."""
    return (t >= cutoff) & (t > T_DEAD)


def _shifted(t, shift, keep):
    """An archive's times ``t`` against a base ``shift`` later, where
    they outlive it (``keep``; else every row is dead): a row the shift
    takes past ``T_DEAD`` is dead too."""
    import jax.numpy as jnp

    return jnp.where(keep > 0, jnp.maximum(t - shift, T_DEAD), T_DEAD)


def _match(key_l, lo, hi, valid_l, key_s, ts_s, valid_s):
    """For every row of the larger side its matches on the smaller one,
    the rows of its key with time in ``[lo, hi]``, as ranks in the
    smaller side's (key, time) order: ``(start, cum, by_rank)``, ``cum``
    the inclusive running count of matches over the larger side's rows,
    ``by_rank[r]`` the smaller side's row of rank ``r``. A row that is
    not there sorts last and counts for nothing."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    key_s = jnp.where(valid_s, key_s, _I32_MAX)
    ts_s = jnp.where(valid_s, ts_s, _I32_MAX)
    _, _, by_rank = jax.lax.sort(
        (key_s, ts_s, jnp.arange(key_s.shape[0], dtype=i32)), num_keys=2,
        is_stable=False)
    # a rank is the rows sorted before: every row of the larger side
    # counts them among ALL of the smaller (compare and count, fused)
    less = key_s[None, :] < key_l[:, None]
    same = key_s[None, :] == key_l[:, None]
    start = jnp.sum(less | (same & (ts_s[None, :] < lo[:, None])), axis=1,
                    dtype=i32)
    end = jnp.sum(less | (same & (ts_s[None, :] <= hi[:, None])), axis=1,
                  dtype=i32)
    counts = jnp.where(valid_l, end - start, 0)
    return start, jnp.cumsum(counts, dtype=i32), by_rank


def _pairs(start, cum, by_rank, first_lane, n_lanes: int):
    """Output lanes ``[first_lane, first_lane + n_lanes)`` of the step's
    pairs, as ``(row of the larger side, row of the smaller)``: lane ``j``
    is match ``j - (cum[r] - count[r])`` of the larger side's row ``r``
    whose running count first passes ``j``. Lanes past the total hold
    some pair; the batch's size says how many count."""
    import jax.numpy as jnp

    i32 = jnp.int32
    n = cum.shape[0]
    before = cum - jnp.diff(cum, prepend=0)        # pairs of the rows above
    lanes = first_lane + jnp.arange(n_lanes, dtype=i32)
    # the row whose running count first passes a lane is the number of
    # rows whose count has not: counted in two levels, the stretches of
    # ~sqrt(n) rows that end at or below the lane, then the rows of the
    # stretch it falls in (one gather of a stretch a lane). A scatter of
    # every row that has matches to its first lane costs by the ROW
    # (0.64 ms for 131,072 on the chip) and so by the ring: with 64 slots
    # the finish waited 2.4 ms a block for its step, with this 0.5
    side = max(1, math.isqrt(n))
    n_side = -(-n // side)
    grid = jnp.pad(cum, (0, n_side * side - n),
                   constant_values=_I32_MAX).reshape(n_side, side)
    stretch = jnp.minimum(
        jnp.sum(grid[None, :, -1] <= lanes[:, None], axis=1, dtype=i32),
        n_side - 1)
    row = jnp.minimum(
        stretch * side + jnp.sum(grid[stretch] <= lanes[:, None], axis=1,
                                 dtype=i32), n - 1)
    rank = start[row] + lanes - before[row]
    return row, by_rank[jnp.clip(rank, 0, by_rank.shape[0] - 1)]


class IntervalJoinTPUReplica(TPUReplicaBase):
    """One replica's two archives and the programs over them."""

    def __init__(self, op: Interval_Join_TPU, idx: int) -> None:
        super().__init__(op, idx)
        self._st_join = self.stats.stage("join")
        # per side: the archive (int32 ``[columns + 1, slots * rows]``, the
        # last row the times), its columns' names and dtypes, the ring's
        # shape ``(slots, rows a slot)``, the slot the next batch takes,
        # and the newest time of every slot in use, oldest first
        self.arch: List[Optional[Any]] = [None, None]
        self.cols: List[Optional[Dict[str, np.dtype]]] = [
            None if sch is None else
            {k: np.dtype(dt) for k, dt in sch.fields.items()}
            for sch in op.schemas]
        self.ring = [(0, 0), (0, 0)]
        self.head = [0, 0]
        self.held = (deque(), deque())
        self.live = [0, 0]      # rows there, by the last step read back
        # event time of offset 0 for the batch being prepared, and of each
        # archive's times (its own side's last step moved them there)
        self.base: Optional[int] = None
        self.abase: List[Optional[int]] = [None, None]
        # the last step launched whose further output batches may still
        # have to be gathered from the archive it probed (``_resolve``)
        self._pend: Optional[dict] = None
        # pairs leave in batches of the widest input batch seen (either
        # side's: the later arrival delivers, and a small batch of one
        # side may meet every row of a large one of the other)
        self.out_rows = 0
        # A batches waiting for input B to pass them, oldest first, each
        # with its oldest and newest event; the newest event of B taken
        self._waiting: "deque[Tuple[BatchTPU, int, int]]" = deque()
        self._b_seen: Optional[int] = None
        self._due = 0           # of them, to take after this message
        self._steps: Dict[tuple, Callable] = {}
        self._more: Dict[tuple, Callable] = {}
        self._out_schema: Optional[TupleSchema] = None

    # -- archives ----------------------------------------------------------
    def _fit(self, side: int, rows: int) -> None:
        """``side``'s ring with a free slot of at least ``rows`` rows:
        made on the input's first batch (``RING_SLOTS`` slots, or those
        that hold the input's capacity), doubled when every slot is in
        use, laid out anew for a wider batch. The commits in flight
        reassign the archive, so they land first."""
        import jax.numpy as jnp

        slots, width = self.ring[side]
        used = len(self.held[side])
        if self.arch[side] is not None and rows <= width and used < slots:
            return
        self.dispatch.drain(forced=True)
        key, cols = self.op.key_field, self.cols[side]
        if key not in cols or cols[key].kind not in "iu":
            raise WindFlowError(
                f"{self.op.name}: input {'AB'[side]} has no integer column "
                f"{key!r} to join by (columns: {sorted(cols)})")
        new_width = max(rows, width)
        cap = self.op.capacity[side]
        new_slots = max(RING_SLOTS if cap is None
                        else max(2, -(-cap // new_width)), slots)
        while used >= new_slots:
            new_slots *= 2
            self.stats.join_archive_growths += 1
        mat = jnp.full((len(cols) + 1, new_slots, new_width), T_DEAD,
                       jnp.int32)
        if used:
            # the slots in use, oldest first, at the head of the new ring
            old = self.arch[side].reshape(-1, slots, width)
            tail = (self.head[side] - used) % slots
            mat = mat.at[:, :used, :width].set(
                jnp.roll(old, -tail, axis=1)[:, :used])
        if self.arch[side] is None:
            self.abase[side] = self.base     # nothing in it is alive
        self.arch[side] = mat.reshape(len(cols) + 1, -1)
        self.ring[side], self.head[side] = (new_slots, new_width), used
        self.stats.join_archive_capacity_rows = sum(
            n * w for n, w in self.ring)

    # -- programs ----------------------------------------------------------
    def _unpack(self, side: int, mat) -> Dict[str, Any]:
        return {k: _from_i32(mat[i], dt)
                for i, (k, dt) in enumerate(self.cols[side].items())}

    def _emit_pairs(self, side: int, x, other, other_t, ranks, first_lane,
                    lanes):
        """``(out_fields, out_ts)`` of ``lanes`` output lanes from
        ``first_lane`` on: the larger of batch ``x`` and archive ``other``
        (its times ``other_t`` against the base) leads (``_match``), each
        side's packed rows are gathered once."""
        import jax.numpy as jnp

        batch_leads = x.shape[1] >= other.shape[1]
        led, follows = _pairs(*ranks, first_lane, lanes)
        at_x, at_o = (led, follows) if batch_leads else (follows, led)
        # the whole archive, its times too (replaced below): a gather of
        # its columns alone lays that slice out anew first, a row's words
        # along 128 lanes (8.6 GB for 16.8M rows of seven words)
        mine, theirs = x[:, at_x], other[:, at_o]
        fields = (self._unpack(side, mine), self._unpack(1 - side, theirs))
        out = self.op.join_func(*(fields if side == 0 else fields[::-1]))
        if not isinstance(out, dict):
            raise WindFlowError(f"{self.op.name}: the join function must "
                                "return a dict of columns")
        return out, jnp.maximum(mine[-1], other_t[at_o])

    def _step(self, side: int, probe: bool) -> Callable:
        """The step of a batch of ``side``: ``(fields, tsp, own, other)
        -> (own, x, out_fields, back, ranks)``, ``own`` DONATED and
        returned with the batch written in, ``other`` read only,
        ``back`` the three counts and then the first output batch's
        stamps, ``x`` the batch packed as an archive's rows, as wide as
        its ring's slots (a batch a filter left mostly empty is probed and
        archived at its size's bucket, not its capacity's). Without
        ``probe`` (the other input's columns are not known yet: it has
        sent nothing and declared nothing) it archives only."""
        lanes, width = self.out_rows, self.ring[side][1]
        sig = (side, probe, lanes, width)
        prog = self._steps.get(sig)
        if prog is not None:
            return prog
        import jax
        import jax.numpy as jnp

        op = self.op
        names = list(self.cols[side])
        k_own = names.index(op.key_field)
        k_other = list(self.cols[1 - side]).index(op.key_field) \
            if probe else 0
        # a window's rows: a False ``valid`` is an empty window's row
        k_valid = names.index("valid") \
            if self.cols[side].get("valid") == np.dtype(bool) else None
        bounds = (op.lower_bound, op.upper_bound)
        cut_own, cut_other = ((_P_CUT_A, _P_CUT_B) if side == 0
                              else (_P_CUT_B, _P_CUT_A))

        def rebase(mat, tsp):
            return mat.at[-1].set(_shifted(mat[-1], tsp[_P_SHIFT],
                                           tsp[_P_KEEP]))

        def step(fields, tsp, own, other):
            ts_in = tsp[N_PARAMS:][:width]
            there = jnp.arange(ts_in.shape[0], dtype=jnp.int32) < tsp[_P_ROWS]
            x = jnp.stack([_to_i32(fields[k])[:width] for k in names]
                          + [ts_in])
            if k_valid is not None:
                there = there & (x[k_valid] != 0)
            with jax.named_scope(SCOPE_JOIN_PURGE):
                # the archive's times follow the base only where it moved
                # since this side's last step: a pass over its time row
                # the few times it has
                own = jax.lax.cond(
                    (tsp[_P_SHIFT] != 0) | (tsp[_P_KEEP] == 0),
                    rebase, lambda mat, _: mat, own, tsp)
                other_t = _shifted(other[-1], tsp[_P_SHIFT_O],
                                   tsp[_P_KEEP_O]) if probe else None
            out = out_ts = ranks = None
            pairs = jnp.zeros((), jnp.int32)
            if probe:
                with jax.named_scope(SCOPE_JOIN_PROBE):
                    live = _live(other_t, tsp[cut_other])
                    if x.shape[1] >= other.shape[1]:
                        # an A row takes B from [ts - lower, ts + upper],
                        # a B row takes A from [ts - upper, ts + lower]
                        before, after = bounds[::1 if side == 0 else -1]
                        ranks = _match(x[k_own], ts_in - before,
                                       ts_in + after, there, other[k_other],
                                       other_t, live)
                    else:
                        # the archive leads: its rows are of the OTHER side
                        before, after = bounds[::-1 if side == 0 else 1]
                        ranks = _match(other[k_other], other_t - before,
                                       other_t + after, live, x[k_own],
                                       ts_in, there)
                    pairs = ranks[1][-1]
                    out, out_ts = self._emit_pairs(side, x, other, other_t,
                                                   ranks, 0, lanes)
            with jax.named_scope(SCOPE_JOIN_INSERT):
                # a row behind its own purge line is written dead
                block = x.at[-1].set(jnp.where(
                    there & (ts_in >= tsp[cut_own]), ts_in, T_DEAD))
                own = jax.lax.dynamic_update_slice(own, block,
                                                   (0, tsp[_P_AT]))
            n_own = jnp.sum(_live(own[-1], tsp[cut_own]), dtype=jnp.int32)
            n_other = jnp.sum(live, dtype=jnp.int32) if probe else 0
            meta = jnp.stack([pairs, *((n_own, n_other) if side == 0
                                       else (n_other, n_own))])
            # what the host reads of a step is ONE array: its counts, then
            # the pairs' stamps
            back = meta.astype(jnp.int32)
            if probe:
                back = jnp.concatenate([back, out_ts])
            return own, x, out, back, ranks

        prog = self._steps[sig] = instrumented_jit(
            step, self.stats, label=op.name,
            program=program_name(_PROG_JOIN, op.name), donate_argnums=(2,))
        return prog

    def _more_pairs(self, side: int, lanes: int) -> Callable:
        """Output batch ``chunk`` (from 1) of a step whose pairs
        outnumber its first: ``(x, other, shift, ranks, chunk) ->
        (out_fields, out_ts)``, ``shift`` the step's shift of the other
        archive's times and whether they outlive it."""
        prog = self._more.get((side, lanes))
        if prog is None:
            def more(x, other, shift, ranks, chunk):
                other_t = _shifted(other[-1], shift[0], shift[1])
                return self._emit_pairs(side, x, other, other_t, ranks,
                                        chunk * lanes, lanes)

            prog = self._more[(side, lanes)] = instrumented_jit(
                more, self.stats, label=self.op.name,
                program=program_name(_PROG_MORE, self.op.name))
        return prog

    # -- host half ---------------------------------------------------------
    def _time_params(self, side: int, batch: BatchTPU, lo_ts: int,
                     hi_ts: int):
        """``(tsp, rows archived)``: the step's scalars and the batch's
        event times (the oldest ``lo_ts``, the newest ``hi_ts``) as
        offsets, in one int32 array; moves the base, turns the rings,
        counts the late."""
        op, st, n = self.op, self.stats, batch.size
        wm = int(batch.wm)
        ts = batch.ts_host[:n]
        span = max(op.lower_bound, op.upper_bound)
        horizon = wm - span      # after this step no live row lies below
        if self.base is None:
            self.base = max(horizon, lo_ts - span)
        if hi_ts - self.base > T_MOVE and horizon > self.base:
            self.base = horizon
        if hi_ts - self.base > T_LIM:
            raise WindFlowError(
                f"{op.name}: the watermark ({wm}) stands more than 2**29 "
                f"microseconds behind the newest event ({hi_ts}): the "
                "archives cannot hold that span as int32 offsets")
        cut = (wm - op.upper_bound, wm - op.lower_bound)      # of A, of B
        # a pass over the batch's times is a call that gives the
        # interpreter up and asks for it back among eight threads: the
        # ones only a late or far-off row needs run only for such a batch
        n_late = 0
        if lo_ts < cut[side]:
            late = ts < cut[side]
            n_late = int(np.count_nonzero(late))
            if lo_ts - self.base < -T_LIM and (
                    ~late & (ts - self.base < -T_LIM)).any():
                raise WindFlowError(
                    f"{op.name}: an event of input {'AB'[side]} lies more "
                    "than 2**29 microseconds before the events archived, "
                    f"and at or above its purge line ({cut[side]}): the "
                    "watermark is not moving")
            st.join_late_probes += n_late
        if wm > lo_ts:           # admitted late, as the per-tuple join
            behind = wm - ts[ts < wm]
            st.note_late(len(behind), 0, behind)
        # a slot whose newest row lies behind the purge line is free
        for s in (0, 1):
            held = self.held[s]
            while held and held[0] < cut[s]:
                held.popleft()
        self._fit(side, bucket_capacity(n))
        slots, width = self.ring[side]
        # how far each archive's times lie behind the base: its own this
        # step shifts, the other's it reads shifted
        lag = [0 if a is None else self.base - a for a in self.abase]
        self.abase[side] = self.base
        tsp = np.empty(N_PARAMS + batch.capacity, np.int32)
        if lo_ts - self.base >= -T_LIM:
            # every row's offset is an int32 as it stands (what lies past
            # ``n`` is not there for the step, whatever it reads)
            np.subtract(batch.ts_host, self.base, out=tsp[N_PARAMS:],
                        casting="unsafe")
        else:
            # a row too far back to hold is behind its purge line: it goes
            # where its interval ends below every live offset
            np.clip(batch.ts_host - self.base, -T_LIM - span - 1, T_LIM,
                    out=tsp[N_PARAMS:], casting="unsafe")
        tsp[:N_PARAMS] = 0
        tsp[_P_ROWS] = n
        # an archive whose times lie further behind than any live offset
        # reaches holds nothing alive
        tsp[_P_SHIFT], tsp[_P_SHIFT_O] = (min(lag[s], T_LIM)
                                          for s in (side, 1 - side))
        tsp[_P_KEEP], tsp[_P_KEEP_O] = (lag[s] <= T_LIM
                                        for s in (side, 1 - side))
        tsp[_P_CUT_A], tsp[_P_CUT_B] = (
            max(-_I32_MAX, min(_I32_MAX, c - self.base)) for c in cut)
        tsp[_P_AT] = self.head[side] * width
        self.held[side].append(hi_ts)
        self.head[side] = (self.head[side] + 1) % slots
        st.join_probe_rows[side] += n
        st.join_archived_rows[side] += n - n_late
        return tsp, n - n_late

    def handle_msg(self, ch: int, msg: Any) -> None:
        """A message, then the waiting A batches it has passed (a B
        batch) or pushed out (an A batch past ``HOLD_MAX``): their steps
        follow its own."""
        super().handle_msg(ch, msg)
        due, self._due = self._due, 0
        self._release(due)

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        """The batch's step, or, for an A batch ahead of input B, nothing
        yet."""
        if batch.size == 0:
            return None
        ts = batch.ts_host[:batch.size]
        lo_ts, hi_ts = int(ts.min()), int(ts.max())
        waiting = self._waiting
        if not batch.stream_tag:
            if self._b_seen is None or (
                    not waiting
                    and hi_ts + self.op.upper_bound <= self._b_seen):
                return self._step_of(batch, 0, lo_ts, hi_ts)
            waiting.append((batch, lo_ts, hi_ts))
            self.stats.join_batches_held += 1
            self._due = len(waiting) - HOLD_MAX
            return None
        self._b_seen = hi_ts if self._b_seen is None \
            else max(self._b_seen, hi_ts)
        commit = self._step_of(batch, 1, lo_ts, hi_ts)
        while self._due < len(waiting) and (
                waiting[self._due][2] + self.op.upper_bound
                <= self._b_seen):
            self._due += 1
        return commit

    def _release(self, n: int) -> None:
        """Take the ``n`` oldest waiting A batches now."""
        for _ in range(max(0, n)):
            batch, lo_ts, hi_ts = self._waiting.popleft()
            # it acts on the watermark of now, as far as its own events
            # allow (none of them is made late by having waited)
            batch.wm = max(batch.wm, min(self.cur_wm, lo_ts))
            self.dispatch.submit(self._step_of(batch, 0, lo_ts, hi_ts),
                                 batch.bid)

    def _step_of(self, batch: BatchTPU, side: int, lo_ts: int, hi_ts: int
                 ) -> Callable:
        """The commit of ``batch``'s step, prepared now: in the order of
        these calls the rings turn and the base moves."""
        if self._waiting:
            # what a waiting A batch will meet stays (module doc)
            batch.wm = min(batch.wm, self._waiting[0][1])
        with self._st_join(batch.bid):
            if self.cols[side] is None:
                self.cols[side] = {k: field_dtype(batch.fields, k)
                                   for k in batch.fields}
            # the launch carries ``tsp`` to the device with its other
            # operands: no ``device_put`` of its own
            tsp, archived = self._time_params(side, batch, lo_ts, hi_ts)
        probe = self.cols[1 - side] is not None
        if probe and self.arch[1 - side] is None:
            # declared, and silent so far: an empty ring
            self._fit(1 - side, bucket_capacity(batch.size))
        if probe:
            # the probe compares with every row of the other ring
            slots, width = self.ring[1 - side]
            self.stats.join_probed_rows += slots * width
        self.out_rows = max(self.out_rows, batch.capacity)
        prog = self._step(side, probe)
        base, cap = self.base, self.out_rows

        @split_commit
        def commit() -> Callable[[], None]:
            pend = self._pend
            if pend is not None and pend["other"] is self.arch[side]:
                # this launch is donated what the pending step's further
                # output batches would gather from
                self._resolve(pend, batch.bid)
            other = self.arch[1 - side] if probe else None
            own, x, out, back, ranks = prog(batch.fields, tsp,
                                            self.arch[side], other)
            self.stats.device_programs_run += 1
            self.arch[side] = own
            hold = self._pend = None if not probe else {
                "side": side, "cap": cap, "other": other, "x": x,
                "shift": tsp[_P_SHIFT_O:_P_KEEP_O + 1], "ranks": ranks,
                "back": back, "more": None}
            # the finish reads the step's counts and the pairs' times
            async_host_copy(back)
            return lambda: self._finish(batch, side, archived, base, cap,
                                        out, back, hold)

        return commit

    def _resolve(self, hold: dict, bid: int) -> None:
        """Read the pending step's pair count now, and gather its further
        output batches where it has any, before its other archive goes to
        the next step."""
        with self._st_readback(bid):
            pairs = int(np.asarray(hold["back"])[_M_PAIRS])
        hold["more"] = [self._more_of(hold, chunk)
                        for chunk in range(1, -(-pairs // hold["cap"]))]
        hold["other"] = None

    def _more_of(self, hold: dict, chunk: int):
        """``(out_fields, out_ts)`` of output batch ``chunk`` of the step
        ``hold`` keeps, launched."""
        self.stats.device_programs_run += 1
        return self._more_pairs(hold["side"], hold["cap"])(
            hold["x"], hold["other"], hold["shift"], hold["ranks"], chunk)

    def _finish(self, batch: BatchTPU, side: int, archived: int, base: int,
                cap: int, out, back, hold: Optional[dict]) -> None:
        """The step's readback: its counts, then one output batch for
        every ``cap`` pairs (the first is the step's own, the others
        gathered now or, where ``_resolve`` ran, then)."""
        if self._pend is hold:
            self._pend = None
        st = self.stats
        with self._st_readback(batch.bid):
            meta = np.asarray(back)
            pairs = int(meta[_M_PAIRS])
            ts0 = meta[N_META:]
        live = [int(meta[_M_LIVE_A]), int(meta[_M_LIVE_B])]
        st.join_pairs += pairs
        st.join_purged_rows += sum(self.live) + archived - sum(live)
        st.join_archive_rows = sum(live)
        st.join_scanned_rows += live[1 - side]
        self.live = live
        for chunk in range(-(-pairs // cap)):
            if chunk:
                out, out_ts = hold["more"][chunk - 1] \
                    if hold["more"] is not None \
                    else self._more_of(hold, chunk)
                with self._st_readback(batch.bid):
                    ts0 = np.asarray(out_ts)
            if self._out_schema is None:
                self._out_schema = row_schema(out, None)
            nb = BatchTPU(out, np.add(ts0, base, dtype=np.int64),
                          min(cap, pairs - chunk * cap), self._out_schema,
                          batch.wm).caused_by(batch)
            st.join_output_batches += 1
            self._emit_batch(nb)

    # -- the waiting A batches at the stream's ordering points --------------
    def on_punctuation(self, wm: int) -> None:
        if self.emitter is not None:
            self.emitter.propagate_punctuation(
                min(self.cur_wm, self._waiting[0][1]) if self._waiting
                else self.cur_wm)

    def on_idle(self) -> bool:
        had = bool(self._waiting)
        self._release(len(self._waiting))
        return bool(self.dispatch.on_idle()) or had

    def terminate(self) -> None:
        if not self.terminated:
            self._release(len(self._waiting))
        super().terminate()

    # -- warm-up -----------------------------------------------------------
    def prewarm(self, caps) -> Optional[int]:
        """Both directions of the step, and of the program for the pairs
        past a step's first output batch, at every bucket capacity: on
        an empty batch against empty rings as wide as the widest bucket
        (nothing is archived, nothing leaves). None where an input's
        schema is inferred from its first batch."""
        import jax

        if None in self.cols:
            return None
        for side in (0, 1):
            self._fit(side, max(caps))
        for cap in caps:
            tsp = np.zeros(N_PARAMS + cap, np.int32)
            tsp[_P_KEEP] = tsp[_P_KEEP_O] = 1
            self.out_rows = max(self.out_rows, cap)
            for side in (0, 1):
                self.arch[side], x, _, _, ranks = self._step(side, True)(
                    prewarm_zero_fields(self.op, cap, side), tsp,
                    self.arch[side], self.arch[1 - side])
                jax.block_until_ready(self._more_pairs(side, self.out_rows)(
                    x, self.arch[1 - side], tsp[_P_SHIFT_O:_P_KEEP_O + 1],
                    ranks, 1))
        return 4 * len(caps)

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self) -> dict:
        import jax

        self._release(len(self._waiting))
        st = super().snapshot_state()     # drains the dispatch queue
        st["join"] = {"arch": jax.device_get(self.arch), "cols": self.cols,
                      "ring": list(self.ring), "head": list(self.head),
                      "held": [list(h) for h in self.held],
                      "live": list(self.live), "base": self.base,
                      "abase": list(self.abase),
                      "b_seen": self._b_seen}
        return st

    def restore_state(self, state: dict) -> None:
        import jax

        super().restore_state(state)
        j = state.get("join")
        if j is None:
            return
        self.arch = [None if a is None else jax.device_put(a)
                     for a in j["arch"]]
        self.cols, self.ring, self.head = j["cols"], j["ring"], j["head"]
        self.held = tuple(deque(h) for h in j["held"])
        self.live, self.base = j["live"], j["base"]
        self.abase = list(j["abase"])
        self.stats.join_archive_capacity_rows = sum(
            n * w for n, w in self.ring)
        self._b_seen = j.get("b_seen")
