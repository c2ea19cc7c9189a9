"""TPU operators: Map_TPU, Filter_TPU, Reduce_TPU.

Siblings of the reference CUDA operators (``wf/map_gpu.hpp``,
``wf/filter_gpu.hpp``, ``wf/reduce_gpu.hpp``), re-designed for XLA:

- functors are JAX functions over a dict of columns (struct-of-arrays) —
  the whole batch is one compiled program (the reference launches
  grid-stride kernels per batch; XLA fuses the elementwise chain instead);
- ``jax.jit`` is instantiated once per operator; XLA's own cache handles
  one compile per capacity bucket (the reference caches launch configs per
  batch size, ``map_gpu.hpp:251-277``);
- Filter compacts via a cumsum+scatter keepers-first permutation (the
  reference uses ``thrust::copy_if``, ``filter_gpu.hpp:331-335``; no
  sort on either side);
- Reduce groups by key slot (the permutation comes precomputed from the
  HOST key metadata — one sort of the raw keys; no device sort) and runs
  a segmented associative scan with the user's combine, gathering
  segment tails — one result per key per batch, exactly the reference
  semantics (``reduce_gpu.hpp:239-272``: sort_by_key + reduce_by_key).
  The combine must be associative and commutative (``API:78-80``);
- stateful Map/Filter keep per-key state in a device-resident table
  (slots × state pytree) updated by a masked ``lax.scan`` in arrival order —
  replacing the reference's per-key CUDA state objects + cross-replica
  spinlock (``map_gpu.hpp:233-295``, ``basic_gpu.hpp:142-233``) with a
  functional state carry. Keyed TPU operators hold their state per replica
  (keys are partitioned by the keyby shuffle), so no lock exists at all.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..basic import ExecutionMode, OpType, RoutingMode, WindFlowError
from ..monitoring.flightrec import instrumented_jit
from ..monitoring.tracing import program_name
from ..operators.base import BasicOperator, BasicReplica
from ..runtime.dispatch import DeviceDispatchQueue, split_commit
from .batch import (BatchTPU, StagingBuffers, async_host_copy,
                    key_column_np, key_column_to_list, row_schema)
from .schema import TupleSchema


# XLA module names of the standalone operators' programs
# (``jit_<kind>_<op>`` in a device profile) and the named scope of the
# keyed grid scan inside any program that runs it
_PROG_MAP, _PROG_FILTER, _PROG_REDUCE = "map", "filter", "reduce"
_PROG_SMAP, _PROG_SFILTER = "smap", "sfilter"
SCOPE_GRID_SCAN = "grid_scan"


def prewarm_zero_fields(op: "TPUOperatorBase", cap: int,
                        side: Optional[int] = None):
    """A zero-valued batch's ``fields`` of ``op``'s declared schema at one
    bucket capacity — the dummy input the compile-stability pre-warm
    feeds a program so its signature traces before any real batch
    arrives. In the form ``op``'s stream will present: fed from the host,
    the staging emitters' own transfer (``StagingBuffers.put``: one packed
    buffer per dtype group); fed by a device operator, a dict of
    columns. ``side`` names the input of a two-input operator, which
    declares a schema an input (``schemas``) and is told which of them
    arrive staged (``staged_sides``)."""
    schema, staged = ((op.schema, op.staged_input) if side is None
                      else (op.schemas[side], op.staged_sides[side]))
    fields = StagingBuffers(schema, cap).put(0)
    return fields if staged else dict(fields)


def _compact_order(keep):
    """Stable keepers-first permutation as GATHER indices, via cumsum +
    one scatter — equivalent to ``argsort(~keep, stable)`` but O(n)
    scatter instead of a sort (~11x on CPU, sorts are costly on TPU)."""
    import jax.numpy as jnp

    keep = keep.astype(bool)  # int 0/1 masks: ~keep would be bitwise NOT
    p_keep = jnp.cumsum(keep) - 1
    p_drop = jnp.sum(keep) + jnp.cumsum(~keep) - 1
    pos = jnp.where(keep, p_keep, p_drop).astype(jnp.int32)
    return jnp.zeros(keep.shape[0], jnp.int32).at[pos].set(
        jnp.arange(keep.shape[0], dtype=jnp.int32))


def cached_compile(cache: Dict, lock, key, make):
    """Compile-once lookup shared by every device-program cache
    (double-checked locking: replica worker threads race their first
    batch)."""
    prog = cache.get(key)
    if prog is None:
        with lock:
            prog = cache.get(key)
            if prog is None:
                prog = cache[key] = make()
    return prog


# ---------------------------------------------------------------------------
# composable kernel plane
# ---------------------------------------------------------------------------
# Every device operator's per-batch body is expressed as a kernel of the
# form ``(fields, valid, carry) -> (fields, valid, carry)`` traced inside
# ONE ``jax.jit`` program:
#
# - ``fields``: the batch's column dict;
# - ``valid``: the device-side keep mask (row alive at this point of the
#   chain) — a filter narrows it instead of compacting, so chained
#   operators compose without intermediate HBM materialization or a
#   mid-chain ``int(count)`` readback (compaction + count happen once at
#   the chain exit);
# - ``carry``: the operator's device state (grid tables for stateful
#   ops; None for stateless).
#
# The standalone replicas below and the fused chain replica
# (``tpu/fused_ops.py``) share these kernels, so both paths run the
# same traced math.


def own_key_spec(op):
    """The field (or tuple of fields) ``op`` is keyed by, None for a
    callable extractor: what a batch of ITS results says of its host
    keys (``BatchTPU.key_origin``)."""
    return op.key_field or getattr(op, "key_fields", None)


def op_batch_keys(op, batch: "BatchTPU"):
    """Per-batch keys for ``op``: host metadata where it IS ``op``'s key
    (staged for it, or made by a producer keyed by the same field:
    ``BatchTPU.keys_for``), else the device key column(s) ``op`` names.
    Module-level so fused sub-ops resolve keys with THEIR OWN key
    fields, not the chain head's."""
    keys = batch.keys_for(own_key_spec(op))
    if keys is None:
        field = op.key_field
        if field is not None:
            keys = key_column_to_list(batch, field)
        elif getattr(op, "key_fields", None):
            from .emitters_tpu import composite_keys_from_device
            keys = composite_keys_from_device(batch, op.key_fields)
        else:
            raise WindFlowError(
                f"{op.name}: keyed TPU operator needs keyed staging "
                "(with_key_by on the op) or a field-name key")
    return keys


def op_batch_keys_np(op, batch: "BatchTPU"):
    """``(keys, keys_arr)`` with at most ONE conversion — the host-prep
    stage's hot path (see ``TPUReplicaBase.batch_keys_np``)."""
    keys = batch.keys_for(own_key_spec(op))
    if keys is None and op.key_field is not None \
            and op.key_field in batch.fields:
        arr = key_column_np(batch, op.key_field)
        if arr.dtype.kind in "iu":
            return arr, arr
    if keys is None:
        keys = op_batch_keys(op, batch)
    return keys, np.asarray(keys)


def op_batch_slots_np(op, batch: "BatchTPU"):
    """Per-batch dense slot ids (HOST numpy) + slot->key order for
    ``op``'s key fields. Device ops run in DEFAULT mode only, so
    intra-batch output order is free: int keys take a vectorized unique
    (slot order = sorted keys), others keep first-appearance order via
    the Python loop. Module-level so the fused chain resolves slots with
    the TERMINATOR's key fields, not the chain head's."""
    keys = op_batch_keys(op, batch)
    n = batch.size
    keys_arr = np.asarray(keys)
    # ndim guard: tuple-of-int keys become a 2-D int array
    if n and keys_arr.ndim == 1 and keys_arr.dtype.kind in "iu":
        uniq, inv = np.unique(keys_arr[:n], return_inverse=True)
        slots = np.full(batch.capacity, len(uniq), dtype=np.int32)
        slots[:n] = inv
        slot_of_key = {int(k): i for i, k in enumerate(uniq)}
        return slots, slot_of_key
    if n and keys_arr.ndim == 1 and keys_arr.dtype.kind == "V" \
            and keys_arr.dtype.names:
        # structured composite keys: one unique per batch, slot map
        # keyed by plain tuples (shared dedup: keymap.py
        # structured_unique; None = object field, fall to row loop)
        from .keymap import structured_unique
        uu = structured_unique(keys_arr, n)
        if uu is None:
            keys = keys_arr[:n].tolist()
        else:
            uniq, inv = uu
            slots = np.full(batch.capacity, len(uniq), dtype=np.int32)
            slots[:n] = inv
            slot_of_key = {k.item(): i for i, k in enumerate(uniq)}
            return slots, slot_of_key
    slot_of_key: Dict[Any, int] = {}
    slots = np.zeros(batch.capacity, dtype=np.int32)
    for i, k in enumerate(keys):
        slots[i] = slot_of_key.setdefault(k, len(slot_of_key))
    slots[n:] = len(slot_of_key)  # padding segment
    return slots, slot_of_key


def reduce_order_and_slots(op, batch: "BatchTPU"):
    """(order, sorted slot ids, slot->key map) for a keyed reduce over
    ``batch``, with ONE sort: int keys sort directly (group boundaries
    give the sorted slot ids); other keys go through the generic slot
    map + a radix argsort of the small dense ids. Shared by the
    standalone ``ReduceTPUReplica`` and the fused chain's
    ``keyed_terminator`` exit (both must group identically so their
    per-slot outputs — and the slot->key emit order — stay exact
    equals)."""
    from .keymap import stable_group_argsort

    n = batch.size
    cap = batch.capacity
    _, keys_arr = op_batch_keys_np(op, batch)
    if n and keys_arr.ndim == 1 and keys_arr.dtype.kind in "iu":
        order_n = np.argsort(keys_arr[:n], kind="stable")
        sk = keys_arr[:n][order_n]
        new_grp = np.r_[True, sk[1:] != sk[:-1]]
        uniq = sk[new_grp]
        slot_of_key = {int(k): i for i, k in enumerate(uniq)}
        order = np.empty(cap, dtype=np.int32)
        order[:n] = order_n
        order[n:] = np.arange(n, cap)
        ssorted = np.full(cap, len(uniq), dtype=np.int32)
        ssorted[:n] = np.cumsum(new_grp) - 1
        return order, ssorted, slot_of_key
    slots_np, slot_of_key = op_batch_slots_np(op, batch)
    order = stable_group_argsort(
        slots_np, len(slot_of_key) + 1).astype(np.int32)
    return order, slots_np[order], slot_of_key


def _grid_scan_core(func, filter_mode: bool, M: int, KB: int):
    """The keyed grid-scan device core (see ``_KeyedStateScan``): rows
    scatter to a (KB x M) grid of (key slot, per-key position), a
    ``lax.scan`` walks the position axis while ``vmap`` covers the keys,
    and the results gather back to arrival positions. Returns
    ``core(fields, valid, grid_idx, touched, touched_mask, table, dirty)
    -> (out, table2, dirty2)`` where ``out`` is the per-row output
    columns (map mode) or the per-row keep mask ANDed with ``valid``
    (filter mode) and ``dirty2`` is the touched-slot bitmap with this
    grid's slots marked (rides the carry — incremental checkpoints
    gather only dirty rows).
    ``valid`` may be a host bool array (standalone) or a traced
    device mask (fused chains: rows a mid-chain filter dropped skip the
    grid and leave their key's state untouched)."""
    import jax
    import jax.numpy as jnp

    KM = KB * M
    tmap = jax.tree_util.tree_map

    def bwhere(ok, new, old):
        shaped = ok.reshape(ok.shape + (1,) * (new.ndim - ok.ndim))
        return jnp.where(shaped, new, old).astype(old.dtype)

    @jax.named_scope(SCOPE_GRID_SCAN)
    def core(fields, valid, grid_idx, touched, touched_mask, table, dirty):
        T_cap = next(iter(jax.tree_util.tree_leaves(table))).shape[0]
        tsafe = jnp.where(touched_mask, touched, 0)
        sub = tmap(lambda a: a[tsafe], table)  # (KB, ...)
        safe = jnp.where(valid, grid_idx, KM)
        grids = {f: jnp.zeros((KM,), v.dtype).at[safe].set(
                     v, mode="drop").reshape(KB, M)
                 for f, v in fields.items()}
        gmask = jnp.zeros((KM,), bool).at[safe].set(
            True, mode="drop").reshape(KB, M)
        vfunc = jax.vmap(func)

        def body(tbl, xs):
            col, ok = xs  # col: {f: (KB,)}, ok: (KB,)
            out_col, new_state = vfunc(col, tbl)
            tbl = tmap(lambda o, nw: bwhere(ok, nw, o), tbl, new_state)
            return tbl, out_col

        cols = {f: g.T for f, g in grids.items()}  # (M, KB)
        sub2, outs = jax.lax.scan(body, sub, (cols, gmask.T))
        tscatter = jnp.where(touched_mask, touched, T_cap)
        table2 = tmap(
            lambda a, nw: a.at[tscatter].set(nw, mode="drop"),
            table, sub2)
        # touched-slot bitmap: every slot this grid scattered back to is
        # dirty since the last full snapshot (conservative — marked even
        # when func left the value bit-identical)
        dirty2 = dirty.at[tscatter].set(True, mode="drop")
        # gather outputs back to arrival positions: grid (slot, within)
        slot = grid_idx // M
        within = jnp.where(valid, grid_idx % M, 0)
        row_flat = within * KB + jnp.minimum(slot, KB - 1)
        if filter_mode:
            keep = outs.reshape(-1)[row_flat]  # (cap,)
            return keep.astype(bool) & valid, table2, dirty2
        out_rows = {f: (o.reshape(M * KB, -1)[row_flat].reshape(
                        fields[f].shape)
                        if o.ndim > 2 else o.reshape(-1)[row_flat])
                    for f, o in outs.items()}
        return out_rows, table2, dirty2

    return core


def state_table(state_init, capacity: int):
    """The keyed state table of ``capacity`` slots: every leaf of
    ``state_init`` (a scalar or an array of any shape) repeated along a
    leading slot axis, ``(capacity,) + leaf.shape``."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda v: jnp.full((capacity,) + np.shape(v), v,
                           dtype=jnp.asarray(v).dtype), state_init)


def has_array_leaves(state_init) -> bool:
    """True where a leaf of ``state_init`` is an array, not a scalar."""
    import jax

    return any(np.ndim(v) for v in jax.tree_util.tree_leaves(state_init))


def check_keyed_state(name: str, state_init, tiering,
                      key_capacity: Optional[int]) -> None:
    """Refuse, by name, what the keyed state plane of a single chip does
    not take: a key capacity without state, a capacity beside tiering
    (whose hot tier sizes the table), array leaves under tiering (the
    cold store holds one scalar column a leaf)."""
    if key_capacity is not None:
        if state_init is None:
            raise WindFlowError(
                f"{name}: with_key_capacity sizes the keyed state table of "
                "with_state; a stateless operator keeps none")
        if int(key_capacity) < 1:
            raise WindFlowError(f"{name}: with_key_capacity needs at least "
                                f"one slot, got {key_capacity}")
        if tiering is not None:
            raise WindFlowError(
                f"{name}: with_key_capacity and with_tiering both size the "
                "device table; give the tiers' hot_capacity alone")
    if tiering is not None and state_init is not None \
            and has_array_leaves(state_init):
        raise WindFlowError(
            f"{name}: with_tiering keeps scalar state leaves only (its cold "
            "store holds one column a leaf); an array leaf needs the "
            "dense table (drop with_tiering, size it by with_key_capacity)")


def masked_tree_reduce(combine, fields, valid):
    """Whole-batch fold to one tuple via a masked pairwise tree
    reduction (log2(cap) fused halving passes — associativity is the
    contract). ``valid`` gates which rows participate, so a fused
    chain's filter mask flows straight into the terminal reduce. The
    result is garbage when no row is valid — callers must skip emission
    when the valid count is zero."""
    import jax.numpy as jnp

    n = next(iter(fields.values())).shape[0]
    # Pad up to a power of two so the halving loop never drops an odd
    # tail (upstream ops such as Ffat_Windows_TPU emit batches whose
    # capacity is num_win_per_batch — any user value).
    m = 1 << max(0, n - 1).bit_length()
    if m != n:
        pad = m - n
        fields = {k: jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
            for k, v in fields.items()}
        valid = jnp.concatenate([valid, jnp.zeros(pad, bool)])
    cur = fields
    vcur = valid
    length = m
    while length > 1:
        half = length // 2
        a = {k: v[:half] for k, v in cur.items()}
        b = {k: v[half:half * 2] for k, v in cur.items()}
        va, vb = vcur[:half], vcur[half:half * 2]
        merged = combine(a, b)
        cur = {k: jnp.where(va & vb, merged.get(k, b[k]),
                            jnp.where(va, a[k], b[k]))
               for k in cur}
        vcur = va | vb
        length = half
    return {k: v[:1] for k, v in cur.items()}


# ---------------------------------------------------------------------------
# shared replica machinery
# ---------------------------------------------------------------------------
class TPUReplicaBase(BasicReplica):
    """Processes whole device batches; never iterates rows.

    Batch processing is SPLIT into a host-prep stage and a device-commit
    stage pipelined through a per-replica ``DeviceDispatchQueue``
    (``WF_DISPATCH_DEPTH``, default 2): ``prep_device_batch`` runs the
    host control plane for batch N+1 while batch N's commit sits deferred
    in the queue. A commit whose emit reads a fresh output of the program
    it launches (a compaction) is itself two halves, one launch apart: it
    launches and returns its readback-and-emit as a finish
    (``finish_compacted``), which the queue runs after the replica's next
    launch. The queue drains, launches and finishes, at every ordering
    point (punctuation, EOS/terminate, worker idle tick) and whenever
    host code must touch the replica's device state."""

    def __init__(self, op: BasicOperator, idx: int) -> None:
        super().__init__(op, idx)
        # wf:prep / wait:queue / wf:commit live in the dispatch queue; the
        # commit's children are this replica's (monitoring/tracing.py)
        self.dispatch = DeviceDispatchQueue(stats=self.stats)
        self._st_readback = self.stats.stage("readback")
        self._st_emit = self.stats.stage("emit")
        # per-record error policy (windflow_tpu.supervision.errors): a
        # whole batch shares one XLA program, so a failing batch is
        # BISECTED until the poison record is isolated at size 1 and the
        # policy applies to that record. None (FAIL default) keeps the
        # pipelined hot path untouched.
        pol = getattr(op, "error_policy", None)
        self._err_policy = pol if pol is not None and not pol.is_fail \
            else None

    def handle_msg(self, ch: int, msg: Any) -> None:
        if msg.is_punct:
            self.stats.punct_received += 1
            self._advance_wm(msg.wm)
            # in-flight batches emit BEFORE the punctuation propagates
            # (watermark monotonicity downstream)
            self.dispatch.drain(forced=True)
            self.on_punctuation(msg.wm)
            return
        if not isinstance(msg, BatchTPU):
            raise WindFlowError(
                f"{self.op.name}: TPU operator received a non-device message "
                f"({type(msg).__name__}); the upstream operator must declare "
                "an output batch size > 0")
        self.stats.start_svc()
        self.stats.inputs_received += msg.size
        self.stats.device_batches_in += 1
        if self.stats.sample_every:  # per batch, not per tuple
            self.stats._svc_rec = True
        self._advance_wm(msg.wm)
        msg.wm = self.cur_wm
        if self._err_policy is not None:
            self._process_batch_guarded(msg)
            self.stats.end_svc(msg.size)
            return
        with self.dispatch.prep(msg.bid):
            commit = self.prep_device_batch(msg)
        if commit is not None:
            self.dispatch.submit(commit, msg.bid)
        self.stats.end_svc(msg.size)

    def _process_batch_guarded(self, msg: BatchTPU) -> None:
        """Policy-guarded batch path: commits run SYNCHRONOUSLY (drain
        right after submit) so an error attributes to this exact batch,
        then bisection isolates the offender. Stateless transforms
        bisect safely; a stateful op whose failure left partial device
        state applied keeps that prefix (document-level caveat — the
        FAIL policy is the strict choice for stateful device chains)."""
        try:
            with self.dispatch.prep(msg.bid):
                commit = self.prep_device_batch(msg)
            if commit is not None:
                self.dispatch.submit(commit, msg.bid)
                self.dispatch.drain(forced=True)
        except Exception as exc:  # noqa: BLE001 — the policy boundary
            from ..supervision.errors import (apply_record_policy,
                                              batch_row_payload,
                                              split_batch)
            if msg.size <= 1:
                payload = batch_row_payload(msg, 0) if msg.size else {}
                ts = int(msg.ts_host[0]) if msg.size else 0
                apply_record_policy(self, self._err_policy, payload, ts,
                                    exc)
                return
            for half in split_batch(msg):
                self._process_batch_guarded(half)

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        """Host-prep stage: return this batch's device-commit thunk (or
        None when the batch needs no device work). Subclasses that
        separate their host control plane override this; the default
        keeps the whole legacy ``process_device_batch`` as the commit
        stage — still correct (commits run in submission order and drain
        at every ordering point), just without the prep overlap."""
        return lambda: self.process_device_batch(batch)

    def process_device_batch(self, batch: BatchTPU) -> None:
        raise NotImplementedError

    def on_idle(self) -> bool:
        """Worker idle tick: commit in-flight batches on a quiet stream
        (Worker._process; same contract as the emitter FIFOs)."""
        return self.dispatch.on_idle()

    def terminate(self) -> None:
        # EOS: in-flight batches commit before any flush/close logic —
        # regardless of subclass flush_on_termination overrides
        if not self.terminated:
            self.dispatch.drain(forced=True)
        super().terminate()

    def snapshot_state(self) -> dict:
        # the checkpointing worker drains the dispatch queue before
        # snapshotting, but device state must never be captured with
        # commits in flight (donation reassigns it) — drain defensively
        self.dispatch.drain(forced=True)
        return super().snapshot_state()

    def _emit_batch(self, batch: BatchTPU) -> None:
        self.stats.device_batches_out += 1
        with self._st_emit(batch.bid, batch.cause):
            self.emitter.emit_device_batch(batch)

    def finish_compacted(self, batch: BatchTPU, out_fields, order, count
                         ) -> Callable[[], None]:
        """The end of a compacting commit's launch half (a
        ``split_commit``): start the host copies of what the emit reads,
        so the transfer too runs under the next batch's work, and hand
        the readback-and-emit back as the finish the dispatch queue runs
        one launch later."""
        async_host_copy(count, order)
        return lambda: self.emit_compacted(batch, out_fields, order, count)

    def emit_compacted(self, batch: BatchTPU, out_fields, order, count
                       ) -> None:
        """Emit a compaction result: device columns reordered keep-first,
        host ts/keys reordered to match (shared by the filter paths)."""
        # the compaction readbacks: int(count) + the order materialization
        # block on the program result (this is why a compacting commit
        # hands this call back as its finish)
        with self._st_readback(batch.bid):
            new_size = int(count)
            order_np = np.asarray(order)
        self.stats.inputs_ignored += batch.size - new_size
        ts2 = batch.ts_host[order_np]
        keys2 = batch.host_keys
        if isinstance(keys2, np.ndarray):
            # the kept rows' keys, one gather (kept rows lie below size)
            keys2 = keys2[order_np[:new_size]]
        elif keys2 is not None:     # object keys: a list
            keys2 = [keys2[j] for j in order_np[:new_size].tolist()]
        nb = BatchTPU(out_fields, ts2, new_size,
                      row_schema(out_fields, batch.schema), batch.wm, keys2)
        nb.stream_tag = batch.stream_tag
        nb.copy_trace_from(batch)
        if new_size > 0:
            self._emit_batch(nb)
        else:
            # a batch that keeps nothing still carries its watermark on:
            # a two-input stage downstream aligns on BOTH inputs, and a
            # side silent for a batch would hold it back until the next
            # generated punctuation (100 ms of wall time)
            self.emitter.propagate_punctuation(batch.wm)

    def batch_keys_np(self, batch: BatchTPU):
        """``(keys, keys_arr)`` with at most ONE conversion — the
        host-prep stage's hot path (``key_column_to_list`` followed by
        ``np.asarray`` boxes every key twice per batch). Int key columns
        return the raw array for both forms: every ``KeySlotMap`` path
        that registers keys from an int array goes through ``int()``, so
        slot identity and the int fast path's ``isinstance(key, int)``
        checks still see Python ints. Other dtypes keep the list form
        (their consumers iterate Python keys)."""
        return op_batch_keys_np(self.op, batch)

    # per-batch keys: host metadata when staged keyed, else the device key
    # column named by a string key extractor
    def batch_keys(self, batch: BatchTPU):
        return op_batch_keys(self.op, batch)

    def batch_slots_np(self, batch: BatchTPU):
        """See ``op_batch_slots_np`` (module-level: the fused chain
        resolves slots with a sub-op's own key fields)."""
        return op_batch_slots_np(self.op, batch)


class TPUOperatorBase(BasicOperator):
    op_type = OpType.TPU
    is_tpu = True
    # set where the graph wires a CPU -> TPU edge into this operator: its
    # batches then arrive staged (packed), not as a device program's dict
    staged_input = False

    def __init__(self, name: str, parallelism: int, input_routing: RoutingMode,
                 key_extractor, output_batch_size: int,
                 schema: Optional[TupleSchema]) -> None:
        import threading
        super().__init__(name, parallelism, input_routing, key_extractor,
                         output_batch_size)
        self.schema = schema  # None => inferred at the staging boundary
        # compiled device programs shared across this op's replicas
        self._scan_prog_cache: Dict[Any, Any] = {}
        self._scan_prog_lock = threading.Lock()

    @property
    def is_chainable(self) -> bool:
        return False

    @property
    def fusion_role(self) -> Optional[str]:
        """Device-chain fusion classification (``topology/stage.py``):
        ``"transform"`` composes mid-chain via its ``device_kernel``;
        ``"terminator"`` may only end a fused chain; None never fuses
        (window/mesh operators own their whole stage)."""
        return None

    def device_kernel(self):
        """The operator's composable ``(fields, valid, carry) ->
        (fields, valid, carry)`` kernel (stateless transforms only;
        stateful ops contribute a grid-scan engine instead)."""
        raise WindFlowError(f"{self.name}: no composable device kernel")

    def configure(self, execution_mode, time_policy) -> None:
        if execution_mode is not ExecutionMode.DEFAULT:
            # reference: GPU operators only in DEFAULT mode (map_gpu.hpp:470-478)
            raise WindFlowError(
                f"{self.name}: TPU operators require DEFAULT execution mode")
        super().configure(execution_mode, time_policy)


# ---------------------------------------------------------------------------
# Map_TPU
# ---------------------------------------------------------------------------
class Map_TPU(TPUOperatorBase):
    """Stateless: ``func(fields) -> fields`` (elementwise over columns).
    Stateful (``state_init`` given): ``func(row, state) -> (row, state)``
    over one row's scalars and its key's state (leaves of any shape),
    scanned in arrival order; ``key_capacity`` sizes the state table."""

    def __init__(self, func: Callable, name: str = "map_tpu",
                 parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor=None, output_batch_size: int = 0,
                 schema: Optional[TupleSchema] = None,
                 state_init: Any = None, tiering=None,
                 key_capacity: Optional[int] = None) -> None:
        check_keyed_state(name, state_init, tiering, key_capacity)
        if state_init is not None and key_extractor is None:
            raise WindFlowError(f"{name}: stateful Map_TPU requires a key "
                                "extractor (KEYBY)")
        if tiering is not None and state_init is None:
            raise WindFlowError(f"{name}: with_tiering requires keyed "
                                "state (with_state)")
        super().__init__(name, parallelism,
                         RoutingMode.KEYBY if state_init is not None
                         else input_routing,
                         key_extractor, output_batch_size, schema)
        self.func = func
        self.state_init = state_init
        self.tiering = tiering
        self.key_capacity = key_capacity

    @property
    def fusion_role(self) -> Optional[str]:
        return "transform"

    def device_kernel(self):
        if self.state_init is not None:
            raise WindFlowError(f"{self.name}: stateful Map_TPU carries a "
                                "grid-scan engine, not a stateless kernel")
        func = self.func

        def kernel(fields, valid, carry):
            # the user's function gets a dict of its own (a staged
            # batch's packed mapping is read-only, and returned as it is
            # it would not be a dict of columns)
            return func(dict(fields)), valid, carry

        return kernel

    def build_replicas(self) -> None:
        cls = StatefulMapTPUReplica if self.state_init is not None \
            else MapTPUReplica
        self.replicas = [cls(self, i) for i in range(self.parallelism)]


class MapTPUReplica(TPUReplicaBase):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        kernel = op.device_kernel()

        def run(fields):
            out, _, _ = kernel(fields, None, None)
            return out

        self._jitted = instrumented_jit(
            run, self.stats, label=op.name,
            program=program_name(_PROG_MAP, op.name))

    def process_device_batch(self, batch: BatchTPU) -> None:
        out = self._jitted(batch.fields)
        self.stats.device_programs_run += 1
        if not isinstance(out, dict):
            raise WindFlowError(f"{self.op.name}: Map_TPU function must "
                                "return a dict of columns")
        self._emit_batch(batch.with_fields(out))

    def prewarm(self, caps) -> Optional[int]:
        """Compile-stability pre-warm (``PipeGraph.with_prewarm``): trace
        the program once per bucket capacity on zero dummies — pure
        function, no state, no emit. None when the schema is inferred at
        the staging boundary (nothing to synthesize from yet)."""
        import jax
        sch = self.op.schema
        if sch is None:
            return None
        for cap in caps:
            jax.block_until_ready(
                self._jitted(prewarm_zero_fields(self.op, cap)))
        return len(caps)


class _KeyedStateScan:
    """Shared keyed device-state machinery for stateful Map/Filter.

    The reference runs one CUDA worker per distinct key walking its linked
    chain serially (``map_gpu.hpp:80-102``). The TPU shape of that idea: a
    (K_cap x M) GRID scan — rows scatter to (key slot, per-key position),
    the scan walks the per-key POSITION axis (M = max tuples of one key in
    the batch) while ``vmap`` processes all keys in parallel each step.
    Sequential work is the per-key chain depth, not the batch size; state
    lives in a device-resident table pytree between batches, each leaf
    ``(K_cap,) + its shape in the initial state``.

    The table starts at the operator's ``key_capacity`` (64 where none
    was given) and doubles when the keys outgrow it. Counted on the
    replica's record, per grid scan: ``Scan_programs``, ``Scan_rows``
    (rows given a grid cell), ``Scan_cells`` (KB x M), ``Scan_depth``
    (M), ``Scan_keys`` (touched keys); the host's grid assembly is the
    stage ``wf:grid:<op>``; ``Keys_admitted``, ``Key_slots_live``,
    ``Key_capacity_growths`` as on the window operator.
    """

    def __init__(self, replica, func, state_init, filter_mode: bool,
                 op=None) -> None:
        from .keymap import KeySlotMap
        # ``op`` overrides the owner: a fused chain replica hosts one
        # engine per stateful SUB-operator, each resolving keys and
        # caching against its own op.
        self.op = replica.op if op is None else op
        self.replica = replica
        self.func = func
        self.state_init = state_init
        self.filter_mode = filter_mode
        capacity = getattr(self.op, "key_capacity", None)
        self.table_capacity = capacity or 64
        # int keys are looked up through a direct table laid over the
        # given capacity's span from the first batch on
        self._keymap = KeySlotMap(span=capacity or 0)
        self.slot_of_key = self._keymap.slot_of_key  # shared dict
        self.stats = replica.stats
        self._st_grid = replica.stats.stage("grid", self.op.name)
        # compiled grid-scan programs shared across replicas of the op
        # (keyed by grid shape; the table capacity is read from the table
        # ARGUMENT at trace time, so growth re-traces automatically).
        self._cache = self.op._scan_prog_cache
        self._cache_lock = self.op._scan_prog_lock
        self.table = None  # pytree of (table_capacity, ...) arrays
        # (table_capacity,) int32, -1 between batches: _grid_meta's
        # numbering of the touched rows, grown with the table
        self._mark = None
        # tiered keyed state (windflow_tpu.state): with_tiering caps the
        # device table at hot_capacity and spills the cold tail to a
        # host sqlite store; None = the dense path, byte-identical to
        # before the tier plane existed
        self.tier = None
        cfg = getattr(self.op, "tiering", None)
        if cfg is not None:
            from ..state.tiered import TieredKeyStore
            self.tier = TieredKeyStore(
                f"{self.op.name}_r{replica.idx}_tier", cfg,
                stats=replica.stats)
            self.table_capacity = self.tier.hot_capacity
        # incremental checkpointing (WF_CKPT_DELTA): a device-resident
        # touched-slot bitmap rides the grid-scan carry, so a delta
        # snapshot gathers only the rows dirtied since the last FULL
        # snapshot (the delta base — always a full epoch, chain depth 1)
        self.dirty = None  # (table_capacity,) bool, grown with the table
        self._delta_base = None  # epoch id of the last full snapshot
        self._snaps_since_full = 0
        self._base_capacity = None  # capacity at the last full snapshot
        self._base_nkeys = None  # key count at the last full snapshot

    # -- device program ----------------------------------------------------
    def _make(self, M: int, KB: int):
        """The program works on the BATCH-LOCAL key set: grids are
        (KB x M) where KB = distinct keys in this batch (bucketed), and the
        global state table contributes only its touched rows (gathered in,
        scattered back) — per-batch cost is bounded by the batch, not by
        the stream's total key cardinality. The traced math lives in the
        shared ``_grid_scan_core`` kernel; this wrapper adds the
        standalone exit (compaction for filters) and the jit/donation."""
        import jax
        import jax.numpy as jnp

        core = _grid_scan_core(self.func, self.filter_mode, M, KB)
        filter_mode = self.filter_mode

        def run(fields, grid_idx, valid, touched, touched_mask, table,
                dirty):
            out, table2, dirty2 = core(fields, valid, grid_idx, touched,
                                       touched_mask, table, dirty)
            if filter_mode:
                keep = out
                order = _compact_order(keep)  # keepers first, stable
                outf = {k: v[order] for k, v in fields.items()}
                return outf, order, jnp.sum(keep), table2, dirty2
            return out, table2, dirty2

        # the state table (and its dirty bitmap) are DONATED: the
        # touched-row scatter updates them in place instead of copying
        # the whole table every batch (the same double-buffer discipline
        # as the FFAT forest — every call site reassigns self.table /
        # self.dirty from the program output, so the consumed buffers are
        # never reused)
        return instrumented_jit(
            run, self.replica.stats, label=self.op.name,
            program=program_name(
                _PROG_SFILTER if filter_mode else _PROG_SMAP, self.op.name),
            donate_argnums=(5, 6))

    # -- host side ---------------------------------------------------------
    def _ensure_table(self, n_keys_needed: int) -> None:
        import jax

        if self.table is None:
            self.table = state_table(self.state_init, self.table_capacity)
        self._sync_dirty()
        if self.tier is not None:
            # tiered mode: the device table IS the hot tier, fixed at
            # hot_capacity — keys beyond it spill to the cold store via
            # plan_batch, which guarantees the mapped set always fits
            if n_keys_needed > self.table_capacity:  # pragma: no cover
                from ..basic import KeyCapacityError
                raise KeyCapacityError(
                    self.op.name, self.table_capacity,
                    n_keys_needed - self.table_capacity)
        else:
            if n_keys_needed > self.table_capacity:
                # growth reads the CURRENT table: in-flight commits
                # reassign it (donation), so they must land first
                self.replica.dispatch.drain(forced=True)
            while n_keys_needed > self.table_capacity:
                self.table_capacity *= 2
                old = self.table
                fresh = state_table(self.state_init, self.table_capacity)
                self.table = jax.tree_util.tree_map(
                    lambda f, o: f.at[:o.shape[0]].set(o), fresh, old)
                self.stats.key_capacity_growths += 1
            self._sync_dirty()
        if self._mark is None or len(self._mark) != self.table_capacity:
            self._mark = np.full(self.table_capacity, -1, dtype=np.int32)

    def _sync_dirty(self) -> None:
        """Keep the dirty bitmap allocated and shape-matched to the
        table. Growth carries the old bits over — the grown rows hold
        initial state and get marked when first touched (and growth
        changes capacity, which already forces the next snapshot FULL)."""
        import jax.numpy as jnp

        if self.table is None:
            return
        if self.dirty is None:
            self.dirty = jnp.zeros((self.table_capacity,), bool)
        elif int(self.dirty.shape[0]) != self.table_capacity:
            old = self.dirty
            self.dirty = (jnp.zeros((self.table_capacity,), bool)
                          .at[:old.shape[0]].set(old))

    def grid_meta(self, batch: BatchTPU):
        """``_grid_meta`` timed as ``wf:grid:<op>`` and counted as one
        grid scan."""
        with self._st_grid(batch.bid):
            meta = self._grid_meta(batch)
        st = self.stats
        M, KB = meta[4], meta[5]
        st.scan_programs += 1
        st.scan_rows += batch.size
        st.scan_cells += KB * M
        st.scan_depth += M
        st.scan_keys += int(meta[3].sum())
        return meta

    def _grid_meta(self, batch: BatchTPU):
        """(grid_idx, valid, touched, touched_mask, M, KB): batch-local
        grid positions, the touched global table rows, and the grid
        bucket sizes. No per-key Python and no comparison sort: global
        slots come from the KeySlotMap's direct table, a batch's new keys
        admitted in one operation; touched rows and their dense local ids
        from one O(n) pass through ``_mark`` at any table size; the
        grouping from a radix argsort. ``touched`` is in the order the
        keys' surviving rows come, not in slot order: the program gathers
        and scatters the touched rows by distinct index and each key's
        walk is its own, so the outputs and the table after the step do
        not depend on it."""
        from .keymap import group_positions

        n = batch.size
        cap = batch.capacity
        keys, keys_arr = op_batch_keys_np(self.op, batch)
        if self.tier is not None and n:
            from .keymap import distinct_batch_keys
            plan = self.tier.plan_batch(
                self._keymap, distinct_batch_keys(keys, keys_arr, n))
            if plan is not None:
                self._submit_tier_plan(plan)
            self.tier.publish_gauges(len(self.slot_of_key))
        km, st = self._keymap, self.stats
        n_keys, admits = len(self.slot_of_key), km.batch_admits
        # as intp once: numpy converts any other index array on each of
        # the numbering's five passes
        gslots = km.slots_of(keys, keys_arr, n).astype(np.intp, copy=False)
        st.keys_admitted += len(self.slot_of_key) - n_keys
        st.scan_batch_admits += km.batch_admits - admits
        st.key_slots_live = len(self.slot_of_key)
        self._ensure_table(len(self.slot_of_key))
        # touched rows + dense local ids in one pass: every row writes its
        # index at its slot, the row whose index survived stands for its
        # key, and the keys are numbered in the order of those rows
        mark = self._mark
        rows = np.arange(n, dtype=np.int32)
        mark[gslots] = rows
        touched_list = gslots[mark[gslots] == rows]
        mark[touched_list] = rows[:len(touched_list)]
        lslots = mark[gslots]
        mark[touched_list] = -1
        _, within = group_positions(lslots, len(touched_list))
        max_depth = int(within.max()) + 1 if n else 1
        M = 1
        while M < max_depth:
            M <<= 1
        KB = 1
        while KB < max(1, len(touched_list)):
            KB <<= 1
        grid_idx = np.zeros(cap, dtype=np.int32)
        grid_idx[:n] = lslots * M + within
        valid = np.zeros(cap, dtype=bool)
        valid[:n] = True
        touched = np.zeros(KB, dtype=np.int32)
        touched[:len(touched_list)] = touched_list
        touched_mask = np.zeros(KB, dtype=bool)
        touched_mask[:len(touched_list)] = True
        return grid_idx, valid, touched, touched_mask, M, KB

    def program(self, M: int, KB: int):
        return cached_compile(self._cache, self._cache_lock, (M, KB),
                              lambda: self._make(M, KB))

    # -- tiered data movement ----------------------------------------------
    def _submit_tier_plan(self, plan) -> None:
        """Queue one batch's tier maintenance on the replica's dispatch
        queue: ``handle_msg`` submits the batch's own commit AFTER prep
        returns, so this lands behind every in-flight commit and ahead of
        the batch that needs the promoted rows. The movement itself is
        batched — ONE slot-row gather per leaf for the demotes, ONE
        scatter per leaf for the promotes — never per-key transfers."""
        import jax
        import jax.numpy as jnp

        tier = self.tier

        def tier_commit() -> None:
            import jax.numpy as jnp  # local: commit may run on drain
            self._ensure_table(0)  # first batch: allocate the hot tier
            t0 = time.perf_counter()
            leaves, treedef = jax.tree_util.tree_flatten(self.table)
            if len(plan.demote_keys):
                dslots = jnp.asarray(plan.demote_slots)
                cols = [np.asarray(jax.device_get(lf[dslots]))
                        for lf in leaves]
                tier.cold.put_rows(plan.demote_keys, cols)
                tier.note_demote(len(plan.demote_keys))
            if len(plan.promote_keys):
                init_leaves = jax.tree_util.tree_leaves(self.state_init)
                cols, _hits = tier.cold.take_rows(
                    plan.promote_keys, init_leaves,
                    [np.dtype(lf.dtype) for lf in leaves])
                pslots = jnp.asarray(plan.promote_slots)
                leaves = [lf.at[pslots].set(jnp.asarray(col))
                          for lf, col in zip(leaves, cols)]
                self.table = jax.tree_util.tree_unflatten(treedef, leaves)
                if self.dirty is not None:
                    # promoted rows differ from the delta base's hot tier
                    self.dirty = self.dirty.at[pslots].set(True)
                tier.note_promote(len(plan.promote_keys),
                                  (time.perf_counter() - t0) * 1e6)

        self.replica.dispatch.submit(tier_commit)

    # -- checkpointing -----------------------------------------------------
    # The whole scan state is (key -> slot dict, capacity, one device
    # pytree): device_get it to host numpy for the blob (DrJAX-style —
    # array state makes snapshots a transfer, not a serializer) and
    # device_put it back on restore. The KeySlotMap LUT refills lazily
    # from the restored dict, and compiled programs re-trace on demand.
    def snapshot_state(self) -> dict:
        import jax
        import jax.numpy as jnp
        from ..checkpoint import delta as ckpt_delta

        ctx = ckpt_delta.snapshot_ctx()
        if (self.table is not None and self.dirty is not None
                and self._base_capacity == self.table_capacity
                and ckpt_delta.delta_eligible(
                    self._delta_base, self._snaps_since_full, ctx)):
            # DELTA: gather only the rows dirtied since the last full
            # snapshot — cost scales with the touched set, not capacity
            self._snaps_since_full += 1
            repl, carry = {}, []
            if (self.tier is None
                    and len(self.slot_of_key) == self._base_nkeys):
                # no key registered since the base: the directory rides
                # as a zero-byte carry, not a re-pickle of every key.
                # Dense slots are append-only, so an unchanged count
                # means an unchanged mapping; under tiering demote /
                # promote swaps remap at constant size, so never carry.
                carry += ["slot_of_key", "table_capacity"]
            else:
                repl["slot_of_key"] = dict(self.slot_of_key)
                repl["table_capacity"] = self.table_capacity
            if self.tier is not None:
                repl["tier"] = self.tier.snapshot_delta(self._delta_base)
            return ckpt_delta.make_delta(
                self._delta_base,
                rows={"table": self._dirty_rows()},
                replace=repl or None, carry=carry or None)
        table = (None if self.table is None
                 else jax.device_get(self.table))
        d = {"slot_of_key": dict(self.slot_of_key),
             "table_capacity": self.table_capacity,
             "table": table}
        if self.tier is not None:
            from ..state.tiered import hot_table_digest
            d["tier"] = self.tier.snapshot(
                hot_digest=hot_table_digest(table))
        if ctx is not None and ckpt_delta.env_ckpt_delta():
            # this full capture is the new delta baseline; the bitmap
            # and the cold store's WAL restart from it (capture runs
            # post-drain, so no in-flight commit can race the reset)
            self._delta_base = ctx.ckpt_id
            self._base_capacity = self.table_capacity
            self._base_nkeys = len(self.slot_of_key)
            self._snaps_since_full = 0
            if self.table is not None:
                self.dirty = jnp.zeros((self.table_capacity,), bool)
            if self.tier is not None:
                self.tier.wal_reset()
        return d

    def _dirty_rows(self) -> dict:
        """Host copies of just the dirty slot rows, one gathered column
        per table leaf (tree_flatten order — matches delta._apply_rows)."""
        import jax

        dirty_np = np.asarray(jax.device_get(self.dirty)).astype(bool)
        slots = np.nonzero(dirty_np)[0].astype(np.int64)
        leaves, _ = jax.tree_util.tree_flatten(self.table)
        rows = [np.asarray(jax.device_get(lf[slots])) for lf in leaves]
        return {"slots": slots, "leaves": rows}

    def restore_state(self, state: dict) -> None:
        import jax

        # restored state starts a fresh delta lineage: the next capture
        # is FULL and re-establishes base/bitmap/WAL
        self.dirty = None
        self._delta_base = None
        self._snaps_since_full = 0
        self._base_capacity = None
        self._base_nkeys = None
        tier_blob = state.get("tier")
        if tier_blob is not None and self.tier is None:
            raise WindFlowError(
                f"{self.op.name}: checkpoint holds a TIERED key store "
                "(hot + cold) but this graph was built without "
                "with_tiering(); cold-tier keys cannot be restored into "
                "a dense table — rebuild the graph with tiering enabled")
        self.slot_of_key.clear()  # shared alias with the KeySlotMap
        self.slot_of_key.update(state.get("slot_of_key", {}))
        self._keymap.reset_index()
        table = state.get("table")
        if self.tier is not None:
            if tier_blob is not None:
                from ..state.tiered import hot_table_digest
                self.tier.restore(tier_blob,
                                  hot_digest=hot_table_digest(table))
                self.table_capacity = self.tier.hot_capacity
                self.table = (None if table is None else
                              jax.tree_util.tree_map(jax.device_put,
                                                     table))
            else:
                # dense (pre-tiering) blob into a tiered engine: every
                # checkpointed key becomes hot — dense slot ids are
                # contiguous from 0 so they are valid hot slots iff the
                # key count fits (adopt_dense refuses otherwise)
                self._adopt_dense_blob(table)
            return
        self.table_capacity = state.get("table_capacity",
                                        self.table_capacity)
        self.table = (None if table is None
                      else jax.tree_util.tree_map(jax.device_put, table))

    def _adopt_dense_blob(self, table) -> None:
        import jax
        import jax.numpy as jnp

        self.tier.adopt_dense(self.slot_of_key)
        cap = self.tier.hot_capacity
        self.table_capacity = cap
        if table is None:
            self.table = None
            return
        # refit the dense table to the hot tier's shape: occupied rows
        # carry over (all slots < key count <= cap), padding rows start
        # from the initial state
        self.table = jax.tree_util.tree_map(
            lambda v, a: jnp.full((cap,), v, dtype=np.asarray(a).dtype)
                            .at[:min(cap, len(a))]
                            .set(jnp.asarray(np.asarray(a)[:cap])),
            self.state_init, table)


class StatefulMapTPUReplica(TPUReplicaBase):
    """Per-key device state via the grid scan (see _KeyedStateScan)."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        self.engine = _KeyedStateScan(self, op.func, op.state_init, False)

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        # host prep: slot mapping + grid assembly (grid_meta drains the
        # pipeline itself iff the state table must grow); the commit
        # reads self.engine.table AT COMMIT TIME — earlier queued commits
        # reassign it (donation)
        grid_idx, valid, touched, tmask, M, KB = self.engine.grid_meta(batch)
        prog = self.engine.program(M, KB)

        def commit() -> None:
            outs, table2, dirty2 = prog(batch.fields, grid_idx, valid,
                                        touched, tmask, self.engine.table,
                                        self.engine.dirty)
            self.stats.device_programs_run += 1
            self.engine.table = table2
            self.engine.dirty = dirty2
            self._emit_batch(batch.with_fields(outs))

        return commit

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st["scan"] = self.engine.snapshot_state()
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        if "scan" in state:
            self.engine.restore_state(state["scan"])


class StatefulFilterTPUReplica(TPUReplicaBase):
    """Keyed-state predicate + compaction in one program (the reference's
    stateful Filter_GPU, ``filter_gpu.hpp:331-335``)."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        self.engine = _KeyedStateScan(self, op.pred, op.state_init, True)

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        grid_idx, valid, touched, tmask, M, KB = self.engine.grid_meta(batch)
        prog = self.engine.program(M, KB)

        @split_commit
        def commit() -> Callable[[], None]:
            out, order, count, table2, dirty2 = prog(
                batch.fields, grid_idx, valid, touched, tmask,
                self.engine.table, self.engine.dirty)
            self.stats.device_programs_run += 1
            self.engine.table = table2
            self.engine.dirty = dirty2
            # emit_compacted's int(count)/np.asarray(order) readbacks run
            # in the finish, one launch later — no fresh-result stall
            return self.finish_compacted(batch, out, order, count)

        return commit

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st["scan"] = self.engine.snapshot_state()
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        if "scan" in state:
            self.engine.restore_state(state["scan"])


# ---------------------------------------------------------------------------
# Filter_TPU
# ---------------------------------------------------------------------------
class Filter_TPU(TPUOperatorBase):
    """Stateless: ``pred(fields) -> bool column``; the batch compacts.
    Stateful (``state_init`` given): ``pred(row, state) -> (keep, state)``
    over one row's scalars and its key's state (leaves of any shape;
    grid scan); ``key_capacity`` sizes the state table."""

    def __init__(self, pred: Callable, name: str = "filter_tpu",
                 parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor=None, output_batch_size: int = 0,
                 schema: Optional[TupleSchema] = None,
                 state_init: Any = None, tiering=None,
                 key_capacity: Optional[int] = None) -> None:
        check_keyed_state(name, state_init, tiering, key_capacity)
        if state_init is not None and key_extractor is None:
            raise WindFlowError(f"{name}: stateful Filter_TPU requires a "
                                "key extractor (KEYBY)")
        if tiering is not None and state_init is None:
            raise WindFlowError(f"{name}: with_tiering requires keyed "
                                "state (with_state)")
        super().__init__(name, parallelism,
                         RoutingMode.KEYBY if state_init is not None
                         else input_routing,
                         key_extractor, output_batch_size, schema)
        self.pred = pred
        self.state_init = state_init
        self.tiering = tiering
        self.key_capacity = key_capacity

    @property
    def fusion_role(self) -> Optional[str]:
        return "transform"

    def device_kernel(self):
        if self.state_init is not None:
            raise WindFlowError(f"{self.name}: stateful Filter_TPU carries "
                                "a grid-scan engine, not a stateless kernel")
        pred = self.pred

        def kernel(fields, valid, carry):
            # narrow the keep mask instead of compacting: chained
            # operators see the batch at full capacity and the single
            # chain-exit compaction settles the survivors
            return fields, valid & pred(fields).astype(bool), carry

        return kernel

    def build_replicas(self) -> None:
        cls = (StatefulFilterTPUReplica if self.state_init is not None
               else FilterTPUReplica)
        self.replicas = [cls(self, i) for i in range(self.parallelism)]


class FilterTPUReplica(TPUReplicaBase):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        import jax.numpy as jnp

        kernel = op.device_kernel()

        def run(fields, size):
            n = next(iter(fields.values())).shape[0]
            fields2, keep, _ = kernel(fields, jnp.arange(n) < size, None)
            order = _compact_order(keep)  # keepers first, stable
            out = {k: v[order] for k, v in fields2.items()}
            return out, order, jnp.sum(keep)

        self._jitted = instrumented_jit(
            run, self.stats, label=op.name,
            program=program_name(_PROG_FILTER, op.name))

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        @split_commit
        def commit() -> Callable[[], None]:
            out, order, count = self._jitted(batch.fields, batch.size)
            self.stats.device_programs_run += 1
            return self.finish_compacted(batch, out, order, count)

        return commit

    def prewarm(self, caps) -> Optional[int]:
        """See ``MapTPUReplica.prewarm`` (``size`` traces as a weak
        scalar, so one warm call per capacity covers every real size)."""
        import jax
        sch = self.op.schema
        if sch is None:
            return None
        for cap in caps:
            jax.block_until_ready(
                self._jitted(prewarm_zero_fields(self.op, cap), 0))
        return len(caps)

    # empty batches are dropped entirely (the reference shrinks to zero and
    # forwards; dropping is equivalent because watermarks flow via puncts)


# ---------------------------------------------------------------------------
# Reduce_TPU
# ---------------------------------------------------------------------------
class Reduce_TPU(TPUOperatorBase):
    """Per-batch combine (``combine(fields_a, fields_b) -> fields``,
    associative+commutative, ``API:78-80``). Keyed (key extractor given):
    one output per distinct key per batch (reference ``reduce_by_key``,
    ``reduce_gpu.hpp:245-251``). Global (no key): the whole batch folds to
    ONE output tuple (reference ``thrust::reduce``,
    ``reduce_gpu.hpp:269-272``)."""

    def __init__(self, combine: Callable, key_extractor=None,
                 name: str = "reduce_tpu", parallelism: int = 1,
                 output_batch_size: int = 0,
                 schema: Optional[TupleSchema] = None) -> None:
        routing = (RoutingMode.KEYBY if key_extractor is not None
                   else RoutingMode.FORWARD)
        super().__init__(name, parallelism, routing, key_extractor,
                         output_batch_size, schema)
        self.combine = combine

    @property
    def fusion_role(self) -> Optional[str]:
        # both variants change cardinality, so both may only END a fused
        # chain. The keyed reduce's KEYBY shuffle degenerates to an
        # in-program sort/segment when no cross-device re-shard exists
        # (single replica, or a key-compatible keyed entry) — the
        # legality check in topology/stage.py gates exactly that
        return ("terminator" if self.key_extractor is None
                else "keyed_terminator")

    def build_replicas(self) -> None:
        cls = (ReduceTPUReplica if self.key_extractor is not None
               else GlobalReduceTPUReplica)
        self.replicas = [cls(self, i) for i in range(self.parallelism)]


class GlobalReduceTPUReplica(TPUReplicaBase):
    """Whole-batch fold to one tuple via ``masked_tree_reduce`` (shared
    with the fused-chain exit, which feeds it the chain's keep mask)."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        import jax.numpy as jnp

        combine = op.combine

        def run(fields, size):
            n = next(iter(fields.values())).shape[0]
            return masked_tree_reduce(combine, fields, jnp.arange(n) < size)

        self._jitted = instrumented_jit(
            run, self.stats, label=op.name,
            program=program_name(_PROG_REDUCE, op.name))

    def prewarm(self, caps) -> Optional[int]:
        """See ``MapTPUReplica.prewarm``."""
        import jax
        sch = self.op.schema
        if sch is None:
            return None
        for cap in caps:
            jax.block_until_ready(
                self._jitted(prewarm_zero_fields(self.op, cap), 0))
        return len(caps)

    def process_device_batch(self, batch: BatchTPU) -> None:
        if batch.size == 0:
            return
        out = self._jitted(batch.fields, batch.size)
        self.stats.device_programs_run += 1
        ts = np.array([int(batch.ts_host[:batch.size].max())],
                      dtype=np.int64)
        nb = BatchTPU(out, ts, 1, batch.schema, batch.wm)
        nb.stream_tag = batch.stream_tag
        nb.copy_trace_from(batch)
        self._emit_batch(nb)


class ReduceTPUReplica(TPUReplicaBase):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        import jax
        import jax.numpy as jnp

        combine = op.combine

        def run(fields, order, s):
            # order/s precomputed on HOST from the key metadata (already
            # touched for slot mapping; radix argsort of small ids) — no
            # device sort at all
            f = {k: v[order] for k, v in fields.items()}

            def seg_op(a, b):
                fa, sa = a
                fb, sb = b
                same = sa == sb
                merged = combine(fa, fb)
                # fields the combine does not return pass through unchanged
                out = {k: jnp.where(same, merged.get(k, fb[k]), fb[k])
                       for k in fb}
                return out, sb

            scanned, _ = jax.lax.associative_scan(seg_op, (f, s))
            n = s.shape[0]
            is_last = jnp.concatenate(
                [s[1:] != s[:-1], jnp.ones((1,), dtype=bool)])
            idx = jnp.nonzero(is_last, size=n, fill_value=n - 1)[0]
            return {k: v[idx] for k, v in scanned.items()}

        self._jitted = instrumented_jit(
            run, self.stats, label=op.name,
            program=program_name(_PROG_REDUCE, op.name))

    def prewarm(self, caps) -> Optional[int]:
        """See ``MapTPUReplica.prewarm`` — the keyed reduce's program
        signature is (fields, order, slots) at one capacity; the
        order/slot VALUES are runtime data, not signature."""
        import jax
        sch = self.op.schema
        if sch is None:
            return None
        for cap in caps:
            order = jax.device_put(np.arange(cap, dtype=np.int32))
            slots = jax.device_put(np.zeros(cap, dtype=np.int32))
            jax.block_until_ready(
                self._jitted(prewarm_zero_fields(self.op, cap), order, slots))
        return len(caps)

    def _order_and_slots(self, batch: BatchTPU):
        """See ``reduce_order_and_slots`` (module-level: shared with the
        fused chain's keyed-terminator exit)."""
        return reduce_order_and_slots(self.op, batch)

    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        import jax

        # host prep: ONE key sort + slot metadata; the program call and
        # the output-batch assembly are the deferred commit stage
        order_np, ssorted, slot_of_key = self._order_and_slots(batch)
        n_out = len(slot_of_key)
        if n_out == 0:
            return None
        order_dev = jax.device_put(order_np)
        ssorted_dev = jax.device_put(ssorted)
        out_keys = list(slot_of_key.keys())  # insertion order == slot order
        batch_ts = int(batch.ts_host[:batch.size].max()) if batch.size else 0

        def commit() -> None:
            out_fields = self._jitted(batch.fields, order_dev, ssorted_dev)
            self.stats.device_programs_run += 1
            ts2 = np.full(batch.capacity, batch_ts, dtype=np.int64)
            nb = BatchTPU(out_fields, ts2, n_out, batch.schema, batch.wm,
                          out_keys)
            nb.stream_tag = batch.stream_tag
            nb.copy_trace_from(batch)
            nb.key_origin = own_key_spec(self.op)  # this reduce's keys
            self._emit_batch(nb)

        return commit
