"""Mesh-sharded keyed operators: Map_Mesh / Filter_Mesh / Reduce_Mesh.

The keyed-state plane of the single-chip device operators, sharded over
a device mesh (ROADMAP: "key cardinality and state size scale with
devices instead of one chip's HBM"):

- **stateful Map/Filter** (``Map_TPU_Builder(...).with_state(...)
  .with_mesh(...)``): the per-key grid-scan state table — one row per
  dense key slot — is block-sharded along the slot axis over EVERY
  device of the ``('key','data')`` mesh (flattened owner order,
  ``core.MESH_AXES``; a grid-scan transition is sequential per key, so
  unlike the FFAT forest no associative data-axis merge exists and each
  key lives on exactly one device). One ``shard_map``-jitted step per
  staged batch: bucket-by-owner + ``lax.all_to_all`` (the KEYBY shuffle
  as a device collective — the topology edge into the operator stays
  single-destination, replacing the host-side keyby emitters on this
  edge), the (k_local x M) grid scan on each owner, and an inverse
  all_to_all returning outputs to arrival order;
- **keyed Reduce** (``Reduce_TPU_Builder(...).with_key_by(...)
  .with_mesh(...)``): per-batch ``reduce_by_key`` — the single-chip
  ``Reduce_TPU`` semantics, one output per distinct key per batch —
  with the shuffle and the segmented combine both on device.

Shared mechanics (the ``Ffat_Windows_Mesh`` idiom): ONE host replica
drives the whole mesh; arbitrary int64 keys densify to slots through a
host ``KeySlotMap`` (``key_capacity`` is the declared bound, exceeded =
loud error); batches pad to the mesh's global batch with slot = -1
lanes the routing drops. Fault tolerance: ``snapshot_state`` ships the
state table as PER-SHARD row blocks gathered under one manifest entry;
``restore_state`` relayouts onto a different mesh factorization or
device count by slot-row gather (arXiv:2112.01075's redistribution
decomposition; the ``StateRepartitioner`` idiom at mesh grain).
``rescale()`` refuses mesh operators — parallelism is the mesh shape —
via ``scaling.repartition.repartition_refusal``.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..basic import KeyCapacityError, OpType, RoutingMode, WindFlowError
from ..tpu.batch import (BatchTPU, bucket_capacity, gather_columns,
                         host_columns)
from ..tpu.ops_tpu import (TPUOperatorBase, TPUReplicaBase, cached_compile,
                           has_array_leaves)
from ..tpu.schema import TupleSchema


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------
def refuse_array_leaves(name: str, state_init) -> None:
    """The mesh plane's keyed state (``sharded_grid_scan``) holds one
    scalar a leaf and key; an array leaf runs on one chip."""
    if has_array_leaves(state_init):
        raise WindFlowError(
            f"{name}: the mesh plane's keyed state (sharded_grid_scan) "
            "holds scalar leaves only; a state leaf that is an array runs "
            "on one chip (drop with_mesh)")


class _MeshKeyedOperator(TPUOperatorBase):
    """Shared metadata of the mesh-sharded keyed operators."""

    op_type = OpType.TPU
    # mesh execution plane: parallelism is the mesh shape, not the
    # replica count; snapshot/restore ships per-shard blocks and can
    # relayout onto a different mesh factorization
    is_mesh = True
    mesh_snapshot_capable = True

    def __init__(self, name: str, key_extractor, schema,
                 key_capacity: int, n_devices: Optional[int],
                 mesh_shape: Optional[tuple],
                 local_batch: Optional[int]) -> None:
        if key_extractor is None:
            raise WindFlowError(f"{name}: mesh operators require a key "
                                "extractor (with_key_by)")
        # ONE host replica drives the whole mesh; parallelism is the mesh
        super().__init__(name, 1, RoutingMode.KEYBY, key_extractor, 0,
                         schema)
        self.key_capacity = max(1, int(key_capacity))
        self.n_devices = n_devices
        self.mesh_shape = mesh_shape
        self.local_batch = local_batch


class Map_Mesh(_MeshKeyedOperator):
    """Stateful keyed map over the mesh: ``func(row, state) ->
    (row, state)`` scanned in arrival order, state block-sharded over
    the devices."""

    def __init__(self, func: Callable, state_init: Any, key_extractor,
                 name: str = "map_mesh", key_capacity: int = 1024,
                 n_devices: Optional[int] = None,
                 mesh_shape: Optional[tuple] = None,
                 local_batch: Optional[int] = None,
                 schema: Optional[TupleSchema] = None,
                 tiering=None) -> None:
        if state_init is None:
            raise WindFlowError(
                f"{name}: with_mesh applies to the KEYED-STATE plane; a "
                "stateless Map_TPU is data-parallel already (every chip "
                "can run it) — add with_state(...) or drop with_mesh")
        refuse_array_leaves(name, state_init)
        super().__init__(name, key_extractor, schema, key_capacity,
                         n_devices, mesh_shape, local_batch)
        self.func = func
        self.state_init = state_init
        self.tiering = tiering

    def build_replicas(self) -> None:
        self.replicas = [MapMeshReplica(self, 0)]


class Filter_Mesh(_MeshKeyedOperator):
    """Stateful keyed filter over the mesh: ``pred(row, state) ->
    (keep, state)``; the batch compacts on the host side of the step."""

    def __init__(self, pred: Callable, state_init: Any, key_extractor,
                 name: str = "filter_mesh", key_capacity: int = 1024,
                 n_devices: Optional[int] = None,
                 mesh_shape: Optional[tuple] = None,
                 local_batch: Optional[int] = None,
                 schema: Optional[TupleSchema] = None,
                 tiering=None) -> None:
        if state_init is None:
            raise WindFlowError(
                f"{name}: with_mesh applies to the KEYED-STATE plane; a "
                "stateless Filter_TPU is data-parallel already — add "
                "with_state(...) or drop with_mesh")
        refuse_array_leaves(name, state_init)
        super().__init__(name, key_extractor, schema, key_capacity,
                         n_devices, mesh_shape, local_batch)
        self.pred = pred
        self.state_init = state_init
        self.tiering = tiering

    def build_replicas(self) -> None:
        self.replicas = [FilterMeshReplica(self, 0)]


class Reduce_Mesh(_MeshKeyedOperator):
    """Keyed per-batch reduce over the mesh (``Reduce_TPU`` semantics:
    one output per distinct key per batch; combine associative +
    commutative, ``API:78-80``)."""

    def __init__(self, combine: Callable, key_extractor,
                 name: str = "reduce_mesh", key_capacity: int = 1024,
                 n_devices: Optional[int] = None,
                 mesh_shape: Optional[tuple] = None,
                 local_batch: Optional[int] = None,
                 schema: Optional[TupleSchema] = None) -> None:
        if key_extractor is None:
            raise WindFlowError(
                f"{name}: the GLOBAL (unkeyed) reduce folds one "
                "stream-wide value — there is no keyed plane to shard; "
                "with_mesh requires with_key_by")
        super().__init__(name, key_extractor, schema, key_capacity,
                         n_devices, mesh_shape, local_batch)
        self.combine = combine

    def build_replicas(self) -> None:
        self.replicas = [ReduceMeshReplica(self, 0)]


# ---------------------------------------------------------------------------
# host replicas
# ---------------------------------------------------------------------------
class _MeshReplicaBase(TPUReplicaBase):
    """Shared host control loop: lazy mesh construction, key->slot
    densification, GB-slice padding, mesh stats, and the snapshot/
    restore scaffolding (per-shard blocks, relayout on restore)."""

    def __init__(self, op: _MeshKeyedOperator, idx: int) -> None:
        super().__init__(op, idx)
        from ..tpu.keymap import KeySlotMap
        self._key_by_slot = np.zeros(op.key_capacity, np.int64)
        self._keymap = KeySlotMap(on_new=self._on_new_key)
        self._mesh = None  # lazy: the device mesh exists at run time only
        self._sharding = None
        self._ns = 0
        self._k_local = 0
        self._K_pad = 0
        self._GB = 0
        self._local_batch = 0
        self._val_fields: List[str] = []
        self._val_dtypes: Dict[str, np.dtype] = {}
        self._gpos_dev = None
        self._step_bytes = 0
        self._pending_restore: Optional[dict] = None
        self._tier = None  # _MeshScanReplicaBase builds it when declared

    def _on_new_key(self, key, slot: int) -> None:
        if slot >= self.op.key_capacity:
            raise KeyCapacityError(
                self.op.name, self._K_pad or self.op.key_capacity,
                slot - self.op.key_capacity + 1,
                hint="raise with_mesh(key_capacity=) or enable "
                     "with_tiering to spill the cold key tail")
        self._key_by_slot[slot] = key

    # -- lazy mesh/program construction ---------------------------------
    def _mesh_ensure(self, val_dtypes: Dict[str, Any], cap: int) -> None:
        if self._mesh is not None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .core import MESH_AXES, make_key_mesh, mesh_shard_count

        op = self.op
        n_dev = op.n_devices or len(jax.devices())
        self._mesh = make_key_mesh(n_dev, shape=op.mesh_shape)
        ns = mesh_shard_count(self._mesh)
        self._ns = ns
        self._note_degraded(n_dev, ns)
        self._local_batch = op.local_batch or max(1, math.ceil(cap / ns))
        self._GB = ns * self._local_batch
        self._K_pad = math.ceil(op.key_capacity / ns) * ns
        self._k_local = self._K_pad // ns
        self._val_dtypes = {f: np.dtype(dt) for f, dt in val_dtypes.items()}
        self._val_fields = list(self._val_dtypes)
        self._sharding = NamedSharding(self._mesh, P(MESH_AXES))
        self._gpos_dev = jax.device_put(
            np.arange(self._GB, dtype=np.int32), self._sharding)
        self._step_bytes = self._GB * (8 + sum(
            dt.itemsize for dt in self._val_dtypes.values()))
        self.stats.mesh_devices = ns
        self._after_mesh_ensure()

    def _note_degraded(self, requested: int, ns: int) -> None:
        """Degraded-capacity report: the mesh came up on fewer devices
        than the op would otherwise use because the supervision plane
        excluded lost devices (mesh/core registry). Surfaced per-replica
        as ``Mesh_degraded_devices`` plus a ``mesh:degrade`` flight span;
        the supervisor aggregates it into ``Recovery_degraded_devices``
        and the overload governor jumps straight to SHED while > 0."""
        import jax

        from .core import excluded_device_ids

        excl = excluded_device_ids()
        if not excl:
            self.stats.mesh_degraded = 0
            return
        want = min(int(requested), len(jax.devices()))
        degraded = max(0, want - int(ns))
        self.stats.mesh_degraded = degraded
        if degraded:
            from ..monitoring.flightrec import thread_recorder
            rec = thread_recorder()
            if rec is not None:
                rec.event("mesh:degrade", 0.0, {
                    "op": self.op.name, "devices": ns,
                    "excluded": sorted(excl), "requested": want})

    def _after_mesh_ensure(self) -> None:
        raise NotImplementedError

    def _ensure(self, batch: BatchTPU) -> None:
        if self._mesh is None:
            self._mesh_ensure(
                {f: batch.schema.fields[f] for f in batch.fields},
                batch.capacity)

    # -- per-batch key plane --------------------------------------------
    def _batch_slots(self, batch: BatchTPU):
        n = batch.size
        keys = np.asarray(self.batch_keys(batch))[:n]
        if keys.dtype.kind not in "iu":
            raise WindFlowError(
                f"{self.op.name}: mesh operators require integer keys "
                f"(sparse/negative int64 ok); got dtype {keys.dtype}")
        if self._tier is not None and n:
            # tier pre-pass: the mesh replica commits synchronously (no
            # deferred dispatch), so the batched promote/demote applies
            # inline before the slot resolution
            plan = self._tier.plan_batch(
                self._keymap, [int(k) for k in np.unique(keys)])
            if plan is not None:
                self._apply_tier_plan(plan)
            self._tier.publish_gauges(len(self._keymap))
        slots = np.asarray(self._keymap.slots_of(keys, keys, n),
                           dtype=np.int64)
        from .core import mesh_occupancy
        occ, skew = mesh_occupancy(len(self._keymap), self._k_local,
                                   self._ns)
        self.stats.mesh_shard_occupancy = occ
        self.stats.mesh_shard_skew = skew
        return slots, keys

    def _pad_slice(self, slots, cols, lo: int, hi: int):
        """One GB-sized padded slice: slot = -1 lanes mark padding (the
        routing drops them), value columns zero-fill."""
        import jax

        GB = self._GB
        m = hi - lo
        s_sl = np.full(GB, -1, np.int32)
        s_sl[:m] = slots[lo:hi]
        v_sl = {}
        for f in self._val_fields:
            buf = np.zeros(GB, self._val_dtypes[f])
            buf[:m] = cols[f][lo:hi]
            v_sl[f] = jax.device_put(buf, self._sharding)
        return jax.device_put(s_sl, self._sharding), v_sl

    # -- snapshot/restore scaffolding -----------------------------------
    _STATE_KEY = "mesh_state"

    def _snapshot_extra(self) -> dict:
        return {}

    def _device_state_shards(self) -> Optional[list]:
        return None

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()  # drains the dispatch queue
        if self._mesh is None:
            if self._pending_restore is not None:
                # restored but never touched since: pass the blob through
                st[self._STATE_KEY] = self._pending_restore
            return st
        t0 = time.perf_counter()
        d = {
            "slot_of_key": dict(self._keymap.slot_of_key),
            "key_by_slot": self._key_by_slot.copy(),
            "key_capacity": self.op.key_capacity,
            "K_pad": self._K_pad, "n_shards": self._ns,
            "local_batch": self._local_batch,
            "val_dtypes": {f: dt.str
                           for f, dt in self._val_dtypes.items()},
            # per-shard blobs gathered under this one manifest entry
            "table_shards": self._device_state_shards(),
        }
        d.update(self._snapshot_extra())
        st[self._STATE_KEY] = d
        rec = self.stats.recorder
        if rec is not None:
            rec.event("mesh:snapshot",
                      (time.perf_counter() - t0) * 1e6,
                      {"keys": len(self._keymap.slot_of_key),
                       "shards": self._ns})
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        d = state.get(self._STATE_KEY)
        if d is not None:
            # applied lazily once the mesh exists (_ensure): the target
            # mesh factorization may differ from the checkpointed one
            self._pending_restore = d

    def _restore_keymap(self, d: dict) -> None:
        op = self.op
        if len(d["slot_of_key"]) > op.key_capacity:
            raise KeyCapacityError(
                op.name, self._K_pad or op.key_capacity,
                len(d["slot_of_key"]) - op.key_capacity,
                hint="restore holds more distinct keys than this graph's "
                     "key_capacity; raise with_mesh(key_capacity=) to at "
                     "least the checkpointed count")
        self._keymap.slot_of_key.clear()
        self._keymap.slot_of_key.update(d["slot_of_key"])
        self._keymap.reset_index()
        kbs = np.asarray(d["key_by_slot"])
        self._key_by_slot[:] = 0
        n_copy = min(len(kbs), op.key_capacity)
        self._key_by_slot[:n_copy] = kbs[:n_copy]


class _MeshScanReplicaBase(_MeshReplicaBase):
    """Stateful Map/Filter over the mesh: the grid-scan table
    block-sharded along the slot axis; one sharded step per GB slice."""

    filter_mode = False
    _STATE_KEY = "mesh_scan"

    def __init__(self, op, idx) -> None:
        super().__init__(op, idx)
        self._table = None
        self._out_schema: Optional[TupleSchema] = None
        # incremental checkpointing (WF_CKPT_DELTA): host-side dirty
        # slot set — each batch and each tier promotion marks the global
        # slot rows it rewrites, so a delta snapshot ships per-shard
        # row patches instead of the whole sharded table
        self._ckpt_dirty: set = set()
        self._delta_base = None  # epoch id of the last full snapshot
        self._snaps_since_full = 0
        self._base_nkeys = None  # key count at the last full snapshot
        self._base_geom = None  # (K_pad, n_shards) at the last full
        cfg = getattr(op, "tiering", None)
        if cfg is not None:
            if cfg.hot_capacity > op.key_capacity:
                raise WindFlowError(
                    f"{op.name}: with_tiering(hot_capacity="
                    f"{cfg.hot_capacity}) exceeds with_mesh(key_capacity="
                    f"{op.key_capacity}) — the mesh table IS the hot "
                    "tier; raise key_capacity or lower hot_capacity")
            from ..state.tiered import TieredKeyStore
            self._tier = TieredKeyStore(f"{op.name}_mesh_tier", cfg,
                                        stats=self.stats)

    @property
    def functor(self) -> Callable:
        raise NotImplementedError

    def _apply_tier_plan(self, plan) -> None:
        """Batched tier movement against the SHARDED table: one slot-row
        gather per leaf feeds the cold writes, one scatter per leaf lands
        the promotions (re-pinned to the mesh sharding — an eager
        scatter's output sharding is XLA's choice, the table's is not)."""
        import jax
        import jax.numpy as jnp

        tier = self._tier
        t0 = time.perf_counter()
        leaves, treedef = jax.tree_util.tree_flatten(self._table)
        if len(plan.demote_keys):
            dslots = jnp.asarray(plan.demote_slots)
            cols = [np.asarray(jax.device_get(lf[dslots]))
                    for lf in leaves]
            tier.cold.put_rows(plan.demote_keys, cols)
            tier.note_demote(len(plan.demote_keys))
        if len(plan.promote_keys):
            init_leaves = jax.tree_util.tree_leaves(self.op.state_init)
            cols, _hits = tier.cold.take_rows(
                plan.promote_keys, init_leaves,
                [np.dtype(lf.dtype) for lf in leaves])
            pslots = jnp.asarray(plan.promote_slots)
            leaves = [jax.device_put(
                          lf.at[pslots].set(jnp.asarray(col)),
                          self._sharding)
                      for lf, col in zip(leaves, cols)]
            self._table = jax.tree_util.tree_unflatten(treedef, leaves)
            for k, s in zip(plan.promote_keys, plan.promote_slots):
                self._key_by_slot[int(s)] = k
            self._ckpt_dirty.update(int(s) for s in plan.promote_slots)
            tier.note_promote(len(plan.promote_keys),
                              (time.perf_counter() - t0) * 1e6)

    def _after_mesh_ensure(self) -> None:
        import jax

        from .core import make_mesh_table

        op = self.op
        self._table = make_mesh_table(self._mesh, op.state_init,
                                      self._K_pad)
        if not self.filter_mode:
            sample_row = {f: jax.ShapeDtypeStruct((), dt)
                          for f, dt in self._val_dtypes.items()}
            state_abs = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(
                    np.shape(v), np.asarray(v).dtype), op.state_init)
            out_shapes, _ = jax.eval_shape(self.functor, sample_row,
                                           state_abs)
            self._out_schema = TupleSchema(
                {f: np.dtype(s.dtype) for f, s in out_shapes.items()})
        if self._pending_restore is not None:
            self._apply_pending_restore()

    def _program(self, M: int):
        from .core import sharded_grid_scan
        op = self.op
        return cached_compile(
            op._scan_prog_cache, op._scan_prog_lock,
            ("mesh", M, self._GB),
            lambda: sharded_grid_scan(self._mesh, self.functor,
                                      self.filter_mode, op.key_capacity,
                                      M, self._local_batch)[0])

    # -- streaming ------------------------------------------------------
    def process_device_batch(self, batch: BatchTPU) -> None:
        self._ensure(batch)
        n = batch.size
        if n == 0:
            return
        slots, keys_raw = self._batch_slots(batch)
        from ..checkpoint.delta import env_ckpt_delta
        if env_ckpt_delta():
            # every slot row this batch scans through is dirty vs base
            self._ckpt_dirty.update(np.unique(slots).tolist())
        cols = {f: c[:n] for f, c in host_columns(
            batch.fields, self._val_fields).items()}
        ts = np.asarray(batch.ts_host[:n])
        GB = self._GB
        for lo in range(0, n, GB):
            hi = min(lo + GB, n)
            cnt = np.bincount(slots[lo:hi],
                              minlength=1) if hi > lo else np.zeros(1)
            mx = max(1, int(cnt.max()))
            M = 1
            while M < mx:
                M <<= 1
            prog = self._program(M)
            s_dev, v_sl = self._pad_slice(slots, cols, lo, hi)
            t0 = time.perf_counter()
            table2, out, _n_ok = prog(self._table, s_dev,
                                      self._gpos_dev, v_sl)
            self._table = table2
            self.stats.device_programs_run += 1
            self.stats.note_mesh_step(
                (time.perf_counter() - t0) * 1e6, self._step_bytes)
            self._emit_slice(batch, out, ts, keys_raw, lo, hi)

    def _emit_slice(self, batch, out, ts, keys_raw, lo, hi) -> None:
        raise NotImplementedError

    # -- compile-stability pre-warm -------------------------------------
    def prewarm(self, caps) -> Optional[int]:
        """Compile the mesh step's small-M bucket signatures on
        all-padding slices (state untouched: every lane is dropped by
        the routing). The per-key-depth axis M is runtime cardinality,
        so deeper batches still trace on demand — but the M=1/2/4
        buckets cover the common keyed-stream shapes. None when the
        schema is inferred at the staging boundary."""
        sch = self.op.schema
        if sch is None:
            return None
        import jax

        if self._mesh is None:
            self._mesh_ensure(dict(sch.fields), max(caps))
        warmed = 0
        for M in (1, 2, 4):
            prog = self._program(M)
            s_dev = jax.device_put(np.full(self._GB, -1, np.int32),
                                   self._sharding)
            v_sl = {f: jax.device_put(np.zeros(self._GB, dt),
                                      self._sharding)
                    for f, dt in self._val_dtypes.items()}
            out = prog(self._table, s_dev, self._gpos_dev, v_sl)
            self._table = out[0]
            jax.block_until_ready(
                jax.tree_util.tree_leaves(self._table)[0])
            warmed += 1
        return warmed

    # -- sharded fault tolerance ----------------------------------------
    def snapshot_state(self) -> dict:
        from ..checkpoint import delta as ckpt_delta

        ctx = ckpt_delta.snapshot_ctx()
        if (self._mesh is not None and self._table is not None
                and self._base_geom == (self._K_pad, self._ns)
                and ckpt_delta.delta_eligible(
                    self._delta_base, self._snaps_since_full, ctx)):
            # DELTA: the TPUReplicaBase part (drain + generic fields)
            # still captures fully; only the mesh_scan entry shrinks to
            # per-shard patches of the dirty slot rows
            st = TPUReplicaBase.snapshot_state(self)
            self._snaps_since_full += 1
            st[self._STATE_KEY] = self._snapshot_mesh_delta()
            return st
        st = super().snapshot_state()
        if (ctx is not None and ckpt_delta.env_ckpt_delta()
                and self._mesh is not None and self._table is not None):
            # this full capture is the new delta baseline
            self._delta_base = ctx.ckpt_id
            self._base_geom = (self._K_pad, self._ns)
            self._base_nkeys = len(self._keymap.slot_of_key)
            self._snaps_since_full = 0
            self._ckpt_dirty = set()
            if self._tier is not None:
                self._tier.wal_reset()
        return st

    def _snapshot_mesh_delta(self) -> dict:
        """Delta against the last full snapshot: ONE cross-shard gather
        of the dirty slot rows, split into per-shard local-row patches
        (shard s owns global rows [s*k_local, (s+1)*k_local))."""
        import jax
        import jax.numpy as jnp

        from ..checkpoint import delta as ckpt_delta

        sl = np.asarray(sorted(self._ckpt_dirty), dtype=np.int64)
        kl = self._k_local
        leaves, _ = jax.tree_util.tree_flatten(self._table)
        jsl = jnp.asarray(sl)
        rows = [np.asarray(jax.device_get(lf[jsl])) for lf in leaves]
        shard_of = sl // kl if len(sl) else sl
        patches: List[Optional[dict]] = []
        for s in range(self._ns):
            m = shard_of == s
            if not len(sl) or not m.any():
                patches.append(None)
                continue
            patches.append({"slots": sl[m] - s * kl,
                            "leaves": [r[m] for r in rows]})
        repl = {"key_capacity": self.op.key_capacity,
                "K_pad": self._K_pad, "n_shards": self._ns,
                "local_batch": self._local_batch,
                "val_dtypes": {f: dt.str
                               for f, dt in self._val_dtypes.items()}}
        rows = {}
        carry = []
        if (self._tier is None
                and len(self._keymap.slot_of_key) == self._base_nkeys):
            # no key registered since the base: the directory (and its
            # device twin by-slot column) is a zero-byte carry. Slots
            # are append-only without tiering; tier swaps remap at
            # constant size, so never carry there.
            carry += ["slot_of_key", "key_by_slot"]
        else:
            repl["slot_of_key"] = dict(self._keymap.slot_of_key)
            rows["key_by_slot"] = {
                "slots": sl, "leaves": [self._key_by_slot[sl].copy()]}
        node = ckpt_delta.make_delta(
            self._delta_base, rows=rows or None,
            shards={"table_shards": patches},
            replace=repl, carry=carry or None)
        if self._tier is not None:
            node["replace"]["tier"] = self._tier.snapshot_delta(
                self._delta_base)
        return node

    def restore_state(self, state: dict) -> None:
        # restored state starts a fresh delta lineage
        self._ckpt_dirty = set()
        self._delta_base = None
        self._snaps_since_full = 0
        self._base_geom = None
        self._base_nkeys = None
        super().restore_state(state)

    def _snapshot_extra(self) -> dict:
        if self._tier is None:
            return {}
        import jax

        from ..state.tiered import hot_table_digest

        host = (None if self._table is None
                else jax.device_get(self._table))
        return {"tier": self._tier.snapshot(
            hot_digest=hot_table_digest(host))}

    def _device_state_shards(self) -> Optional[list]:
        if self._table is None:
            return None
        import jax

        tmap = jax.tree_util.tree_map
        host = tmap(lambda a: np.ascontiguousarray(
            np.asarray(jax.device_get(a))), self._table)
        kl = self._k_local
        return [tmap(lambda a, _s=s: a[_s * kl:(_s + 1) * kl], host)
                for s in range(self._ns)]

    def _apply_pending_restore(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .core import MESH_AXES

        t0 = time.perf_counter()
        d, self._pending_restore = self._pending_restore, None
        tier_blob = d.get("tier")
        if tier_blob is not None and self._tier is None:
            raise WindFlowError(
                f"{self.op.name}: checkpoint holds a TIERED key store "
                "but this graph was built without with_tiering(); "
                "cold-tier keys cannot restore into a dense mesh table")
        self._restore_keymap(d)
        if self._tier is not None:
            if tier_blob is not None:
                from ..state.tiered import hot_table_digest
                shards_ = d.get("table_shards")
                full_ = (None if shards_ is None else jax.tree_util.tree_map(
                    lambda *parts: np.concatenate(parts, axis=0), *shards_))
                self._tier.restore(tier_blob,
                                   hot_digest=hot_table_digest(full_))
            else:
                # dense mesh checkpoint into a tiered graph: adopt every
                # checkpointed key as hot (refused when they don't fit)
                self._tier.adopt_dense(self._keymap.slot_of_key)
        shards = d.get("table_shards")
        if shards is None:
            return
        tmap = jax.tree_util.tree_map
        full = tmap(lambda *parts: np.concatenate(parts, axis=0), *shards)
        K_new = self._K_pad

        def fit(leaf, init_leaf):
            leaf = np.asarray(leaf)
            out = np.empty((K_new,) + leaf.shape[1:], dtype=leaf.dtype)
            out[:] = np.asarray(init_leaf, dtype=leaf.dtype)
            rows = min(leaf.shape[0], K_new)
            out[:rows] = leaf[:rows]
            return out

        sh = NamedSharding(self._mesh, P(MESH_AXES))
        self._table = tmap(
            lambda l, i: jax.device_put(fit(l, i), sh),
            full, self.op.state_init)
        rec = self.stats.recorder
        if rec is not None:
            rec.event("mesh:restore",
                      (time.perf_counter() - t0) * 1e6,
                      {"keys": len(self._keymap.slot_of_key),
                       "K_pad": K_new})


class MapMeshReplica(_MeshScanReplicaBase):
    filter_mode = False

    @property
    def functor(self) -> Callable:
        return self.op.func

    def _emit_slice(self, batch, out, ts, keys_raw, lo, hi) -> None:
        GB = self._GB
        m = hi - lo
        ts2 = np.zeros(GB, np.int64)
        ts2[:m] = ts[lo:hi]
        nb = BatchTPU(dict(out), ts2, m, self._out_schema, batch.wm,
                      keys_raw[lo:hi].tolist())
        nb.stream_tag = batch.stream_tag
        nb.copy_trace_from(batch)
        self._emit_batch(nb)


class FilterMeshReplica(_MeshScanReplicaBase):
    filter_mode = True

    @property
    def functor(self) -> Callable:
        return self.op.pred

    def _emit_slice(self, batch, out, ts, keys_raw, lo, hi) -> None:
        import jax

        m = hi - lo
        keep = np.asarray(out)[:m].astype(bool)
        kept = np.nonzero(keep)[0]
        self.stats.inputs_ignored += m - len(kept)
        if not len(kept):
            return
        cap = bucket_capacity(len(kept))
        sel = np.zeros(cap, np.int32)
        sel[:len(kept)] = lo + kept  # rows of the ORIGINAL device batch
        sel_dev = jax.device_put(sel)
        out_fields = gather_columns(batch.fields, sel_dev)
        ts2 = np.zeros(cap, np.int64)
        ts2[:len(kept)] = ts[lo:hi][kept]
        nb = BatchTPU(out_fields, ts2, len(kept), batch.schema, batch.wm,
                      keys_raw[lo:hi][kept].tolist())
        nb.stream_tag = batch.stream_tag
        nb.copy_trace_from(batch)
        self._emit_batch(nb)


class ReduceMeshReplica(_MeshReplicaBase):
    """Keyed per-batch reduce: shuffle + segmented combine on device,
    per-slot results harvested to one output row per distinct key."""

    _STATE_KEY = "mesh_reduce"

    def __init__(self, op, idx) -> None:
        super().__init__(op, idx)
        self._step = None

    def _after_mesh_ensure(self) -> None:
        from .core import sharded_keyed_reduce
        self._step = sharded_keyed_reduce(
            self._mesh, self.op.combine, self.op.key_capacity,
            self._local_batch)[0]
        if self._pending_restore is not None:
            self._restore_keymap(self._pending_restore)
            self._pending_restore = None

    def _host_combine(self, a: dict, b: dict) -> dict:
        """Cross-slice merge (only when one batch spans several GB
        slices): the user combine over host scalars; fields it does not
        return pass through unchanged."""
        merged = self.op.combine(a, b)
        return {f: np.asarray(merged[f]).astype(self._val_dtypes[f])
                if f in merged else b[f] for f in b}

    def process_device_batch(self, batch: BatchTPU) -> None:
        self._ensure(batch)
        n = batch.size
        if n == 0:
            return
        import jax  # noqa: F401  (device plane active past this point)

        slots, keys_raw = self._batch_slots(batch)
        cols = {f: c[:n] for f, c in host_columns(
            batch.fields, self._val_fields).items()}
        acc: Dict[int, dict] = {}
        GB = self._GB
        for lo in range(0, n, GB):
            hi = min(lo + GB, n)
            s_dev, v_sl = self._pad_slice(slots, cols, lo, hi)
            t0 = time.perf_counter()
            res, touched, _n_ok = self._step(s_dev, v_sl)
            self.stats.device_programs_run += 1
            self.stats.note_mesh_step(
                (time.perf_counter() - t0) * 1e6, self._step_bytes)
            touched_np = np.asarray(touched)
            res_np = {f: np.asarray(v) for f, v in res.items()}
            for s in np.nonzero(touched_np)[0]:
                row = {f: res_np[f][s] for f in res_np}
                s = int(s)
                acc[s] = row if s not in acc \
                    else self._host_combine(acc[s], row)
        if not acc:
            return
        self._emit_rows(batch, acc, ts_max=int(np.asarray(
            batch.ts_host[:n]).max()))

    def _emit_rows(self, batch, acc: Dict[int, dict], ts_max: int) -> None:
        import jax

        out_slots = sorted(acc)
        n_out = len(out_slots)
        cap = bucket_capacity(n_out)
        out_fields = {}
        for f in self._val_fields:
            buf = np.zeros(cap, self._val_dtypes[f])
            buf[:n_out] = [acc[s][f] for s in out_slots]
            out_fields[f] = jax.device_put(buf)
        ts2 = np.full(cap, ts_max, np.int64)
        keys2 = [int(self._key_by_slot[s]) for s in out_slots]
        nb = BatchTPU(out_fields, ts2, n_out, batch.schema, batch.wm,
                      keys2)
        nb.stream_tag = batch.stream_tag
        nb.copy_trace_from(batch)
        self._emit_batch(nb)

    # -- compile-stability pre-warm -------------------------------------
    def prewarm(self, caps) -> Optional[int]:
        """The keyed-reduce mesh step has ONE signature per graph (the
        GB padding makes every batch identical in shape): compile it on
        an all-padding slice. None when the schema is inferred."""
        sch = self.op.schema
        if sch is None:
            return None
        import jax

        if self._mesh is None:
            self._mesh_ensure(dict(sch.fields), max(caps))
        s_dev = jax.device_put(np.full(self._GB, -1, np.int32),
                               self._sharding)
        v_sl = {f: jax.device_put(np.zeros(self._GB, dt), self._sharding)
                for f, dt in self._val_dtypes.items()}
        out = self._step(s_dev, v_sl)
        jax.block_until_ready(out[1])
        return 1
