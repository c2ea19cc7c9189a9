"""Ffat_Windows_Mesh: the sharded FlatFAT forest as a FRAMEWORK operator.

Round-2 verdict: ``parallel/mesh.py`` was a standalone library — no
builder, operator, or PipeGraph path reached it. This module closes that
gap: a topology-level operator whose single host replica drives
``parallel.sharded_ffat_forest`` over a ``jax.sharding.Mesh``, so a real
pipeline (CPU source -> keyed staging -> sharded forest across chips ->
CPU sink) runs THROUGH the topology layer. Construct it with
``Ffat_Windows_TPU_Builder(...).with_mesh(...)``.

Design (vs the single-chip ``tpu/ffat_tpu.py``):
- the keyby SHUFFLE moves from inter-replica channels to ``lax.all_to_all``
  over the mesh's ICI (the reference's analogous plane is the GPU keyby
  emitter wired into the topology, ``wf/keyby_emitter_gpu.hpp:518-583``;
  here the topology edge stays single-destination — one host replica — and
  the per-key routing happens inside the jitted step);
- per-key control state (next_fire / max_leaf / fired) lives ON DEVICE in
  the shard that owns the key: firing decisions need no host metadata and
  no cross-chip traffic;
- window semantics are ORIGIN-ANCHORED: window ``w`` of a key covers panes
  ``[w*slide, w*slide + win)`` from the epoch, and empty eligible windows
  fire with ``valid=False`` — the reference's TB numbering
  (``wf/window_replica.hpp:253-283``), NOT the single-chip plane's
  first-tuple anchoring (PARITY.md §2.3 documents that divergence);
- keys may be ARBITRARY integers (any int64, sparse or negative): a host
  ``KeySlotMap`` assigns each distinct key a dense slot in
  ``[0, key_capacity)`` in first-seen order — the same dictionary the
  single-chip plane routes through — and the slot feeds the block-owner
  mapping (shard ``s`` owns slots ``[s*k_local, (s+1)*k_local)``); fired
  windows carry the ORIGINAL key. More distinct keys than
  ``key_capacity`` raise loudly (``with_key_capacity`` is the knob).
  Non-integer key types stay single-chip-only: their per-row Python
  hashing would serialize the mesh's host control loop;
- lateness is a per-key rule enforced on device. The DEFAULT
  (``late_policy="keep_open"``) drops a tuple (counted ignored) iff
  every window containing its pane has already fired for its key —
  ``pane < next_fire[key]`` — a deliberate LESS-LOSSY divergence from
  the reference, which drops any tuple inside the last fired window
  even when it still belongs to open windows
  (``wf/window_replica.hpp:257-258``: ``index < win + last_lwid*slide``,
  only once a window fired). ``late_policy="ref_fired"`` reproduces the
  reference bound exactly (``pane < next_fire + win - slide`` once
  ``next_fire > 0``). Either way the only host-side drop is panes
  below the first batch's slide-aligned rebase anchor, which the device
  pane domain cannot represent. Keys that go idle are fast-forwarded past
  the frontier inside the step (their skipped windows are provably
  empty), so an idle-resume key can never read aliased ring leaves; and
  tuples more than ``ring - win`` panes AHEAD of the frontier trigger
  host-driven ring GROWTH with leaf migration (the single-chip plane's
  ``_grow_ring`` analog: geometric doubling, one step recompile per
  growth, internal levels rebuilt by the next firing step) — growth past
  ``RING_CAP_PANES`` (2^20 panes per key) is refused with a loud error,
  since an outrun that large is a watermark bug; ``with_mesh(ring_panes=)``
  pre-sizes the ring for known-bursty sources.

One step per staged input batch (padded to the mesh's global batch with
key = -1 lanes, which the routing drops); partial tail batches therefore
add bounded latency, never unbounded buffering.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..basic import OpType, RoutingMode, WinType, WindFlowError
from ..tpu.batch import BatchTPU, host_columns
from ..tpu.ops_tpu import TPUOperatorBase, TPUReplicaBase
from ..tpu.schema import TupleSchema


class Ffat_Windows_Mesh(TPUOperatorBase):
    """Keyed sliding-window aggregation sharded over a device mesh."""

    op_type = OpType.WIN_TPU
    # mesh execution plane: parallelism is the mesh shape, not the
    # replica count (rescale/autoscale refuse via repartition_refusal);
    # snapshot/restore ships per-shard state blocks under one manifest
    # entry and can relayout onto a different mesh factorization
    is_mesh = True
    mesh_snapshot_capable = True

    def __init__(self, lift: Callable, combine: Callable, key_extractor,
                 win_len: int, slide_len: int,
                 win_type: WinType = WinType.TB, lateness: int = 0,
                 name: str = "ffat_windows_mesh",
                 key_capacity: int = 16,
                 n_devices: Optional[int] = None,
                 mesh_shape: Optional[tuple] = None,
                 local_batch: Optional[int] = None,
                 fire_rounds: int = 4,
                 ring_panes: int = 0,
                 late_policy: str = "keep_open",
                 schema: Optional[TupleSchema] = None) -> None:
        if key_extractor is None:
            raise WindFlowError(f"{name}: requires a key extractor")
        if win_type is not WinType.TB:
            raise WindFlowError(
                f"{name}: the mesh plane supports TB windows (CB arrival "
                "indexing needs per-key host counters; use the single-chip "
                "Ffat_Windows_TPU)")
        if win_len <= 0 or slide_len <= 0:
            raise WindFlowError(f"{name}: win/slide must be > 0")
        # ONE host replica drives the whole mesh; parallelism is the mesh
        super().__init__(name, 1, RoutingMode.KEYBY, key_extractor, 0,
                         schema)
        self.lift = lift
        self.combine = combine
        self.win_len = win_len
        self.slide_len = slide_len
        self.win_type = win_type
        self.lateness = lateness
        self.key_capacity = max(1, key_capacity)
        self.n_devices = n_devices
        self.mesh_shape = mesh_shape
        self.local_batch = local_batch
        if late_policy not in ("keep_open", "ref_fired"):
            raise WindFlowError(
                f"{name}: late_policy must be 'keep_open' or 'ref_fired' "
                f"(got {late_policy!r})")
        self.fire_rounds = max(1, fire_rounds)
        self.ring_panes = ring_panes
        self.late_policy = late_policy
        self.pane_len = math.gcd(win_len, slide_len)

    def build_replicas(self) -> None:
        self.replicas = [FfatMeshReplica(self, 0)]


class FfatMeshReplica(TPUReplicaBase):
    """Host control loop: staged batch -> sharded step -> fired windows."""

    def __init__(self, op: Ffat_Windows_Mesh, idx: int) -> None:
        super().__init__(op, idx)
        self.win_units = op.win_len // op.pane_len
        self.slide_units = op.slide_len // op.pane_len
        self._mesh = None  # lazy: the device mesh exists at run time only
        self._step = None
        self._state = None
        self._sharding = None
        self._GB = 0
        self._K_pad = 0
        self._F = 0
        self._val_fields: List[str] = []
        self._val_dtypes: Dict[str, Any] = {}
        self._out_fields: List[str] = []
        self._frontier = 0        # REBASED panes (see _pane_base)
        self._max_pane_seen = -1  # rebased
        # pane REBASE: epoch-µs timestamps make ts//pane_len overflow the
        # device's int32 pane domain immediately; the first batch anchors
        # a base (rounded DOWN to a slide multiple so window numbering
        # stays origin-anchored), device panes are pane-base, and emitted
        # wids add base//slide back (host int64)
        self._pane_base: Optional[int] = None
        # host upper bound on the per-key fired-window backlog (frontier
        # advanced minus fire_rounds per step): eviction lags firing, so
        # ring-aliasing safety must account for it (see _maybe_catch_up)
        self._backlog_bound = 0
        # restored snapshot awaiting relayout (applied in _ensure once
        # the mesh exists; snapshot_state passes it through untouched)
        self._pending_restore: Optional[dict] = None
        # arbitrary int keys -> dense slots [0, key_capacity) in
        # first-seen order; fired windows map slots back to originals
        from ..tpu.keymap import KeySlotMap
        self._key_by_slot = np.zeros(op.key_capacity, np.int64)
        self._keymap = KeySlotMap(on_new=self._on_new_key)

    def _on_new_key(self, key, slot: int) -> None:
        if slot >= self.op.key_capacity:
            from ..basic import KeyCapacityError
            raise KeyCapacityError(
                self.op.name,
                getattr(self, "_K_pad", 0) or self.op.key_capacity,
                slot - self.op.key_capacity + 1,
                hint="raise with_key_capacity")
        self._key_by_slot[slot] = key

    # -- lazy mesh/program construction ---------------------------------
    def _ensure(self, batch: Optional[BatchTPU]) -> None:
        """Build mesh + sharded step. ``batch=None`` builds from a
        pending restored snapshot's metadata (a watermark-only advance or
        EOS flush can need the restored forest before any batch arrives);
        a restored snapshot's state is relayouted in either case."""
        if self._step is not None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .core import make_key_mesh

        pend = getattr(self, "_pending_restore", None)
        if batch is None and pend is None:
            return
        op = self.op
        n_dev = op.n_devices or len(jax.devices())
        self._mesh = make_key_mesh(n_dev, shape=op.mesh_shape)
        ka = self._mesh.shape["key"]
        da = self._mesh.shape["data"]
        if batch is not None:
            local_batch = op.local_batch or max(
                1, math.ceil(batch.capacity / (ka * da)))
            self._val_fields = list(batch.fields.keys())
            self._val_dtypes = {f: batch.schema.fields[f]
                                for f in self._val_fields}
        else:
            local_batch = op.local_batch or pend["local_batch"]
            self._val_dtypes = {f: np.dtype(dt)
                                for f, dt in pend["val_dtypes"].items()}
            self._val_fields = list(self._val_dtypes.keys())
        from .core import default_ring_panes
        self._F = op.ring_panes or default_ring_panes(
            self.win_units, self.slide_units, op.fire_rounds)
        if pend is not None:
            # ring geometry is state: the restored forest's leaf layout
            # is pane % F, so the rebuilt step must use the SAME F
            self._F = max(self._F, int(pend["F"]))
            if self._F != int(pend["F"]):
                # a larger configured ring: migrate like ring growth does
                pass  # relayout below re-maps leaves pane-wise
        self._local_batch = local_batch
        init_fn, step, (K_pad, k_local, GB) = self._build_forest(self._F)
        self._step = step
        self._GB, self._K_pad = GB, K_pad
        sample = {f: np.zeros(1, dt) for f, dt in self._val_dtypes.items()}
        self._out_fields = list(jax.eval_shape(
            lambda v: op.lift(v), sample).keys())
        self._state = init_fn(sample)
        self._sharding = NamedSharding(self._mesh, P(("key", "data")))
        self.stats.mesh_devices = ka * da
        from .core import excluded_device_ids
        if excluded_device_ids():
            want = min(n_dev, len(jax.devices()))
            self.stats.mesh_degraded = max(0, want - ka * da)
        else:
            self.stats.mesh_degraded = 0
        if pend is not None:
            self._apply_pending_restore()

    def _build_forest(self, ring_panes: int):
        """ONE construction path for the sharded step (initial build and
        ring growth must never drift apart in config or error handling)."""
        from .core import sharded_ffat_forest

        op = self.op
        try:
            return sharded_ffat_forest(
                self._mesh, op.lift, op.combine, n_keys=op.key_capacity,
                win_panes=self.win_units, slide_panes=self.slide_units,
                local_batch=self._local_batch,
                fire_rounds=op.fire_rounds, ring_panes=ring_panes,
                late_policy=op.late_policy)
        except ValueError as e:  # config validation -> framework error
            raise WindFlowError(f"{op.name}: {e}") from None

    # -- sharded fault tolerance ----------------------------------------
    def snapshot_state(self) -> dict:
        """Aligned snapshot: host control state + the forest as PER-SHARD
        row blocks gathered under one manifest entry (one blob per mesh
        operator; each block is one key-shard's rows, so restore can
        relayout onto a different mesh factorization or device count by
        slot-row gather)."""
        import time as _time

        st = super().snapshot_state()  # drains the dispatch queue
        pend = getattr(self, "_pending_restore", None)
        if self._step is None:
            if pend is not None:
                # restored but never touched since: the restored blob is
                # still the exact state — pass it through unchanged
                st["mesh_ffat"] = pend
            return st
        import jax

        t0 = _time.perf_counter()
        ns = self._mesh.shape["key"]
        trees, tvalid, nf, ml, fired = self._state
        blocks = lambda a: np.split(np.ascontiguousarray(
            np.asarray(jax.device_get(a))), ns, axis=0)
        st["mesh_ffat"] = {
            "slot_of_key": dict(self._keymap.slot_of_key),
            "key_by_slot": self._key_by_slot.copy(),
            "key_capacity": self.op.key_capacity,
            "val_dtypes": {f: np.dtype(dt).str
                           for f, dt in self._val_dtypes.items()},
            "local_batch": self._local_batch,
            "F": self._F, "K_pad": self._K_pad, "key_shards": ns,
            "pane_base": self._pane_base,
            "frontier": self._frontier,
            "max_pane_seen": self._max_pane_seen,
            "backlog_bound": self._backlog_bound,
            "trees": {f: blocks(a) for f, a in trees.items()},
            "tvalid": blocks(tvalid),
            "next_fire": blocks(nf),
            "max_leaf": blocks(ml),
            "fired": blocks(fired),
        }
        rec = self.stats.recorder
        if rec is not None:
            rec.event("mesh:snapshot",
                      (_time.perf_counter() - t0) * 1e6,
                      {"keys": len(self._keymap.slot_of_key),
                       "shards": ns})
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        d = state.get("mesh_ffat")
        if d is not None:
            # applied lazily once the mesh exists (_ensure): restore runs
            # before workers start, and the target mesh factorization may
            # differ from the checkpointed one
            self._pending_restore = d

    def _apply_pending_restore(self) -> None:
        """Relayout the restored forest onto THIS mesh: per-shard blocks
        concatenate to the global slot axis, rows re-pad to the new
        K_pad, and live leaves re-map ``pane % F_old -> pane % F_new``
        (identity for an unchanged ring; the ring-growth migration
        otherwise). Runs after ``_build_forest`` so initial build and
        restore share one construction path."""
        import time as _time

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        d, self._pending_restore = self._pending_restore, None
        op = self.op
        t0 = _time.perf_counter()
        if len(d["slot_of_key"]) > op.key_capacity:
            raise WindFlowError(
                f"{op.name}: restore holds {len(d['slot_of_key'])} "
                f"distinct keys but this graph declares key_capacity="
                f"{op.key_capacity}; raise with_key_capacity to at least "
                "the checkpointed count")
        if set(d["trees"]) != set(self._state[0]):
            raise WindFlowError(
                f"{op.name}: restored forest fields "
                f"{sorted(d['trees'])} do not match this graph's lift "
                f"output {sorted(self._state[0])} — the checkpointed "
                "operator ran a different aggregation")
        self._keymap.slot_of_key.clear()
        self._keymap.slot_of_key.update(d["slot_of_key"])
        self._keymap.reset_index()
        kbs = np.asarray(d["key_by_slot"])
        self._key_by_slot[:] = 0
        n_copy = min(len(kbs), op.key_capacity)
        self._key_by_slot[:n_copy] = kbs[:n_copy]
        self._pane_base = d["pane_base"]
        self._frontier = int(d["frontier"])
        self._max_pane_seen = int(d["max_pane_seen"])
        self._backlog_bound = int(d["backlog_bound"])

        full = lambda bl: np.concatenate([np.asarray(b) for b in bl],
                                         axis=0)
        K_new, F_new, F_old = self._K_pad, self._F, int(d["F"])
        nf_old = full(d["next_fire"]).astype(np.int64)
        ml_old = full(d["max_leaf"]).astype(np.int64)
        fired_old = full(d["fired"])
        tvalid_old = full(d["tvalid"])
        trees_old = {f: full(bl) for f, bl in d["trees"].items()}
        K_old = tvalid_old.shape[0]
        # live slots all sit below key_capacity <= min(K_old, K_new):
        # rows beyond are untouched padding on either side
        rows_k = min(K_old, K_new)

        def fit_rows(a, fill):
            out = np.full((K_new,) + a.shape[1:], fill, dtype=a.dtype)
            out[:rows_k] = a[:rows_k]
            return out

        nf = fit_rows(nf_old, 0)
        ml = fit_rows(ml_old, -1)
        fired = fit_rows(fired_old, 0)
        spans = np.maximum(0, ml - nf + 1)
        spans[rows_k:] = 0
        rows = np.repeat(np.arange(K_new), spans)
        before = np.cumsum(spans) - spans
        seg = np.arange(int(spans.sum()), dtype=np.int64) \
            - np.repeat(before, spans)
        panes = np.repeat(nf, spans) + seg
        src = (F_old + (panes % F_old)).astype(np.int64)
        dst = (F_new + (panes % F_new)).astype(np.int64)
        new_trees = {f: np.zeros((K_new, 2 * F_new), t.dtype)
                     for f, t in trees_old.items()}
        new_tvalid = np.zeros((K_new, 2 * F_new), bool)
        for f, t in trees_old.items():
            new_trees[f][rows, dst] = t[rows, src]
        new_tvalid[rows, dst] = tvalid_old[rows, src]
        # internal levels stay invalid — the first firing step's
        # in-program rebuild recomputes them from leaves (the same
        # contract ring growth relies on)
        sh_keys = NamedSharding(self._mesh, P("key", None))
        sh_key1 = NamedSharding(self._mesh, P("key"))
        self._state = (
            {f: jax.device_put(a, sh_keys)
             for f, a in new_trees.items()},
            jax.device_put(new_tvalid, sh_keys),
            jax.device_put(nf.astype(np.int32), sh_key1),
            jax.device_put(ml.astype(np.int32), sh_key1),
            jax.device_put(fired.astype(np.int32), sh_key1))
        rec = self.stats.recorder
        if rec is not None:
            rec.event("mesh:restore",
                      (_time.perf_counter() - t0) * 1e6,
                      {"keys": len(self._keymap.slot_of_key),
                       "F": F_new, "K_pad": K_new})

    # -- streaming ------------------------------------------------------
    def _rebased_frontier(self, wm: Optional[int] = None) -> int:
        """Frontier from ``wm`` (default: the replica watermark). Batch
        commits MUST pass their batch's own arrival-time watermark: the
        dispatch pipeline defers commits, so by commit time ``cur_wm``
        may already reflect LATER batches — folding this batch under
        that future frontier would fast-forward keys past panes still
        in this very batch and drop them as late."""
        if wm is None:
            wm = self.cur_wm
        f_abs = max(0, wm - self.op.lateness) // self.op.pane_len
        return max(0, f_abs - (self._pane_base or 0))

    def _advance_frontier(self, new_frontier: int) -> bool:
        """Move the fire frontier and accrue the fired-window backlog it
        creates (up to ceil(delta/slide) new fireable windows per key) —
        accrual must happen HERE, before any ring-headroom check reads
        the bound."""
        if new_frontier <= self._frontier:
            return False
        delta = new_frontier - self._frontier
        self._frontier = new_frontier
        self._backlog_bound += -(-delta // self.slide_units)
        return True

    def process_device_batch(self, batch: BatchTPU) -> None:
        self._ensure(batch)
        n = batch.size
        keys = np.asarray(self.batch_keys(batch))[:n]
        if keys.dtype.kind not in "iu":
            raise WindFlowError(
                f"{self.op.name}: mesh FFAT requires integer keys "
                f"(sparse/negative int64 ok); got dtype {keys.dtype}")
        # arbitrary int domain -> dense slots (the capacity guard lives
        # in _on_new_key: it fires against the DECLARED capacity, not
        # the mesh-padded K_pad — acceptance must not depend on shape;
        # slots stay in the keymap's narrow dtype, _run_steps casts once)
        keys = self._keymap.slots_of(keys, keys, n)
        from .core import mesh_occupancy
        occ, skew = mesh_occupancy(
            len(self._keymap), self._K_pad // self._mesh.shape["key"],
            self._mesh.shape["key"])
        self.stats.mesh_shard_occupancy = occ
        self.stats.mesh_shard_skew = skew
        panes = (batch.ts_host[:n] // self.op.pane_len).astype(np.int64)
        if self._pane_base is None:
            base = int(panes.min()) if n else 0
            self._pane_base = (base // self.slide_units) * self.slide_units
        panes = panes - self._pane_base
        # frontier: the single-chip convention ((wm - lateness) // pane),
        # from THIS batch's arrival-time watermark — commits are
        # deferred, so the replica watermark may already be ahead
        self._advance_frontier(self._rebased_frontier(batch.wm))
        # the per-key lateness rule (late_policy: "keep_open" drops iff
        # every containing window fired; "ref_fired" also drops inside
        # the last fired window) lives ON DEVICE as a mask on next_fire;
        # the host only drops panes below the rebase anchor (the first
        # batch's slide-aligned min pane — the device pane domain cannot
        # represent them; counted ignored, a documented anchor divergence)
        live = panes >= 0
        dropped = n - int(live.sum())
        # unified late accounting, arrival side: anchor drops are counted
        # records+dropped here; rows behind this batch's watermark are
        # counted records-only — the per-key drop decision is deferred to
        # the device program, whose count rides the existing fire
        # readback in _run_steps (drop-only there, no double count and
        # NO new host sync)
        st = self.stats
        ts_live = batch.ts_host[:n][live] if dropped else batch.ts_host[:n]
        panes_live = panes[live] if dropped else panes
        # behind this batch's watermark, OR behind the replica's fire
        # frontier (a slower input channel's wm can trail it; the device
        # drop rule compares against per-key next_fire ≤ frontier, so
        # this mask is a strict superset of every deferred device drop)
        late_mask = (ts_live < batch.wm) | (panes_live < self._frontier)
        n_late_seen = int(late_mask.sum())
        if n_late_seen or dropped:
            st.note_late(n_late_seen + dropped, dropped,
                         batch.wm - ts_live[late_mask]
                         if st.hist_lateness is not None and n_late_seen
                         else None)
        if dropped:
            self.stats.inputs_ignored += dropped
            keys, panes = keys[live], panes[live]
        if panes.size:
            self._check_ring_headroom(int(panes.max()))
            if int(panes.max()) >= np.iinfo(np.int32).max:
                raise WindFlowError(
                    f"{self.op.name}: rebased pane {int(panes.max())} "
                    "overflows the device's int32 pane domain; use a "
                    "larger pane (win/slide gcd)")
            self._max_pane_seen = max(self._max_pane_seen, int(panes.max()))
        vals = {f: c[:n][live] for f, c in host_columns(
            batch.fields, self._val_fields).items()}
        self._run_steps(keys.astype(np.int32), panes.astype(np.int32), vals)

    def on_punctuation(self, wm: int) -> None:
        # a watermark-only advance can make windows fireable with no new
        # data: run a data-less step when the frontier moved (only once
        # data anchored the pane rebase — before that the absolute
        # epoch-µs frontier would poison the rebased domain)
        if self._step is None and self._pending_restore is not None:
            self._ensure(None)  # restored forest, no batch yet
        if self._step is not None and self._pane_base is not None:
            if self._advance_frontier(self._rebased_frontier()):
                self._run_steps(np.zeros(0, np.int32),
                                np.zeros(0, np.int32), self._empty_vals())
        super().on_punctuation(wm)

    # -- ring-aliasing safety -------------------------------------------
    def _check_ring_headroom(self, max_pane: int) -> None:
        """A new pane ``p`` of key k aliases k's circular leaf ring iff
        ``p >= next_fire[k] + F`` (leaves below next_fire are evicted;
        key rows are independent). next_fire trails the frontier by the
        per-key fired-window BACKLOG (each step fires at most fire_rounds
        windows), tracked conservatively on the host; when the slack is
        gone, data-less catch-up steps fire + evict until the device
        control state shows the backlog cleared."""
        while True:
            floor = (self._frontier - self.win_units + 1
                     - self._backlog_bound * self.slide_units)
            if max_pane < floor + self._F and max_pane < self._frontier \
                    + self._F - self.win_units:
                return
            if self._backlog_bound > 0:
                self._catch_up()
                continue
            if self._grow_ring_to(max_pane):
                continue  # re-check against the grown ring
            raise WindFlowError(
                f"{self.op.name}: pane {max_pane} is more than ring-win "
                f"({self._F}-{self.win_units}) panes ahead of the "
                f"watermark frontier {self._frontier}, and growing the "
                f"ring past {self.RING_CAP_PANES} panes is refused "
                "(a source outrunning its watermarks by that much is a "
                "watermark bug); advance watermarks faster or raise "
                "with_mesh(ring_panes=...)")

    RING_CAP_PANES = 1 << 20  # growth refusal threshold (per-key panes)

    def _grow_ring_to(self, max_pane: int) -> bool:
        """Ring growth with state migration — the mesh analog of the
        single-chip plane's ``_grow_ring`` (a source briefly outrunning
        its watermarks must not be fatal). Host-driven: fetch the forest,
        re-map LIVE LEAVES ``pane % F -> pane % F'`` per key, rebuild the
        sharded step for the larger ring, and re-shard the migrated
        state. Internal levels are left invalid — the first firing
        step's in-program rebuild recomputes them from leaves (the same
        contract the conditional rebuild relies on). Returns False when
        the needed ring exceeds RING_CAP_PANES (caller raises)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        op = self.op
        new_F = self._F
        while (max_pane - self._frontier + self.win_units >= new_F
               or new_F < self.win_units
               + op.fire_rounds * self.slide_units):
            new_F *= 2
            if new_F > self.RING_CAP_PANES:
                return False
        trees = {f: np.asarray(v) for f, v in self._state[0].items()}
        tvalid = np.asarray(self._state[1])
        nf = np.asarray(self._state[2]).astype(np.int64)
        ml = np.asarray(self._state[3]).astype(np.int64)
        fired = np.asarray(self._state[4])
        K_pad = tvalid.shape[0]
        old_F = self._F
        spans = np.maximum(0, ml - nf + 1)
        rows = np.repeat(np.arange(K_pad), spans)
        before = np.cumsum(spans) - spans
        seg = np.arange(int(spans.sum()), dtype=np.int64) \
            - np.repeat(before, spans)
        panes = np.repeat(nf, spans) + seg
        src = old_F + (panes % old_F)
        dst = new_F + (panes % new_F)
        new_trees = {f: np.zeros((K_pad, 2 * new_F), t.dtype)
                     for f, t in trees.items()}
        new_tvalid = np.zeros((K_pad, 2 * new_F), bool)
        for f, t in trees.items():
            new_trees[f][rows, dst] = t[rows, src]
        new_tvalid[rows, dst] = tvalid[rows, src]
        _init, step, (_kp, _kl, _gb) = self._build_forest(new_F)
        sh_keys = NamedSharding(self._mesh, P("key", None))
        sh_key1 = NamedSharding(self._mesh, P("key"))
        self._step = step
        self._state = (
            {f: jax.device_put(a, sh_keys) for f, a in new_trees.items()},
            jax.device_put(new_tvalid, sh_keys),
            jax.device_put(nf.astype(np.int32), sh_key1),
            jax.device_put(ml.astype(np.int32), sh_key1),
            jax.device_put(fired, sh_key1))
        self._F = new_F
        return True

    def _catch_up(self) -> None:
        """Fire the backlog with data-less steps. ONE control-state fetch
        sizes the whole drain (a D2H per iteration would serialize the
        steps): each key can fire ``min((frontier-win-nf)//slide,
        (ml-nf)//slide) + 1`` windows — the device's own eligibility rule
        — and every step fires up to fire_rounds of them per key."""
        nf = np.asarray(self._state[2]).astype(np.int64)
        ml = np.asarray(self._state[3]).astype(np.int64)
        per_key = np.minimum(
            (self._frontier - self.win_units - nf) // self.slide_units,
            (ml - nf) // self.slide_units) + 1
        n_win = int(np.maximum(per_key, 0).max(initial=0))
        for _ in range(-(-n_win // self.op.fire_rounds)):
            self._run_steps(np.zeros(0, np.int32), np.zeros(0, np.int32),
                            self._empty_vals())
        self._backlog_bound = 0

    def _empty_vals(self) -> Dict[str, np.ndarray]:
        return {f: np.zeros(0, dt) for f, dt in self._val_dtypes.items()}

    def _run_steps(self, keys, panes, vals) -> None:
        """Feed ``GB``-sized slices (padded with key=-1 lanes) through the
        sharded step; emit fired windows after each."""
        import time as _time

        import jax

        GB = self._GB
        total = keys.shape[0]
        off = 0
        # per-step shuffle traffic: every tuple column rides the
        # all_to_all once (keys + panes int32 + the value columns)
        step_bytes = GB * (8 + sum(np.dtype(dt).itemsize
                                   for dt in self._val_dtypes.values()))
        while True:
            t0 = _time.perf_counter()
            lo, hi = off, min(off + GB, total)
            m = hi - lo
            k_sl = np.full(GB, -1, np.int32)
            p_sl = np.zeros(GB, np.int32)
            k_sl[:m] = keys[lo:hi]
            p_sl[:m] = panes[lo:hi]
            v_sl = {}
            for f, col in vals.items():
                buf = np.zeros((GB,) + col.shape[1:], col.dtype)
                buf[:m] = col[lo:hi]
                v_sl[f] = jax.device_put(buf, self._sharding)
            out = self._step(
                *self._state, jax.device_put(k_sl, self._sharding),
                v_sl, jax.device_put(p_sl, self._sharding),
                np.int32(min(self._frontier, np.iinfo(np.int32).max)))
            self._state = out[:5]
            self.stats.device_programs_run += 1
            self.stats.note_mesh_step(
                (_time.perf_counter() - t0) * 1e6, step_bytes)
            self._backlog_bound = max(0,
                                      self._backlog_bound
                                      - self.op.fire_rounds)
            n_late = int(out[9])
            if n_late:
                self.stats.inputs_ignored += n_late
                # in-program late count riding the existing readback:
                # drop-only — these rows were already counted into
                # late_records at arrival (every device-dropped pane sits
                # behind the watermark frontier of its batch)
                self.stats.note_late(0, n_late)
            self._emit_fired(out[5], out[6], out[7])
            off = hi
            if off >= total:
                break

    def _emit_fired(self, res, res_valid, res_wid) -> None:
        """Harvest the step's fired-window block (K_pad x fire_rounds —
        small) and emit ONE columnar batch per step through the exit
        edge, like the single-chip plane (``tpu/ffat_tpu.py`` emits one
        ``BatchTPU`` per fire sweep): numpy gathers only, no per-window
        Python loop. Rows carry ``valid`` — the aggregate fields of a
        ``valid=False`` (empty-window) row are meaningless, matching the
        single-chip plane's columnar contract."""
        rw = np.asarray(res_wid)
        fired = rw >= 0
        n_out = int(fired.sum())
        if not n_out:
            return
        rv = np.asarray(res_valid)
        key_field = self.op.key_field or "key"
        wid_base = (self._pane_base or 0) // self.slide_units
        krows, rounds = np.nonzero(fired)
        wids = rw[krows, rounds].astype(np.int64) + wid_base
        end_ts = (wids * self.slide_units + self.win_units) \
            * self.op.pane_len
        fields: Dict[str, np.ndarray] = {
            key_field: self._key_by_slot[krows],  # slots -> original keys
            "wid": wids,
            "valid": rv[krows, rounds],
        }
        for f in self._out_fields:
            fields[f] = np.asarray(res[f])[krows, rounds]
        schema = TupleSchema({name: np.dtype(col.dtype)
                              for name, col in fields.items()})
        out = BatchTPU(fields, end_ts, n_out, schema, self.cur_wm,
                       host_keys=fields[key_field])
        self._emit_batch(out)

    def flush_on_termination(self) -> None:
        """EOS: fire every remaining window that holds data (partial
        windows fire with their partial content, like the single-chip
        plane's EOS flush)."""
        if self._step is None and self._pending_restore is not None:
            self._ensure(None)  # restored forest, no batch since
        if self._step is None or self._max_pane_seen < 0:
            return
        self._advance_frontier(self._max_pane_seen + self.win_units + 1)
        # ONE control-state fetch sizes the drain (no per-iteration D2H):
        # with the frontier past every pane, key k has (ml-nf)//slide + 1
        # windows left; each data-less step fires up to fire_rounds of
        # them per key
        nf = np.asarray(self._state[2]).astype(np.int64)  # next_fire
        ml = np.asarray(self._state[3]).astype(np.int64)  # max_leaf
        per_key = (ml - nf) // self.slide_units + 1
        n_win = int(np.maximum(per_key, 0).max(initial=0))
        for _ in range(-(-n_win // self.op.fire_rounds)):
            self._run_steps(np.zeros(0, np.int32), np.zeros(0, np.int32),
                            self._empty_vals())
