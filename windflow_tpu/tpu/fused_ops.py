"""FusedTPUReplica: one XLA program per batch across a chained device
stage.

The reference fuses chain-compatible operators into one thread
(``wf/multipipe.hpp:537-590``); the TPU-native analog fuses their
*programs*. A ``Map_TPU -> Filter_TPU -> Map_TPU`` chain built via
``MultiPipe.chain`` runs as ONE replica whose per-batch work is a single
``jax.jit`` program composed from the sub-operators' kernels
(``ops_tpu.py`` kernel plane):

- a filter's keep mask flows to the next sub-op as a device-side
  ``valid`` mask — no mid-chain compaction, no mid-chain ``int(count)``
  readback; the one compaction + count readback happens at the chain
  exit (or never, for map-only chains);
- stateful sub-ops contribute their grid tables as additional carried
  state: the fused program threads every table through and the
  donation discipline matches the standalone grid scan (tables are
  donated, every commit reassigns them);
- a global ``Reduce_TPU`` terminator folds the masked survivors to one
  tuple inside the same program (``masked_tree_reduce``); a KEYED
  ``Reduce_TPU`` terminator runs its key-sorted segmented scan in the
  same program over the chain's valid mask (the KEYBY shuffle it would
  normally own degenerates to this in-program sort/segment when no
  cross-device re-shard exists — ``topology/stage.py`` legality);
- the whole chain submits ONE host-prep/device-commit pair to the
  replica's ``DeviceDispatchQueue`` — three chained operators cost one
  program launch and one commit per batch instead of three of each
  plus two channel hops.

Cross-operator XLA fusion then eliminates the intermediate HBM
materialization between sub-ops (Snider & Liang, arXiv:2301.13062;
Zheng et al., arXiv:1811.05213): the elementwise map/filter chain
compiles to one fused loop over the batch.

MEGABATCH: when ``WF_MEGABATCH=K`` > 1, the dispatch queue
(``runtime/dispatch.py``) coalesces up to K queued same-signature
commits and runs them through ``_run_megabatch`` — one jitted
``lax.scan`` over the chain program with the grid tables as carry, so K
batches cost ONE host dispatch. Ordering points (EOS / punctuation /
checkpoint / growth drains) always drain as singles, leaving alignment,
exactly-once, and rescale semantics untouched.

Compiled programs are cached per chain signature: the cache key covers
every stateful sub-op's grid shape ``(M, KB)`` (stateless sub-ops pin a
``None`` slot), and the cache itself lives on the chain's HEAD operator
so all replicas of the fused stage share one compilation.

Checkpointing: ``snapshot_state`` records the fused signature plus one
positional entry per sub-op, so PR 3 restores land each grid table back
into the right sub-op; a blob from a differently-fused (or unfused)
topology fails loudly instead of silently dropping state.

``FusedFfatReplica`` (bottom of this module) is the window-terminated
variant: the chain's stateless map/filter prefix composes INTO the
``Ffat_Windows_TPU`` step program via the ``_lift_fn``/``_prefix_mask``
hooks on ``FfatTPUReplica`` — ``source -> map -> Ffat_Windows`` runs as
ONE program per batch.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional

import numpy as np

from ..basic import WindFlowError
from ..monitoring.flightrec import instrumented_jit
from ..monitoring.tracing import program_name
from ..runtime.dispatch import megabatch_k, split_commit
from .batch import BatchTPU, async_host_copy
from .ffat_tpu import Ffat_Windows_TPU, FfatTPUReplica
from .ops_tpu import (Filter_TPU, Map_TPU, Reduce_TPU, TPUReplicaBase,
                      _compact_order, _grid_scan_core, _KeyedStateScan,
                      cached_compile, masked_tree_reduce, own_key_spec,
                      prewarm_zero_fields, reduce_order_and_slots)


# XLA module names: a fused chain's program is ``chain_<ops>``, the
# prefix-mask program of a window-terminated chain ``mask_<prefix ops>``
_PROG_CHAIN, _PROG_MASK = "chain", "mask"


class _SubSpec:
    """One sub-operator's contribution to the fused program: a stateless
    kernel, a stateful grid-scan engine, or a terminal reduce."""

    __slots__ = ("op", "kind", "kernel", "engine", "func")

    def __init__(self, op, kind: str, kernel: Optional[Callable],
                 engine: Optional[_KeyedStateScan],
                 func: Optional[Callable] = None) -> None:
        self.op = op
        self.kind = kind  # map | filter | smap | sfilter | reduce | kreduce
        self.kernel = kernel  # stateless composable kernel
        self.engine = engine  # _KeyedStateScan for stateful sub-ops
        self.func = func  # user functor for the grid-scan core


def _build_specs(replica: "FusedTPUReplica", ops) -> List[_SubSpec]:
    specs: List[_SubSpec] = []
    for op in ops:
        if isinstance(op, Reduce_TPU):
            specs.append(_SubSpec(
                op, "reduce" if op.key_extractor is None else "kreduce",
                None, None))
        elif isinstance(op, Map_TPU):
            if op.state_init is not None:
                specs.append(_SubSpec(
                    op, "smap", None,
                    _KeyedStateScan(replica, op.func, op.state_init,
                                    False, op=op), func=op.func))
            else:
                specs.append(_SubSpec(op, "map", op.device_kernel(), None))
        elif isinstance(op, Filter_TPU):
            if op.state_init is not None:
                specs.append(_SubSpec(
                    op, "sfilter", None,
                    _KeyedStateScan(replica, op.pred, op.state_init,
                                    True, op=op), func=op.pred))
            else:
                specs.append(_SubSpec(op, "filter", op.device_kernel(),
                                      None))
        else:
            raise WindFlowError(
                f"{op.name}: operator kind {type(op).__name__} has no "
                "composable device kernel (fusion legality should have "
                "refused this chain)")
    return specs


class FusedTPUReplica(TPUReplicaBase):
    """One replica running a whole chained device stage as one program.

    Protocol-compatible with any ``TPUReplicaBase``: same dispatch-queue
    ordering contract, punctuation/EOS handling, latency-stamp
    propagation (``trace_min/max`` ride the batch through the single
    program) and barrier-alignment drains — the fused node is simply a
    bigger per-batch program."""

    def __init__(self, ops, idx: int) -> None:
        ops = list(ops)
        super().__init__(ops[0], idx)
        self.ops = ops
        self.fused_name = "∘".join(o.name for o in ops)
        # stats/trace attribution: the fused stage is ONE observable
        # operator named map∘filter∘map; prep/commit spans + histograms
        # land on this record
        # (the stage labels follow the record's name: wf:prep:map∘filter∘map)
        self.stats.op_name = self.fused_name
        self.stats.fused_ops = len(ops)
        self.specs = _build_specs(self, ops)
        self._engines = [s.engine for s in self.specs
                         if s.engine is not None]
        self._has_filter = any(s.kind in ("filter", "sfilter")
                               for s in self.specs)
        last_kind = self.specs[-1].kind
        self._reduce_combine = (ops[-1].combine
                                if last_kind == "reduce" else None)
        self._kreduce_combine = (ops[-1].combine
                                 if last_kind == "kreduce" else None)
        if any(s.kind in ("reduce", "kreduce") for s in self.specs[:-1]):
            raise WindFlowError(
                f"{self.fused_name}: Reduce_TPU must terminate "
                "the fused chain")
        # the emit reads fresh outputs of the chain's program (the three
        # branches of ``_commit_emit`` that take a readback): such a
        # chain's commit is a launch half that returns its finish
        self._emit_reads = (self._has_filter
                            or self._reduce_combine is not None
                            or self._kreduce_combine is not None)
        # compiled fused programs shared across this stage's replicas
        # (the graph build is single-threaded; worker threads only read)
        head = ops[0]
        if not hasattr(head, "_fused_prog_cache"):
            head._fused_prog_cache = {}
            head._fused_prog_lock = threading.Lock()
        self._prog_cache = head._fused_prog_cache
        self._prog_lock = head._fused_prog_lock

    # -- identity ----------------------------------------------------------
    @property
    def fused_signature(self) -> List[str]:
        return [op.name for op in self.ops]

    def _program_name(self) -> str:
        """XLA module name of the chain's program: ``chain_views_join``
        (``jit_chain_views_join`` in a device profile)."""
        return program_name(_PROG_CHAIN, *self.fused_signature)

    # -- fused program -----------------------------------------------------
    def _chain_body(self, statics) -> Callable:
        """The UN-jitted chain body ``run(fields, size, hargs, tables)``
        — shared by the per-batch program (``_make``) and the megabatch
        scan program (``_make_scan``), so both trace identical math.
        ``statics`` pins each stateful sub-op's grid shape ``(M, KB)``
        (None for stateless slots) — together with the traced shapes it
        is the full chain signature."""
        import jax
        import jax.numpy as jnp

        specs = self.specs
        has_filter = self._has_filter
        reduce_combine = self._reduce_combine
        kreduce_combine = self._kreduce_combine
        fused_name = self.fused_name

        def run(fields, size, hargs, tables):
            n = next(iter(fields.values())).shape[0]
            valid = jnp.arange(n) < size
            new_tables = []
            ti = 0
            for i, spec in enumerate(specs):
                # each sub-operator's body under its own name in a profile
                if spec.kind in ("map", "filter"):
                    with jax.named_scope(program_name(spec.kind,
                                                      spec.op.name)):
                        fields, valid, _ = spec.kernel(fields, valid, None)
                    if spec.kind == "map" and not isinstance(fields, dict):
                        raise WindFlowError(
                            f"{fused_name}: Map_TPU function must return "
                            "a dict of columns")
                elif spec.kind in ("smap", "sfilter"):
                    M, KB = statics[i]
                    core = _grid_scan_core(spec.func,
                                           spec.kind == "sfilter", M, KB)
                    grid_idx, touched, tmask = hargs[i]
                    tbl, dirty = tables[ti]
                    with jax.named_scope(program_name(spec.kind,
                                                      spec.op.name)):
                        out, t2, d2 = core(fields, valid, grid_idx,
                                           touched, tmask, tbl, dirty)
                    new_tables.append((t2, d2))
                    ti += 1
                    if spec.kind == "sfilter":
                        valid = out
                    else:
                        fields = out
                # reduce/kreduce handled at the exit below (always last)
            if reduce_combine is not None:
                with jax.named_scope(program_name("reduce",
                                                  specs[-1].op.name)):
                    red = masked_tree_reduce(reduce_combine, fields, valid)
                return (red, _compact_order(valid), jnp.sum(valid),
                        tuple(new_tables))
            if kreduce_combine is not None:
                # keyed terminator: host prep sorted the rows by key
                # (reduce_order_and_slots — mask-independent, so it runs
                # over ALL rows); the scan folds each key's VALID rows
                # with the user combine. Validity rides the scan as an
                # Option: an invalid side passes the other through, an
                # invalid tail means no surviving row hit that key and
                # the slot is dropped — exactly the keys the unfused
                # filter stage would have compacted away upstream.
                order, ssorted = hargs[-1]
                f = {c: v[order] for c, v in fields.items()}
                v = valid[order]

                def seg_op(a, b):
                    fa, va, sa = a
                    fb, vb, sb = b
                    same = sa == sb
                    both = va & vb & same
                    merged = kreduce_combine(fa, fb)
                    # fields the combine does not return pass through
                    out = {c: jnp.where(both, merged.get(c, fb[c]),
                                        jnp.where(vb, fb[c],
                                                  jnp.where(same, fa[c],
                                                            fb[c])))
                           for c in fb}
                    return out, vb | (va & same), sb

                scanned, vscan, _ = jax.lax.associative_scan(
                    seg_op, (f, v, ssorted))
                is_last = jnp.concatenate(
                    [ssorted[1:] != ssorted[:-1], jnp.ones((1,), bool)])
                tkeep = is_last & vscan
                torder = _compact_order(tkeep)  # surviving tails first
                tails = {c: a[torder] for c, a in scanned.items()}
                return (tails, ssorted[torder], jnp.sum(tkeep),
                        _compact_order(valid), jnp.sum(valid),
                        tuple(new_tables))
            if has_filter:
                order = _compact_order(valid)  # keepers first, stable
                out = {k: v[order] for k, v in fields.items()}
                return out, order, jnp.sum(valid), tuple(new_tables)
            return fields, tuple(new_tables)

        return run

    def _make(self, statics) -> Callable:
        """Compose the chain into one jitted per-batch program."""
        # grid tables are DONATED exactly like the standalone scan:
        # every commit reassigns the engines' tables from the output.
        # instrumented_jit attributes (re)traces to this replica's
        # Compile_* stats with the chain signature — a fused chain whose
        # batch shapes churn shows up as a retrace storm in the trace
        return instrumented_jit(self._chain_body(statics), self.stats,
                                label=self.fused_name,
                                program=self._program_name(),
                                donate_argnums=(3,))

    def _make_scan(self, statics, k: int) -> Callable:
        """Megabatch program: stack K same-signature batches' columns
        in-trace, ``lax.scan`` the chain body over them with the grid
        tables as carry, and unstack the per-batch outputs in-trace —
        ONE compiled program and ONE host dispatch for K batches. The
        scan body IS ``_chain_body``, so a megabatch commit is
        bit-identical to K sequential single commits (the carry threads
        tables batch-to-batch exactly like sequential donation)."""
        import jax
        import jax.numpy as jnp

        run = self._chain_body(statics)
        tmap = jax.tree_util.tree_map

        def scan_run(fields_t, sizes, hargs_tt, tables):
            # None leaves (stateless sub-op hargs) are empty pytree
            # subtrees: tree_map skips them and the stacked structure
            # mirrors the per-batch one
            xf = tmap(lambda *xs: jnp.stack(xs), *fields_t)
            xh = tmap(lambda *xs: jnp.stack(xs), *hargs_tt)

            def body(tb, x):
                f, sz, h = x
                res = run(f, sz, h, tb)
                return res[-1], res[:-1]

            tables2, outs = jax.lax.scan(body, tables, (xf, sizes, xh))
            per = tuple(tmap(lambda a: a[i], outs) for i in range(k))
            return per, tables2

        return instrumented_jit(scan_run, self.stats,
                                label=f"{self.fused_name}:scan{k}",
                                program=f"{self._program_name()}_scan{k}",
                                donate_argnums=(3,))

    # -- compile-stability pre-warm ----------------------------------------
    def prewarm(self, caps) -> Optional[int]:
        """Compile the whole-chain program — and, when ``WF_MEGABATCH``
        enables the scan loop, every power-of-two K-scan variant — once
        per bucket capacity (``PipeGraph.with_prewarm``). Stateless
        chains only: a stateful sub-op's grid shape ``(M, KB)`` and
        table capacity are runtime cardinality — their signatures cannot
        be enumerated at start. A keyed-reduce terminator IS
        enumerable: its order/slot arrays are runtime values, not
        signature."""
        import jax

        if self._engines:
            return None
        sch = self.op.schema
        if sch is None:
            return None
        key = tuple(None for _ in self.specs)
        prog = cached_compile(self._prog_cache, self._prog_lock, key,
                              lambda: self._make(key))
        scan_ks: List[int] = []
        kk = 2
        while kk <= megabatch_k():
            scan_ks.append(kk)
            kk <<= 1
        warmed = 0
        for cap in caps:
            fields = prewarm_zero_fields(self.op, cap)
            hargs = tuple(
                ((jax.device_put(np.arange(cap, dtype=np.int32)),
                  jax.device_put(np.zeros(cap, dtype=np.int32)))
                 if s.kind == "kreduce" else None)
                for s in self.specs)
            jax.block_until_ready(prog(fields, 0, hargs, ()))
            warmed += 1
            for k2 in scan_ks:
                sprog = cached_compile(
                    self._prog_cache, self._prog_lock,
                    ("scan", key, cap, k2),
                    lambda: self._make_scan(key, k2))
                jax.block_until_ready(sprog(
                    tuple(fields for _ in range(k2)),
                    np.zeros(k2, dtype=np.int32),
                    tuple(hargs for _ in range(k2)), ()))
                warmed += 1
        return warmed

    # -- batch path --------------------------------------------------------
    def prep_device_batch(self, batch: BatchTPU) -> Optional[Callable]:
        # HOST-PREP: per-stateful-sub-op slot mapping + grid assembly
        # (grid_meta drains the pipeline itself iff a state table must
        # grow); ONE cached-program lookup for the whole chain
        kred_hargs = None
        kextra = None
        if self._kreduce_combine is not None:
            import jax
            # key order over ALL rows (mask-independent: the program
            # applies the chain's valid mask in-trace, so the sort can
            # run before any filter verdict exists)
            order_np, ssorted_np, slot_of_key = reduce_order_and_slots(
                self.ops[-1], batch)
            if not slot_of_key:
                return None
            kred_hargs = (jax.device_put(order_np),
                          jax.device_put(ssorted_np))
            kextra = list(slot_of_key.keys())  # slot order == insertion
        statics: List[Any] = []
        hargs: List[Any] = []
        for spec in self.specs:
            if spec.engine is not None:
                grid_idx, _valid, touched, tmask, M, KB = \
                    spec.engine.grid_meta(batch)
                statics.append((M, KB))
                hargs.append((grid_idx, touched, tmask))
            elif spec.kind == "kreduce":
                statics.append(None)
                hargs.append(kred_hargs)
            else:
                statics.append(None)
                hargs.append(None)
        key = tuple(statics)
        prog = cached_compile(self._prog_cache, self._prog_lock, key,
                              lambda: self._make(key))
        hargs_t = tuple(hargs)
        engines = self._engines

        def commit() -> Optional[Callable[[], None]]:
            # tables (+ dirty bitmaps) read AT COMMIT TIME — earlier
            # queued commits reassign them (donation)
            tables = tuple((e.table, e.dirty) for e in engines)
            res = prog(batch.fields, batch.size, hargs_t, tables)
            self.stats.device_programs_run += 1  # ONE program per batch
            for eng, td in zip(engines, res[-1]):
                eng.table, eng.dirty = td
            parts = res[:-1]
            if not self._emit_reads:
                self._commit_emit(batch, parts, kextra)
                return None
            # the launch half of a chain that ends in a filter or a
            # reduce: what the emit reads (every part after the columns)
            # starts its way to the host, and the readback-and-emit goes
            # back to the queue as the finish, run one launch later
            async_host_copy(*parts[1:])
            return lambda: self._commit_emit(batch, parts, kextra)

        if self._emit_reads:
            split_commit(commit)

        # megabatch metadata: the dispatch queue groups consecutive
        # commits whose scan_sig matches (same chain, same grid shapes,
        # same capacity bucket => same compiled scan program) and hands
        # the group to scan_runner. Non-fused replicas carry no such
        # attributes and always run as singles.
        commit.scan_sig = (id(self), key, batch.capacity)
        commit.scan_payload = (batch, hargs_t, kextra)
        commit.scan_runner = self._run_megabatch
        return commit

    def _run_megabatch(self, commits: List[Callable]) -> None:
        """Commit K queued same-signature batches through ONE jitted
        ``lax.scan`` over the chain program — host prep already ran per
        batch, so this amortizes the per-program dispatch/commit
        overhead K x. Ordering points (EOS / punctuation / checkpoint /
        growth drains) never reach here: the queue's drain path always
        runs singles (``runtime/dispatch.py``)."""
        import time

        t0 = time.perf_counter()
        k = len(commits)
        payloads = [c.scan_payload for c in commits]
        key = commits[0].scan_sig[1]
        cap = payloads[0][0].capacity
        prog = cached_compile(self._prog_cache, self._prog_lock,
                              ("scan", key, cap, k),
                              lambda: self._make_scan(key, k))
        engines = self._engines
        tables = tuple((e.table, e.dirty) for e in engines)
        fields_t = tuple(p[0].fields for p in payloads)
        sizes = np.asarray([p[0].size for p in payloads], dtype=np.int32)
        hargs_tt = tuple(p[1] for p in payloads)
        per, new_tables = prog(fields_t, sizes, hargs_tt, tables)
        self.stats.device_programs_run += 1  # ONE program for K batches
        # the scan carry threads (table, dirty) batch-to-batch, so a
        # megabatch accumulates dirty bits across all K batches exactly
        # like K sequential commits would
        for eng, td in zip(engines, new_tables):
            eng.table, eng.dirty = td
        for p, parts in zip(payloads, per):
            self._commit_emit(p[0], parts, p[2])
        self.stats.note_megabatch(k, (time.perf_counter() - t0) * 1e6)

    def _commit_emit(self, batch: BatchTPU, parts,
                     kextra=None) -> None:
        """Readback + emit of one batch's program outputs — the ONE
        definition shared by the per-batch commit and the megabatch scan
        loop (their emitted batches must be byte-identical)."""
        if self._kreduce_combine is not None:
            tails, tslots, tcount, rorder, rcount = parts
            with self._st_readback(batch.bid):
                m = int(tcount)  # surviving key count (chain-exit readback)
                rn = int(rcount)
                if m:
                    ro = np.asarray(rorder)[:rn]
                    out_slots = np.asarray(tslots)[:m]
            self.stats.inputs_ignored += batch.size - rn
            if m == 0:
                return
            batch_ts = int(batch.ts_host[ro].max())
            out_keys = [kextra[s] for s in out_slots]
            ts2 = np.full(batch.capacity, batch_ts, dtype=np.int64)
            nb = BatchTPU(tails, ts2, m, batch.schema, batch.wm, out_keys)
            nb.stream_tag = batch.stream_tag
            nb.copy_trace_from(batch)
            nb.key_origin = own_key_spec(self.ops[-1])  # the reduce's keys
            self._emit_batch(nb)
        elif self._reduce_combine is not None:
            out, order, count = parts
            with self._st_readback(batch.bid):
                n_out = int(count)  # the chain's single exit readback
                if n_out:
                    order_np = np.asarray(order)
            self.stats.inputs_ignored += batch.size - n_out
            if n_out == 0:
                return
            ts = np.array([int(batch.ts_host[order_np[:n_out]].max())],
                          dtype=np.int64)
            nb = BatchTPU(out, ts, 1, batch.schema, batch.wm)
            nb.stream_tag = batch.stream_tag
            nb.copy_trace_from(batch)
            self._emit_batch(nb)
        elif self._has_filter:
            out, order, count = parts
            # emit_compacted's int(count)/np.asarray(order) readbacks
            # run here: in the finish, one launch after this batch's own
            # (inside the group's commit under a megabatch)
            self.emit_compacted(batch, out, order, count)
        else:
            (out,) = parts
            self._emit_batch(batch.with_fields(out))

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self) -> dict:
        st = super().snapshot_state()  # drains the dispatch queue
        st["__fused__"] = self.fused_signature
        st["fused_sub_states"] = [
            (spec.engine.snapshot_state() if spec.engine is not None
             else None)
            for spec in self.specs]
        return st

    def restore_state(self, state: dict) -> None:
        sig = state.get("__fused__")
        if sig is None:
            raise WindFlowError(
                f"restore: this graph fuses {self.fused_name!r} into one "
                f"device chain, but the checkpoint blob for "
                f"{self.op.name!r} holds standalone state — the "
                "checkpointed topology was fused differently (match "
                "WF_TPU_FUSION / the chain() calls of the original graph)")
        if list(sig) != self.fused_signature:
            raise WindFlowError(
                "restore: fused-chain mismatch — the checkpoint holds "
                f"{'∘'.join(sig)!r}, this graph builds "
                f"{self.fused_name!r}")
        super().restore_state(state)
        subs = state.get("fused_sub_states")
        if subs is None or len(subs) != len(self.specs):
            raise WindFlowError(
                f"restore: fused chain {self.fused_name!r} expects "
                f"{len(self.specs)} per-sub-op states, checkpoint holds "
                f"{0 if subs is None else len(subs)}")
        # positional restore: entry i belongs to sub-op i
        for spec, sub in zip(self.specs, subs):
            if spec.engine is not None:
                spec.engine.restore_state(sub or {})


class FusedFfatReplica(FfatTPUReplica):
    """A fused device chain TERMINATED by ``Ffat_Windows_TPU``: the
    chain's stateless map/filter prefix composes INTO the window
    replica's own per-batch step program, so ``source -> map -> filter
    -> Ffat_Windows`` runs as ONE composed program per batch — the
    forest rides as donated carried state, compaction + fire readback
    happen once at chain exit (unchanged FFAT commit plane).

    Two composition seams (the ``FfatTPUReplica`` hooks):

    - ``_lift_fn``: the prefix kernels run in front of the user lift
      inside every step/ingest program, so the data plane needs no
      extra program for the prefix maps;
    - ``_prefix_mask``: when the prefix contains filters, the keep mask
      is resolved at PREP time by a small cached mask program (one bool
      readback per batch). It must be: the host control plane's
      liveness quantities (max_leaf / next_fire / CB count) are exact,
      so a row the filter drops may never register a key, advance a
      leaf, or count toward a CB window — otherwise fused and unfused
      topologies would fire different windows. Map-only prefixes skip
      the mask program entirely: ONE program per batch, total.

    Legality (enforced again here after ``topology/stage.py``): the
    prefix is stateless map/filter only — a stateful prefix would run
    twice per batch (mask + compose) and double-advance its grid — and
    the prefix must not rewrite the key field (same PR-4 contract as
    every fused keyed chain: ``_keys_compatible`` checks names only)."""

    def __init__(self, ops, idx: int) -> None:
        ops = list(ops)
        super().__init__(ops[-1], idx)
        self.ops = ops
        self.fused_name = "∘".join(o.name for o in ops)
        self.stats.op_name = self.fused_name
        self.stats.fused_ops = len(ops)
        prefix = ops[:-1]
        for o in prefix:
            if not isinstance(o, (Map_TPU, Filter_TPU)) \
                    or o.state_init is not None:
                raise WindFlowError(
                    f"{self.fused_name}: only stateless map/filter "
                    f"sub-ops may precede a window terminator "
                    f"({o.name} — fusion legality should have refused "
                    "this chain)")
        self._prefix_kernels = [o.device_kernel() for o in prefix]
        self._prefix_scopes = [
            program_name("filter" if isinstance(o, Filter_TPU) else "map",
                         o.name) for o in prefix]
        self._prefix_filters = any(isinstance(o, Filter_TPU)
                                   for o in prefix)
        self._tag = tuple(o.name for o in prefix)

    # -- identity ----------------------------------------------------------
    @property
    def fused_signature(self) -> List[str]:
        return [op.name for op in self.ops]

    # -- composition seams -------------------------------------------------
    def _chain_tag(self):
        return ("chain",) + self._tag

    def _lift_fn(self) -> Callable:
        import jax.numpy as jnp

        import jax

        kernels = self._prefix_kernels
        scopes = self._prefix_scopes
        lift = super()._lift_fn()
        if not kernels:
            return lift

        def lifted(fields):
            n = next(iter(fields.values())).shape[0]
            valid = jnp.ones((n,), bool)
            for kern, scope in zip(kernels, scopes):
                with jax.named_scope(scope):
                    fields, valid, _ = kern(fields, valid, None)
            # rows the prefix filtered compute garbage through the lift;
            # their segment lanes carry the sentinel (prep scattered the
            # packed composite over surviving rows only), so the scan
            # plane drops them before any leaf is touched
            return lift(fields)

        return lifted

    def _prefix_mask(self, batch: BatchTPU):
        if not self._prefix_filters:
            return None
        prog = cached_compile(self._prog_cache, self.op._prog_lock,
                              ("fmask", batch.capacity, self._tag),
                              self._make_mask)
        # prep-time readback of the keep mask (bools, one D2H): the
        # price of exact host liveness under a fused filter — map-only
        # chains never pay it
        keep = np.asarray(prog(batch.fields, batch.size))
        self.stats.device_programs_run += 1
        return keep[:batch.size]

    def _make_mask(self) -> Callable:
        import jax.numpy as jnp

        kernels = self._prefix_kernels

        def mask(fields, size):
            n = next(iter(fields.values())).shape[0]
            valid = jnp.arange(n) < size
            for kern in kernels:
                fields, valid, _ = kern(fields, valid, None)
            return valid

        return instrumented_jit(mask, self.stats,
                                label=f"{self.fused_name}:mask",
                                program=program_name(_PROG_MASK, *self._tag))

    # -- prewarm -----------------------------------------------------------
    def _prewarm_entry(self):
        # batches arrive with the CHAIN ENTRY's schema (the prefix maps
        # transform columns in-program)
        return self.ops[0]

    def prewarm(self, caps) -> Optional[int]:
        warmed = super().prewarm(caps)
        if warmed is None or not self._prefix_filters:
            return warmed
        import jax

        entry = self._prewarm_entry()
        for cap in caps:
            prog = cached_compile(self._prog_cache, self.op._prog_lock,
                                  ("fmask", cap, self._tag),
                                  self._make_mask)
            jax.block_until_ready(prog(prewarm_zero_fields(entry, cap), 0))
            warmed += 1
        return warmed

    # -- checkpointing -----------------------------------------------------
    def snapshot_state(self) -> dict:
        st = super().snapshot_state()  # drains the dispatch queue
        st["__fused__"] = self.fused_signature
        return st

    def restore_state(self, state: dict) -> None:
        sig = state.get("__fused__")
        if sig is None:
            raise WindFlowError(
                f"restore: this graph fuses {self.fused_name!r} into one "
                f"device chain, but the checkpoint blob for "
                f"{self.op.name!r} holds standalone state — the "
                "checkpointed topology was fused differently (match "
                "WF_TPU_FUSION / the chain() calls of the original graph)")
        if list(sig) != self.fused_signature:
            raise WindFlowError(
                "restore: fused-chain mismatch — the checkpoint holds "
                f"{'∘'.join(sig)!r}, this graph builds "
                f"{self.fused_name!r}")
        st = dict(state)
        st.pop("__fused__", None)
        super().restore_state(st)


def make_fused_replica(ops, idx: int):
    """Replica factory for a chained device stage: a window-terminated
    chain composes into the window replica's own step program
    (``FusedFfatReplica``); everything else — including keyed/global
    reduce terminators — runs the generic composed-kernel program
    (``FusedTPUReplica``)."""
    if isinstance(ops[-1], Ffat_Windows_TPU):
        return FusedFfatReplica(ops, idx)
    return FusedTPUReplica(ops, idx)
