"""Source operator and Source_Shipper.

Parity: ``wf/source.hpp:55-163`` (user functor drives the shipper, then EOS)
and ``wf/source_shipper.hpp`` (``push`` for INGRESS_TIME at L171/210,
``pushWithTimestamp``/``setNextWatermark`` for EVENT_TIME at L248/289/328).
Timestamps are microseconds; in DEFAULT mode with ingress time the watermark
equals the tuple timestamp (monotone because "now" is monotone).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional

import numpy as np

from ..basic import (ExecutionMode, OpType, RoutingMode, TimePolicy,
                     WindFlowError, current_time_usecs)
from .base import BasicOperator, BasicReplica, arity


class SourceShipper:
    """User-visible push API for Source functors."""

    def __init__(self, replica: "SourceReplica") -> None:
        self._r = replica
        self._next_wm = 0
        self._epoch = current_time_usecs()

    # -- INGRESS_TIME ------------------------------------------------------
    def push(self, payload: Any) -> None:
        if self._r.op.time_policy is not TimePolicy.INGRESS_TIME:
            raise WindFlowError("push() requires INGRESS_TIME; use "
                                "push_with_timestamp() under EVENT_TIME")
        ts = current_time_usecs() - self._epoch
        wm = ts if self._r.op.execution_mode is ExecutionMode.DEFAULT else 0
        self._r.ship(payload, ts, wm)

    # -- EVENT_TIME --------------------------------------------------------
    def push_with_timestamp(self, payload: Any, ts: int) -> None:
        if self._r.op.time_policy is not TimePolicy.EVENT_TIME:
            raise WindFlowError("push_with_timestamp() requires EVENT_TIME")
        ts = int(ts)
        st = self._r.stats
        if ts > st.wm_max_source_ts:  # event-time lag numerator
            st.wm_max_source_ts = ts
        self._r.ship(payload, ts, self._next_wm)

    def set_next_watermark(self, wm: int) -> None:
        if wm < self._next_wm:
            raise WindFlowError("watermarks must be non-decreasing")
        self._next_wm = int(wm)

    # -- columnar fast path ------------------------------------------------
    def push_columns(self, cols, ts=None) -> None:
        """Push a whole COLUMN BATCH (dict of equal-length 1-D numpy
        arrays) in one call. On a device edge this skips per-tuple Python
        entirely — the arrays are padded and shipped as one ``BatchTPU``
        (the reference's per-tuple shipper has no analog; this is the
        tpu-first staging surface). On a CPU edge rows materialize as
        dicts. INGRESS_TIME stamps every row "now"; EVENT_TIME requires
        ``ts`` (int64 array, same length)."""
        n = -1
        for v in cols.values():
            if n < 0:
                n = len(v)
            elif len(v) != n:
                raise WindFlowError("push_columns: ragged columns")
        if n <= 0:
            return
        if self._r.op.time_policy is TimePolicy.INGRESS_TIME:
            if ts is not None:
                raise WindFlowError("push_columns(ts=...) requires "
                                    "EVENT_TIME")
            now = current_time_usecs() - self._epoch
            ts_arr = np.full(n, now, dtype=np.int64)
            wm = (now if self._r.op.execution_mode is ExecutionMode.DEFAULT
                  else 0)
        else:
            if ts is None:
                raise WindFlowError("push_columns under EVENT_TIME needs a "
                                    "ts array")
            ts_arr = np.asarray(ts, dtype=np.int64)
            if len(ts_arr) != n:
                raise WindFlowError("push_columns: ts length mismatch")
            st = self._r.stats
            m = int(ts_arr.max())
            if m > st.wm_max_source_ts:  # event-time lag numerator
                st.wm_max_source_ts = m
            wm = self._next_wm
        self._r.ship_columns(cols, ts_arr, wm)

    # -- checkpointing -----------------------------------------------------
    def request_checkpoint(self) -> Optional[int]:
        """Force an aligned checkpoint NOW (at this tuple boundary) instead
        of waiting for the coordinator's interval — the deterministic
        trigger used by tests and drain-style shutdowns. Returns the new
        checkpoint id, or None when checkpointing is not enabled."""
        return self._r.request_checkpoint()

    # convenience used by generators/tests
    @property
    def current_watermark(self) -> int:
        return self._next_wm


class Source(BasicOperator):
    """Parallel replicas are independent generators; ``func(shipper[, ctx])``
    is called once per replica and runs its own loop."""

    op_type = OpType.SOURCE

    def __init__(self, func: Callable, name: str = "source",
                 parallelism: int = 1, output_batch_size: int = 0) -> None:
        super().__init__(name, parallelism, RoutingMode.NONE,
                         output_batch_size=output_batch_size)
        self.func = func
        self._riched = arity(func) >= 2

    def build_replicas(self) -> None:
        self.replicas = [SourceReplica(self, i) for i in range(self.parallelism)]


class SourceReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        # sampled latency tracing (monitoring/tracing.py): every Nth
        # shipped tuple carries a wall-clock origin stamp. The gate is
        # a single integer AND against this mask — sample_every is a
        # power of two, and a mask of -1 (sampling off) can never make
        # ``inputs_received & mask`` zero, so the hot path costs the
        # same with tracing off or sampling 1/64
        self._trace_mask = self.stats.sample_every - 1
        self._st_ingest = self.stats.stage("ingest")  # one column block
        # aligned checkpointing (windflow_tpu.checkpoint): the coordinator
        # bumps an epoch; we notice at the next tuple boundary, snapshot
        # our replay position and inject the barrier downstream
        self._coord = None
        self._inject_cb = None  # Worker.checkpoint_now (chain-wide)
        self._last_ckpt = 0
        # set while a multi-chunk column block is mid-flight: barriers
        # may only land at BLOCK boundaries (the functor's cursor moves
        # per block, so a mid-block barrier would replay already-emitted
        # chunks after a restore — see ColumnarSourceReplica._drive)
        self._inject_suppressed = False
        self._restore_position = None
        # overload admission control (windflow_tpu.overload): the
        # governor installs an AdmissionGate here while shedding; the
        # default hot path pays one is-None check per push. Shedding
        # happens HERE — before the emitter, the barriers and the
        # exactly-once plane — so shed records never enter a channel,
        # a snapshot or a sink transaction.
        self._gate = None
        # records that were buffered in an admission gate at snapshot
        # time (restore_state stashes them; run_source re-emits before
        # the functor resumes — the cursor is already past them)
        self._restore_gate_pending = None

    def process(self, payload, ts, wm, tag):  # pragma: no cover
        raise WindFlowError("Source has no input")

    # -- checkpointing -----------------------------------------------------
    def bind_checkpoint(self, coordinator, inject_cb) -> None:
        """Wired by the source Worker when checkpointing is enabled."""
        self._coord = coordinator
        self._inject_cb = inject_cb
        self._last_ckpt = coordinator.requested_id

    def request_checkpoint(self):
        if self._coord is None:
            return None
        cid = self._coord.trigger(force=True)
        self._maybe_inject()
        return cid

    def _maybe_inject(self) -> None:
        from ..message import Barrier
        cid = self._coord.requested_id
        if cid > self._last_ckpt:
            self._last_ckpt = cid
            self._inject_cb(Barrier(cid))

    def final_checkpoint(self) -> None:
        """Called by the worker when the generation loop ends, before the
        EOS cascade: an epoch opened while we were finishing still gets
        this source's barrier + (final) position snapshot."""
        if self._coord is not None:
            self._maybe_inject()

    def snapshot_state(self) -> dict:
        """Base state + the functor's replay position when it speaks the
        replayable protocol: ``snapshot_position([ctx])`` returning any
        picklable cursor, and ``restore(position[, ctx])`` on restart.
        The position must describe exactly the tuples pushed so far —
        barriers inject at push boundaries, so a one-tuple-per-increment
        cursor gives exact resume; coarser cursors give at-least-once."""
        st = super().snapshot_state()
        st["shipped"] = self.stats.inputs_received
        # shed accounting rides the snapshot: a restore/rescale must not
        # zero counters for records that are gone for good
        st["shed_records"] = self.stats.shed_records
        st["shed_bytes"] = self.stats.shed_bytes
        snap = getattr(self.op.func, "snapshot_position", None)
        if snap is not None:
            st["position"] = (snap(self.context) if arity(snap) >= 1
                              else snap())
        gate = self._gate
        if gate is not None and gate.pending:
            # records accepted into the gate but still awaiting tokens:
            # the position above already covers them (the cursor
            # advanced when they were pushed), so they must ride the
            # snapshot — a restore that dropped them would lose records
            # that are neither admitted nor shed
            st["gate_pending"] = gate.snapshot_pending()
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        self._restore_position = state.get("position")
        self._restore_gate_pending = state.get("gate_pending")
        self.stats.inputs_received = state.get("shipped", 0)
        self.stats.shed_records = state.get("shed_records", 0)
        self.stats.shed_bytes = state.get("shed_bytes", 0)

    def run_source(self) -> None:
        """Run the user generation loop to completion (then the worker
        triggers the EOS cascade, ``wf/source.hpp:114-129``)."""
        shipper = SourceShipper(self)
        if self._restore_position is not None:
            restore = getattr(self.op.func, "restore", None)
            if restore is None:
                raise WindFlowError(
                    f"{self.op.name}: checkpoint restore needs a replayable "
                    "source functor (snapshot_position()/restore(position)); "
                    "this one has no restore()")
            if arity(restore) >= 2:
                restore(self._restore_position, self.context)
            else:
                restore(self._restore_position)
        pend = self._restore_gate_pending
        if pend:
            # records the snapshot caught inside an admission gate's
            # buffer: the restored cursor is already past them, so the
            # functor will never regenerate them — re-emit (with their
            # accept-time watermarks) before the loop resumes, ahead of
            # everything the replay produces
            self._restore_gate_pending = None
            for p, t, w in pend:
                self._advance_wm(w)
                self._emit_admitted(p, t)
        self._drive(shipper)
        gate = self._gate
        if gate is not None and gate.pending:
            # end-of-stream with records still buffered in the admission
            # gate: they were ACCEPTED (only awaiting tokens) — emit them
            # rather than silently dropping accepted data at EOS
            for p, t, w in gate.drain_pending():
                self._advance_wm(w)
                self._emit_admitted(p, t)

    def _drive(self, shipper: SourceShipper) -> None:
        """Run the user functor (the generation loop). Subclasses with a
        different functor contract (block sources) override this; the
        restore / gate-pending / EOS-drain bracket in ``run_source``
        stays shared."""
        if self.op._riched:
            self.op.func(shipper, self.context)
        else:
            self.op.func(shipper)

    def ship(self, payload: Any, ts: int, wm: int) -> None:
        # barrier BEFORE the tuple: the functor's cursor has not advanced
        # past the tuple being pushed (the natural ``v = pos; push(v);
        # pos += 1`` style), so the snapshot position covers exactly the
        # tuples already emitted and the in-flight one replays post-restore
        if self._coord is not None \
                and self._coord.requested_id != self._last_ckpt:
            self._maybe_inject()
        gate = self._gate
        if gate is not None:
            # the watermark rides each record through the gate: while
            # records wait in its buffer ``cur_wm`` must NOT advance
            # past them, or they would emit under a watermark newer
            # than their ts and downstream windows the gate chose to
            # ADMIT them into would already be closed
            for p, t, w in gate.offer(payload, ts, wm):
                self._advance_wm(w)
                self._emit_admitted(p, t)
            if gate.released and not gate.pending:
                self._gate = None  # recovery: back to the ungated path
            return
        if wm > self.cur_wm:
            self.cur_wm = wm
            st = self.stats
            st.wm_current = wm
            st.wm_advances += 1
        self._emit_admitted(payload, ts)

    def _emit_admitted(self, payload: Any, ts: int) -> None:
        st = self.stats
        st.inputs_received += 1
        if not (st.inputs_received & self._trace_mask):
            self.emitter.trace_ts = current_time_usecs()
        self.emitter.emit(payload, ts, self.cur_wm)

    def ship_columns(self, cols, ts_arr, wm: int) -> None:
        # one pushed block, gate to emit, waits included (blk:ingest)
        with self._st_ingest():
            if self._coord is not None and not self._inject_suppressed \
                    and self._coord.requested_id != self._last_ckpt:
                self._maybe_inject()  # before the push, like ship()
            gate = self._gate
            if gate is not None:
                if gate.pending:
                    # row-path records accepted into the buffer precede
                    # this batch: emit them (with their accept-time
                    # watermarks) first — discarding them here would lose
                    # accepted records, emitting them later would reorder
                    for p, t, w in gate.drain_pending():
                        self._advance_wm(w)
                        self._emit_admitted(p, t)
                if gate.released:
                    self._gate = None  # recovery: back to the ungated path
                else:
                    cols, ts_arr, n = gate.offer_columns(cols, ts_arr)
                    if n == 0:
                        return
            if wm > self.cur_wm:
                self.cur_wm = wm
                self.stats.wm_current = wm
                self.stats.wm_advances += 1
            st = self.stats
            n = len(ts_arr)
            base = st.inputs_received
            st.inputs_received = base + n
            trace_rows = None
            se = st.sample_every
            if se:
                # vectorized mask gate: the traced cohort is exactly the rows
                # the row path would stamp — global positions base+1+i that
                # are multiples of sample_every — computed as one arange, all
                # sharing one wall-clock stamp (per-row clock reads would
                # defeat the no-Python fast path)
                first = (-(base + 1)) % se
                if first < n:
                    trace_rows = np.arange(first, n, se)
                    self.emitter.trace_ts = current_time_usecs()
            self.emitter.emit_columns(cols, ts_arr, self.cur_wm, trace_rows)
            st.ingest_rows += n


class Columnar_Source(Source):
    """Schema-declared BLOCK source: the functor is a generator of column
    blocks instead of a per-tuple push loop. Called as ``func([ctx])``,
    it yields ``cols`` (a dict of equal-length 1-D arrays; INGRESS_TIME),
    ``(cols, ts)`` (int64 microsecond timestamps; EVENT_TIME) or
    ``(cols, ts, wm)`` (also advances the watermark before the push).
    Blocks ride ``SourceReplica.ship_columns`` — barriers, the admission
    gate, trace stamps and watermark triples all operate on block
    boundaries, and on a device edge no per-tuple Python runs at all.

    ``block_size`` (builder: ``with_block_size``; env default
    ``WF_INGEST_BLOCK_ROWS``) re-chunks oversized yields; barriers still
    land only at FUNCTOR-YIELD boundaries so a replayable functor's
    block-granular cursor stays exact. ``schema`` (name -> numpy dtype)
    canonicalizes each declared column's dtype at the edge."""

    def __init__(self, func: Callable, name: str = "columnar_source",
                 parallelism: int = 1, output_batch_size: int = 0,
                 block_size: int = 0,
                 schema: Optional[Dict[str, Any]] = None) -> None:
        super().__init__(func, name, parallelism, output_batch_size)
        if block_size <= 0:
            try:
                block_size = int(os.environ.get("WF_INGEST_BLOCK_ROWS", "0"))
            except ValueError:
                block_size = 0
        self.block_size = max(0, block_size)
        self.block_schema = ({k: np.dtype(v) for k, v in schema.items()}
                             if schema else None)
        # the block functor takes (ctx) not (shipper[, ctx]): rich means
        # it wants the RuntimeContext
        self._riched = arity(func) >= 1

    def build_replicas(self) -> None:
        self.replicas = [ColumnarSourceReplica(self, i)
                         for i in range(self.parallelism)]


class ColumnarSourceReplica(SourceReplica):
    """Drives a block functor; everything else (restore, gate pending,
    EOS gate drain, snapshot semantics) is the row replica's."""

    def _drive(self, shipper: SourceShipper) -> None:
        op = self.op
        it = op.func(self.context) if op._riched else op.func()
        if it is None:
            return
        bs = op.block_size
        schema = op.block_schema
        for block in it:
            cols, ts, wm = _normalize_block(block)
            if schema is not None:
                # asarray is copy-free when the dtype already matches
                cols = {k: (np.asarray(v, dtype=schema[k])
                            if k in schema else v)
                        for k, v in cols.items()}
            if wm is not None:
                shipper.set_next_watermark(int(wm))
            n = 0
            for v in cols.values():
                n = len(v)
                break
            if bs and n > bs:
                # re-chunk to the declared block size; suppress barrier
                # injection between chunks — the functor's cursor covers
                # whole blocks, so a mid-block barrier would double-emit
                # the leading chunks after a restore
                off = 0
                try:
                    while off < n:
                        end = min(off + bs, n)
                        shipper.push_columns(
                            {k: v[off:end] for k, v in cols.items()},
                            ts[off:end] if ts is not None else None)
                        self._inject_suppressed = True
                        off = end
                finally:
                    self._inject_suppressed = False
            else:
                shipper.push_columns(cols, ts)


def _normalize_block(block):
    """(cols, ts_or_None, wm_or_None) from a block functor yield."""
    if isinstance(block, dict):
        return block, None, None
    if isinstance(block, tuple):
        if len(block) == 2:
            return block[0], block[1], None
        if len(block) == 3:
            return block
    raise WindFlowError(
        "Columnar_Source functor must yield cols dicts or "
        "(cols, ts[, wm]) tuples, got " + type(block).__name__)


class ArrayBlockSource:
    """Replayable block functor over in-memory numpy columns: yields
    ``block_size``-row slices. The cursor advances AFTER each yield, so
    a barrier injected during the push snapshots a position that covers
    exactly the blocks already shipped — the in-flight block replays
    post-restore (exactly-once with aligned checkpointing)."""

    def __init__(self, cols: Dict[str, Any], ts: Optional[Any] = None,
                 block_size: int = 8192) -> None:
        if block_size <= 0:
            raise WindFlowError("ArrayBlockSource: block_size must be > 0")
        self._cols = {k: np.asarray(v) for k, v in cols.items()}
        n = -1
        for v in self._cols.values():
            if n < 0:
                n = len(v)
            elif len(v) != n:
                raise WindFlowError("ArrayBlockSource: ragged columns")
        self._ts = None if ts is None else np.asarray(ts, dtype=np.int64)
        if self._ts is not None and len(self._ts) != max(n, 0):
            raise WindFlowError("ArrayBlockSource: ts length mismatch")
        self._n = max(n, 0)
        self._bs = block_size
        self._pos = 0

    def __call__(self):
        while self._pos < self._n:
            lo = self._pos
            hi = min(lo + self._bs, self._n)
            cols = {k: v[lo:hi] for k, v in self._cols.items()}
            if self._ts is None:
                yield cols
            else:
                yield cols, self._ts[lo:hi]
            self._pos = hi

    # replayable-source protocol (block-granular cursor)
    def snapshot_position(self) -> int:
        return self._pos

    def restore(self, position: int) -> None:
        self._pos = int(position)


def arrow_block_source(table, ts_column: Optional[str] = None,
                       block_size: int = 8192) -> ArrayBlockSource:
    """Block functor over a pyarrow Table / RecordBatch: columns convert
    to numpy once (zero-copy where the Arrow layout allows) and stream
    as ``ArrayBlockSource`` blocks. Gated on pyarrow being installed."""
    try:
        import pyarrow  # noqa: F401
    except Exception as exc:  # pragma: no cover - depends on environment
        raise WindFlowError(
            "arrow_block_source requires pyarrow, which is not "
            "available in this environment") from exc
    tbl = table.combine_chunks() if hasattr(table, "combine_chunks") else table
    cols = {}
    for name in tbl.schema.names:
        col = tbl.column(name) if hasattr(tbl, "column") else tbl[name]
        try:
            cols[name] = col.to_numpy(zero_copy_only=True)
        except Exception:
            cols[name] = col.to_numpy(zero_copy_only=False)
    ts = cols.pop(ts_column) if ts_column else None
    return ArrayBlockSource(cols, ts, block_size)
