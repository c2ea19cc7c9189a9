"""TPU edge emitters — the device-plane routing (reference
``wf/forward_emitter_gpu.hpp`` / ``wf/keyby_emitter_gpu.hpp`` /
``wf/broadcast_emitter_gpu.hpp``, template cases <inputGPU, outputGPU>).

- TPUStageEmitter  (CPU -> TPU): accumulates rows + keys into columnar
  staging and ships a ``BatchTPU`` per ``output_batch_size`` tuples: one
  host buffer and one ``device_put`` per dtype group of the schema, the
  columns packed end to end (``tpu/batch.py`` ``StagingBuffers`` /
  ``PackedFields``). JAX ``device_put`` dispatch is async, which provides
  the copy/compute overlap the reference gets from double-buffered pinned
  staging (``keyby_emitter_gpu.hpp:443-505``). KEYBY routing hashes on the host and
  keeps one staging buffer per destination; partial batches flush on
  punctuation/EOS (pad+mask instead of variable shapes).
- TPUForward/Broadcast/KeyByEmitter (TPU -> TPU): batches pass by
  reference (device arrays are immutable); a keyed re-shard gathers
  per-destination sub-batches on device from host-computed index vectors
  (the reference rebuilds its key-index maps with device sort/unique,
  ``keyby_emitter_gpu.hpp:518-583`` — here the host key list is the
  canonical metadata, so no device pass is needed).
- TPUExitEmitter   (TPU -> CPU): D2H (``transfer2CPU``) then delegates rows
  to a wrapped CPU emitter (``forward_emitter_gpu.hpp:323-326``).
"""

from __future__ import annotations

import datetime as _dt
import os
import time
from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..basic import ExecutionMode, WindFlowError
from ..message import Batch
from ..monitoring.tracing import StageCounters, next_batch_id, stamp_ns
from ..runtime.emitters import BasicEmitter, SplitMask
from .batch import (BatchTPU, StagingBuffers, async_host_copy,
                    bucket_capacity, gather_columns_each)
from .schema import TupleSchema


class TPUStageEmitter(BasicEmitter):
    """CPU->TPU staging. Routing: FORWARD round-robins full batches,
    KEYBY partitions rows by key hash, BROADCAST ships shared batches."""

    _SWEEP_EVERY = 256  # appended rows between staging-age sweeps

    def __init__(self, num_dests: int, output_batch_size: int,
                 schema: Optional[TupleSchema],
                 key_extractor: Optional[Callable],
                 routing: str = "forward",
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 key_field: Optional[str] = None,
                 key_fields: Optional[Tuple[str, ...]] = None) -> None:
        super().__init__(num_dests, output_batch_size, execution_mode)
        self.schema = schema
        self.key_extractor = key_extractor
        self.key_field = key_field  # string extractor: vectorized keys
        self.key_fields = key_fields  # composite extractor: stacked columns
        self.routing = routing
        n_bufs = num_dests if routing == "keyby" else 1
        self._rows: List[list] = [[] for _ in range(n_bufs)]
        self._keys: List[list] = [[] for _ in range(n_bufs)]
        self._wms: List[int] = [0] * n_bufs
        # block-native staging (append_columns): per-destination
        # ``StagingBuffers`` whose column views are filled IN PLACE by
        # array-slice copies — the columnar twin of ``_rows``. A buffer
        # holds row-staged OR block-staged data, never both (the append
        # paths ship the other form first, preserving order). Key slices
        # accumulate as parts and are concatenated once per flush.
        self._cbuf: List[Optional[StagingBuffers]] = [None] * n_bufs
        self._cts: List[Optional[np.ndarray]] = [None] * n_bufs
        self._ckparts: List[list] = [[] for _ in range(n_bufs)]
        self._ccount: List[int] = [0] * n_bufs
        self._ccap = 0  # capacity bucket of the block staging buffers
        # per-buffer min/max origin stamps of traced rows (latency tracing)
        self._trace_lo: List[int] = [0] * n_bufs
        self._trace_hi: List[int] = [0] * n_bufs
        # timeline identity of the batch each buffer is filling (given
        # when the buffer opens, so wf:stage and wf:h2d carry the id the
        # shipped batch keeps; monitoring/tracing.py)
        self._bids: List[int] = [0] * n_bufs
        self._bind_stages(StageCounters())
        self._rr = 0
        # time-bounded staging (reference: the GPU keyby emitter flushes
        # partial batches rather than parking them, keyby_emitter_gpu.hpp:
        # 740): a partial batch older than this ships even though it is
        # not full, so low-rate streams pay at most ~this much batching
        # delay instead of the full fill time. Only binds when the batch
        # fills SLOWER than the bound — saturated streams are unaffected.
        # Partial batches keep the full capacity bucket: no new compiles.
        # Default 25 ms: the YSB A/B (PERF.md) showed 5 ms multiplies the
        # program count enough to hurt BOTH latency and throughput when
        # host and XLA share cores; 25 ms beat 0 and 5 on each metric.
        try:
            age_ms = float(os.environ.get("WF_MAX_STAGING_MS", "25"))
        except ValueError:
            age_ms = 25.0
        self._stage_age_s = age_ms / 1e3 if age_ms > 0 else None
        self._first_append: List[Optional[float]] = [None] * n_bufs
        self._sweep_every = self._SWEEP_EVERY
        self._sweep_countdown = 1  # first append reads the clock, then adapts
        self._last_sweep = time.monotonic()
        # staging-buffer recycling over async H2D (reference
        # recycling_gpu.hpp per-emitter pools + in-transit counters)
        from ..recycling import ArrayPool, InFlightRecycler
        self.recycler = InFlightRecycler(ArrayPool())
        self._pool_seen = (0, 0)  # (hits, misses) already added to stats

    def _bind_stages(self, owner: StageCounters) -> None:
        self._st_stage = owner.stage("stage")
        self._st_h2d = owner.stage("h2d")
        # Stage_h2d_puts / Stage_unpacked_columns of the batches shipped
        self._counters = owner

    def set_stats(self, stats) -> None:
        super().set_stats(stats)
        self._bind_stages(stats)

    def _update_pool_stats(self) -> None:
        """Accumulate pool counter DELTAS: several emitters may share one
        StatsRecord (split branches), so assignment would drop data."""
        p = self.recycler.pool
        h0, m0 = self._pool_seen
        self.stats.staging_pool_hits += p.hits - h0
        self.stats.staging_pool_misses += p.misses - m0
        self._pool_seen = (p.hits, p.misses)

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        if self.schema is None:
            self.schema = TupleSchema.infer(payload)
        key = (self.key_extractor(payload)
               if self.key_extractor is not None else None)
        buf = (_dest_of_key(key, self.num_dests)
               if self.routing == "keyby" else 0)
        if self._ccount[buf]:
            self._ship(buf)  # block-staged partials precede this row
        rows = self._rows[buf]
        if not rows:
            self._wms[buf] = wm
            if self._stage_age_s is not None:
                self._first_append[buf] = time.monotonic()
        elif wm < self._wms[buf]:
            self._wms[buf] = wm
        rows.append((payload, ts))
        if self.trace_ts:  # traced row: fold its stamp into the buffer
            t0 = self.trace_ts
            self.trace_ts = 0
            if self._trace_lo[buf] == 0 or t0 < self._trace_lo[buf]:
                self._trace_lo[buf] = t0
            if t0 > self._trace_hi[buf]:
                self._trace_hi[buf] = t0
        if self.key_extractor is not None:
            self._keys[buf].append(key)
        if len(rows) >= self.output_batch_size:
            self._ship(buf)
        self._sweep_tick(1)
        self._maybe_generate_punctuation(wm)

    def _sweep_tick(self, n_rows: int) -> None:
        """Staging-age sweep bookkeeping, hoisted to once per append CALL
        on the row path and once per BLOCK on the columnar path (the
        countdown decrements by the rows the call staged, so the adaptive
        cadence sees the same row counts as per-row bookkeeping would).

        Sweep EVERY buffer: under keyby routing a shifted key
        distribution must not park another buffer's partial batch
        past the bound (the idle tick never fires on a busy stream).
        AMORTIZED with a rate-ADAPTIVE cadence — a per-row
        monotonic() + O(num_dests) loop is measurable at tens of
        millions of rows/sec, but a fixed row count would let a
        saturated-but-SLOW stream (queue never empty, so no idle
        ticks) overshoot the bound by rows_per_sweep/rate. Each
        sweep re-targets ~[age/8, age/2] between sweeps: fast
        streams settle at the 256-row cap (clock read every ~µs
        of work), slow ones walk down toward per-row checks,
        where the clock read is negligible at their rate."""
        if self._stage_age_s is None:
            return
        self._sweep_countdown -= n_rows
        if self._sweep_countdown > 0:
            return
        now = time.monotonic()
        dt = now - self._last_sweep
        self._last_sweep = now
        if dt > self._stage_age_s / 2:
            self._sweep_every = max(1, self._sweep_every // 8)
        elif dt < self._stage_age_s / 8:
            self._sweep_every = min(self._SWEEP_EVERY,
                                    self._sweep_every * 2)
        self._sweep_countdown = self._sweep_every
        for b in range(len(self._rows)):
            t0 = self._first_append[b]
            if t0 is not None and now - t0 >= self._stage_age_s \
                    and (self._rows[b] or self._ccount[b]):
                self._ship(b)

    def on_idle(self) -> bool:
        """Worker idle tick: ship partial batches older than the staging
        bound (a quiet stream must not park staged rows indefinitely)."""
        if self._stage_age_s is None:
            return False
        now = time.monotonic()
        did = False
        for buf in range(len(self._rows)):
            t0 = self._first_append[buf]
            if t0 is not None and now - t0 >= self._stage_age_s \
                    and (self._rows[buf] or self._ccount[buf]):
                self._ship(buf)
                did = True
        return did

    def _ship(self, buf: int) -> None:
        if self._ccount[buf]:
            self._ship_cbuf(buf)
        rows = self._rows[buf]
        if not rows:
            return
        keys = self._keys[buf] if self.key_extractor is not None else None
        bid = self._bids[buf] = next_batch_id()
        # row-staged batches are built whole here: the rows -> columns
        # encode + pad + device_put are all this stage
        with self._st_h2d(bid):
            batch = BatchTPU.stage(
                rows, self.schema, self._wms[buf], keys,
                bucket_capacity(self.output_batch_size
                                if len(rows) <= self.output_batch_size
                                else len(rows)),
                self.recycler, self._counters)
        n = len(rows)
        self._rows[buf] = []
        self._keys[buf] = []
        self._dispatch_batch(buf, batch, n)

    def _ship_cbuf(self, buf: int) -> None:
        """Ship a block-staged buffer: the staging buffers were filled in
        place by ``_append_part`` (at the capacity bucket), so the only
        work left is the key-part concatenation — ONE ``np.concatenate``
        per flush — zeroing a partial batch's pad rows, and one
        device_put per dtype group."""
        n = self._ccount[buf]
        if not n:
            return
        # buffers already filled in place (wf:stage), so this stage is
        # the key concat + the device_put of each group (issue time: the
        # copy itself is asynchronous)
        with self._st_h2d(self._bids[buf]):
            kparts = self._ckparts[buf]
            keys = None
            if kparts:
                keys = (kparts[0] if len(kparts) == 1
                        else np.concatenate(kparts))
            batch = BatchTPU.stage_prefilled(
                self._cbuf[buf], self._cts[buf], n, self.schema,
                self._wms[buf], keys, self._counters)
        # ownership of the staging buffers moved to the batch/recycler:
        # a fresh set is allocated at the next append (device_put may
        # alias the host buffer on the CPU backend)
        self._cbuf[buf] = None
        self._cts[buf] = None
        self._ckparts[buf] = []
        self._ccount[buf] = 0
        self._dispatch_batch(buf, batch, n)

    def _dispatch_batch(self, buf: int, batch: BatchTPU, n: int) -> None:
        if self.stats is not None:
            self.stats.outputs_sent += n
            self.stats.device_bytes_h2d += batch.nbytes()
            self._update_pool_stats()
        batch.trace_min = self._trace_lo[buf]
        batch.trace_max = self._trace_hi[buf]
        self._trace_lo[buf] = self._trace_hi[buf] = 0
        batch.bid = self._bids[buf]
        self._first_append[buf] = None
        if self.routing == "keyby":
            batch.id = self._next_ids[buf]
            self._next_ids[buf] += 1
            self.ports[buf].send(batch)
        elif self.routing == "broadcast":
            for d in range(self.num_dests):
                out = batch.copy_for_dest() if d > 0 else batch
                out.id = self._next_ids[d]
                self._next_ids[d] += 1
                self.ports[d].send(out)
        else:  # forward round-robin
            batch.id = self._next_ids[self._rr]
            self._next_ids[self._rr] += 1
            self.ports[self._rr].send(batch)
            self._rr = (self._rr + 1) % self.num_dests

    def flush(self) -> None:
        for buf in range(len(self._rows)):
            self._ship(buf)
        # EOS/flush: return every tracked staging buffer to the pool
        self.recycler.drain()

    # -- columnar fast path (push_columns) -----------------------------
    def emit_columns(self, cols, ts_arr, wm: int, trace_rows=None) -> None:
        """Columnar push entry: delegates to the block-native
        ``append_columns`` fast path. KEYBY with an arbitrary callable
        key extractor (no field name to hash vectorized) falls back to
        the generic per-row path — the documented object-key cliff
        (PERF.md)."""
        if self.routing == "keyby" and self.key_field is None \
                and self.key_fields is None:
            return super().emit_columns(cols, ts_arr, wm, trace_rows)
        self.append_columns(cols, ts_arr, wm, trace_rows)

    def append_columns(self, cols, ts_arr, wm: int, trace_rows=None) -> None:
        """Block-native staging: buffer array SLICES instead of per-row
        list appends. Each destination's slice of the block is copied
        once (vectorized) into a staging buffer that is already padded to
        the output capacity bucket; a full buffer ships with no further
        copy — ``device_put`` reads the staging array directly. KEYBY
        routing hashes the key COLUMN once, then argsort/bincount split
        the block into contiguous per-destination slices, so routing cost
        is per-block, not per-row. ``trace_rows`` (int indices) marks the
        traced cohort: a destination's ``trace_lo/hi`` fold the stamp iff
        one of ITS rows is traced."""
        n = len(ts_arr)
        if n == 0:
            return
        if self.schema is None:
            self.schema = TupleSchema(
                {k: np.asarray(v).dtype for k, v in cols.items()})
        t_trace = self.trace_ts
        self.trace_ts = 0
        tmask = None
        if t_trace and trace_rows is not None and len(trace_rows):
            # None tmask + a stamp means "whole block traced" (legacy
            # per-push stamping); an explicit cohort builds the row mask
            tmask = np.zeros(n, dtype=bool)
            tmask[trace_rows] = True
        if self.routing == "keyby":
            # routing the block is staging work with no batch yet (b=0);
            # the appends below ship, so they stay outside this span
            with self._st_stage():
                parts = self._route_block(cols, ts_arr, n, tmask)
            for d, pcols, pts, pkeys, ptm in parts:
                self._append_part(d, pcols, pts, pkeys, wm, t_trace, ptm)
        else:
            keys = None
            if self.key_field is not None:
                # copy: the caller may reuse its arrays after push_columns
                keys = np.array(cols[self.key_field])
            elif self.key_fields is not None:
                keys = _stack_key_fields(cols, self.key_fields, n)
            self._append_part(0, cols, ts_arr, keys, wm, t_trace, tmask)
        self._sweep_tick(n)
        # punctuation cadence is per TUPLE (basic.py DEFAULT_WM_AMOUNT),
        # not per columnar push
        self._emit_count += max(0, n - 1)
        self._maybe_generate_punctuation(wm)

    def _route_block(self, cols, ts_arr, n: int, tmask) -> list:
        """A KEYBY block as per-destination ``(dest, cols, ts, keys,
        tmask)`` slices."""
        kcol, dests = self._block_dests(cols, n)
        if self.num_dests == 1:
            return [(0, {k: np.asarray(v) for k, v in cols.items()},
                     ts_arr, np.array(kcol), tmask)]
        # ONE stable sort + one gather per column routes the whole
        # block; per-destination slices are then contiguous views (zero
        # further copies before the staging write)
        order = np.argsort(dests, kind="stable")
        counts = np.bincount(dests, minlength=self.num_dests)
        scols = {k: np.asarray(v)[order] for k, v in cols.items()}
        sts = ts_arr[order]
        skeys = kcol[order]
        stm = tmask[order] if tmask is not None else None
        parts = []
        off = 0
        for d in range(self.num_dests):
            c = int(counts[d])
            if c:
                sl = slice(off, off + c)
                parts.append((d, {k: v[sl] for k, v in scols.items()},
                              sts[sl], skeys[sl],
                              stm[sl] if stm is not None else None))
            off += c
        return parts

    def _block_dests(self, cols, n: int):
        """(key column, destination vector) for a KEYBY block — hashed
        vectorized where the key dtype allows, per-row only for
        object/mixed keys."""
        if self.key_field is not None:
            kcol = np.asarray(cols[self.key_field])
            dests = None
            if _int_keys_hashable_as_identity(kcol, n):
                # hash(n) == n for ints in [0, 2^61-1): the vectorized
                # modulo routes identically to the per-tuple hash of
                # the CPU/TPU keyby emitters
                dests = kcol.astype(np.int64) % self.num_dests
            elif kcol.dtype.kind in "SU":
                dests = _bytes_key_dests(kcol, n, self.num_dests)
        else:
            # composite multi-field key: a structured (void) column
            # carries the key downstream; routing is the vectorized
            # per-field FNV fold over the same structured form
            kcol = _stack_key_fields(cols, self.key_fields, n)
            dests = _vector_key_dests(kcol, n, self.num_dests)
        if dests is None:
            # object keys (mixed types): the per-row Python cliff —
            # documented + bounded in PERF.md
            dests = np.fromiter(
                (_dest_of_key(k, self.num_dests)
                 for k in kcol.tolist()),
                dtype=np.int64, count=n)
        return kcol, dests

    def _append_part(self, buf: int, pcols, pts, pkeys, wm: int,
                     t_trace: int, tmask=None) -> None:
        """Append one destination's slice of a column block to its
        staging buffer, shipping whenever the buffer reaches the output
        batch size. The single host copy per column happens here (caller
        arrays -> staging buffer), so callers may reuse their arrays."""
        if self._rows[buf]:
            self._ship(buf)  # row-staged partials precede this block
        n = len(pts)
        obs = self.output_batch_size
        if obs <= 0:
            # unbatched edge: the block ships as-is (no re-batching);
            # _dispatch_batch transfers the trace stamps, so fold them
            # into the buffer slots it reads
            bid = self._bids[buf] = next_batch_id()
            with self._st_h2d(bid):
                batch = BatchTPU.stage_columns(pcols, pts, self.schema, wm,
                                               pkeys, self.recycler,
                                               self._counters)
            if t_trace and (tmask is None or tmask.any()):
                self._trace_lo[buf] = self._trace_hi[buf] = t_trace
            self._wms[buf] = wm
            self._dispatch_batch(buf, batch, n)
            return
        off = 0
        while off < n:
            cnt = self._ccount[buf]
            if cnt == 0:
                self._wms[buf] = wm
                self._bids[buf] = next_batch_id()
                if self._stage_age_s is not None:
                    self._first_append[buf] = time.monotonic()
            elif wm < self._wms[buf]:
                self._wms[buf] = wm
            take = min(n - off, obs - cnt)
            end = off + take
            # the copy alone: shipping (wf:h2d, then a channel put that
            # may block) happens after the span has closed
            with self._st_stage(self._bids[buf]):
                cb = self._cbuf[buf]
                if cb is None:
                    cb = self._cbuf_alloc(buf)
                for name, col in cb.cols.items():
                    col[cnt:cnt + take] = pcols[name][off:end]
                self._cts[buf][cnt:cnt + take] = pts[off:end]
                if pkeys is not None:
                    self._ckparts[buf].append(pkeys[off:end])
            if t_trace and (tmask is None or tmask[off:end].any()):
                if self._trace_lo[buf] == 0 or t_trace < self._trace_lo[buf]:
                    self._trace_lo[buf] = t_trace
                if t_trace > self._trace_hi[buf]:
                    self._trace_hi[buf] = t_trace
            self._ccount[buf] = cnt + take
            off = end
            if cnt + take >= obs:
                self._ship_cbuf(buf)

    def _cbuf_alloc(self, buf: int) -> StagingBuffers:
        """One pooled host buffer per dtype group, a row view of it per
        column; uninitialised (the pad rows are zeroed at ship time)."""
        cap = self._ccap
        if cap == 0:
            cap = self._ccap = bucket_capacity(self.output_batch_size)
        cb = self._cbuf[buf] = StagingBuffers(self.schema, cap,
                                              self.recycler)
        # ts is NEVER pooled: it becomes the batch's ts_host metadata and
        # lives as long as the batch itself
        self._cts[buf] = np.zeros(cap, dtype=np.int64)
        return cb


def _maybe_prefetch_key(batch: BatchTPU, field: Optional[str]) -> None:
    """Start an async host copy of the key column when the downstream
    keyed device op will have to read it (no host key metadata on the
    batch that is ITS key — e.g. the key was computed ON DEVICE by an
    upstream Map_TPU, or the batch is a window's result keyed by another
    field). Without this, the consumer's key read is a synchronous D2H
    of a fresh buffer."""
    if field is None or batch.keys_for(field) is not None:
        return
    if field in batch.fields:
        async_host_copy(batch.fields[field])


class TPUForwardEmitter(BasicEmitter):
    """TPU->TPU forward: whole batches round-robin. ``prefetch_field``
    (set by the graph wiring) names the consumer's key column for the
    async-prefetch above."""

    prefetch_field: Optional[str] = None

    def emit_device_batch(self, batch: BatchTPU) -> None:
        _maybe_prefetch_key(batch, self.prefetch_field)
        d = getattr(self, "_rr", 0)
        batch.id = self._next_ids[d]
        self._next_ids[d] += 1
        if self.stats is not None:
            self.stats.outputs_sent += batch.size
        self.ports[d].send(batch)
        self._rr = (d + 1) % self.num_dests


class TPUBroadcastEmitter(BasicEmitter):
    """TPU->TPU broadcast: immutable device arrays are shared."""

    prefetch_field: Optional[str] = None

    def emit_device_batch(self, batch: BatchTPU) -> None:
        _maybe_prefetch_key(batch, self.prefetch_field)
        for d in range(self.num_dests):
            out = batch.copy_for_dest() if d > 0 else batch
            out.id = self._next_ids[d]
            self._next_ids[d] += 1
            if self.stats is not None:
                self.stats.outputs_sent += out.size
            self.ports[d].send(out)


class _D2HPipeline:
    """FIFO of device batches with async host copies in flight. A
    synchronous fetch of a fresh device buffer has a fixed cost
    independent of its size; keeping ``depth`` fetches in flight overlaps
    those costs with each other and with later programs. A queued batch
    is processed when a later batch pushes it out or a drain point
    (single-row emit, punctuation, flush, EOS) forces ordering.
    Latency-sensitive exits can set depth 0 (immediate, synchronous D2H)
    via the env knobs."""

    def _pipe_init(self, env_var: str, default: int,
                   depth: Optional[int] = None) -> None:
        self.depth = (depth if depth is not None
                      else int(os.environ.get(env_var, str(default))))
        try:
            age_ms = float(os.environ.get("WF_PIPELINE_MAX_AGE_MS", "100"))
        except ValueError:
            age_ms = 100.0
        # wall-clock age bound: on a saturated stream with sparse output
        # (and punctuation disabled outside DEFAULT mode) the idle tick
        # never fires, so _pipe_add itself evicts entries older than this.
        # Depth interplay: the bound only binds at inter-batch intervals
        # > age/depth (25 ms at the defaults), where the async D2H of an
        # entry that old has normally completed — eviction then is a
        # cheap consume, not a sync-fetch stall
        self._max_age_ns = int(age_ms * 1e6) if age_ms > 0 else None
        # (enqueue stamp_ns, batch): the stamp bounds the entry's age and
        # feeds the wait:fifo residency when the batch leaves
        self._pending: "deque[Tuple[int, BatchTPU]]" = deque()
        self._pipe_bind(StageCounters())

    def _pipe_bind(self, owner: StageCounters) -> None:
        """Count this FIFO's stages on ``owner`` (every ``set_stats`` of a
        class that mixes this in calls it with the replica's record)."""
        self._st_fifo = owner.stage("fifo")
        self._st_exit = owner.stage("exit")

    def _pipe_process(self, batch: BatchTPU) -> None:
        raise NotImplementedError

    def _pipe_run(self, batch: BatchTPU,
                  queued_ns: Optional[int] = None) -> None:
        """One batch through ``_pipe_process`` as the ``wf:exit`` stage;
        ``queued_ns`` is its ``_pipe_add`` stamp (None: it never waited)."""
        if queued_ns is not None:
            self._st_fifo.since(queued_ns, batch.bid, batch.cause)
        with self._st_exit(batch.bid, batch.cause):
            self._pipe_process(batch)

    def _pipe_add(self, batch: BatchTPU) -> None:
        self._pending.append((stamp_ns(), batch))
        stats = getattr(self, "stats", None)
        if stats is not None:
            stats.note_pipe_depth(len(self._pending))
        while len(self._pending) > self.depth:
            self._pipe_pop()
        if self._max_age_ns is not None:
            horizon = stamp_ns() - self._max_age_ns
            while self._pending and self._pending[0][0] < horizon:
                self._pipe_pop()

    def _pipe_pop(self) -> None:
        queued_ns, batch = self._pending.popleft()
        self._pipe_run(batch, queued_ns)

    def _drain(self) -> None:
        while self._pending:
            self._pipe_pop()

    def on_idle(self) -> bool:
        """Worker idle tick: deliver queued batches — an idle stream must
        not withhold already-computed results (Worker._process). Returns
        whether anything was drained (drives the worker's idle backoff)."""
        had = bool(self._pending)
        self._drain()
        return had


_HASH_MODULUS = (1 << 61) - 1  # CPython hash(n) == n iff 0 <= n < 2^61-1
_FNV_OFFSET = 0xcbf29ce484222325
_FNV_PRIME = 0x100000001b3
_M64 = 0xFFFFFFFFFFFFFFFF


def _column_hashes(col: np.ndarray, n: int) -> Optional[np.ndarray]:
    """Per-row uint64 hash lanes for a key (or key-element) column, or
    None when the dtype has no vectorized representation (object columns
    take the per-row path). int/uint/bool hash as their two's-complement
    uint64 value, floats as their float64 bit pattern, str/bytes ('U'/'S')
    as zero-skipping FNV-1a over codepoint/byte lanes (invariant to the
    dtype's zero padding — the same key must route identically when two
    batches of one stream infer different fixed widths), and structured
    (void) rows as an ordered FNV fold over their fields. Each case
    matches its scalar twin in ``_scalar_elem_hash`` EXACTLY: a source
    may mix push() and push_columns() on one stream, and a key's tuples
    must all reach the same replica. NOT CPython-hash-compatible, which
    is fine: keyby routing needs a deterministic, balanced key->dest map
    per edge, not a globally blessed hash (the reference's
    ``keyby_emitter.hpp:210-228`` likewise only needs std::hash
    determinism). Cost is O(n * key_width) vectorized numpy passes.
    (Tried and rejected: np.unique + one hash per distinct key — the
    C string sort alone costs more than these passes.)"""
    kind = col.dtype.kind
    if kind in "iub":
        return col[:n].astype(np.uint64)
    if kind == "f":
        # EQUALITY-COMPATIBLE float hash: keys equal under Python/dict
        # equality must route identically (CPython guarantees
        # hash(1) == hash(1.0) and hash(0.0) == hash(-0.0), and the
        # KeySlotMap dict unifies them), so integral floats hash as
        # their int value (which also normalizes -0.0 to 0) and only
        # non-integral values use their float64 bit pattern. |v| >= 2^63
        # stays on the bit pattern (int64-representable bound, matching
        # _scalar_elem_hash; an int key equal to such a float is the one
        # remaining — astronomically rare — split).
        f64 = col[:n].astype(np.float64)
        with np.errstate(invalid="ignore"):
            integral = (f64 == np.floor(f64)) & (np.abs(f64) < 2.0**63)
        iv = np.where(integral, f64, 0).astype(np.int64).astype(np.uint64)
        return np.where(integral, iv, f64.view(np.uint64))
    if kind in "Mm":
        # datetime64/timedelta64: hash the int64 of the SAME unit the
        # value materializes to on the row path (.item(), and the
        # scalar twin's np.datetime64(date)->'D' / (datetime)->'us' /
        # np.timedelta64(timedelta)->'us' conversions) — date-valued
        # units normalize to days, time-valued to microseconds, and
        # units .item() leaves as raw ints (ns and finer; 'Y'/'M'
        # timedeltas) hash raw. Without this, an 'M8[s]' column and its
        # own rows would route one key to two replicas. Values the row
        # path does NOT materialize as date/datetime/timedelta — NaT
        # (.item() -> None), unit-conversion overflow, instants beyond
        # the datetime range (.item() -> raw int in the SOURCE unit) —
        # push the whole batch to the per-row path instead, which
        # hashes the .item()ed tuples consistently with push() rows.
        unit = np.datetime_data(col.dtype)[0]
        # native byte order first (like the 'U'/'S' branch): a '>M8'
        # column would hash byte-swapped on the raw-view path below
        c = col[:n].astype(col.dtype.newbyteorder("="), copy=False)
        if np.isnat(c).any():
            return None
        canon = lo = hi = None
        if kind == "M":
            if unit in ("Y", "M", "W", "D"):
                canon, lo, hi = "M8[D]", -719162, 2932896  # date range
            elif unit in ("h", "m", "s", "ms", "us"):
                canon = "M8[us]"                     # datetime range, us
                lo, hi = -62135596800000000, 253402300799999999
        elif unit in ("W", "D", "h", "m", "s", "ms", "us"):
            canon = "m8[us]"  # every in-int64 us value is a timedelta
        if canon is None:
            return c.view(np.int64).astype(np.uint64)
        c2 = c.astype(canon)
        i64 = c2.view(np.int64)
        ok = c2.astype(c.dtype) == c  # False on conversion overflow
        if lo is not None:
            ok &= (i64 >= lo) & (i64 <= hi)
        if not ok.all():
            return None
        return i64.astype(np.uint64)
    if kind in "SU":
        lane = np.uint32 if kind == "U" else np.uint8
        # normalize to native byte order first: a '>U4' column
        # (frombuffer/parquet) viewed as uint32 lanes would hash
        # byte-swapped codepoints and split a key across replicas
        c = col[:n].astype(col.dtype.newbyteorder("="), copy=False)
        b = np.ascontiguousarray(c).view(lane).reshape(n, -1)
        h = np.full(n, _FNV_OFFSET, np.uint64)
        prime = np.uint64(_FNV_PRIME)
        for j in range(b.shape[1]):
            bj = b[:, j].astype(np.uint64)
            h = np.where(bj != 0, (h ^ bj) * prime, h)
        return h
    if kind == "V" and col.dtype.names:
        h = np.full(n, _FNV_OFFSET, np.uint64)
        prime = np.uint64(_FNV_PRIME)
        for name in col.dtype.names:
            sub = col[name]
            if sub.dtype.kind == "V":
                # nested structs materialize as nested TUPLES on the row
                # path, where _scalar_elem_hash has no fold — route
                # per-row (hash of the .item()ed tuple) on both sides
                return None
            eh = _column_hashes(sub, n)
            if eh is None:
                return None
            h = (h ^ eh) * prime
        return h
    return None


def _vector_key_dests(kcol: np.ndarray, n: int,
                      num_dests: int) -> Optional[np.ndarray]:
    """Hash-free (no per-row Python) keyby destinations for a TOP-LEVEL
    key column; None when the dtype needs the per-row path. Only
    str/bytes and structured (composite) columns qualify: top-level
    int/float keys route via CPython ``hash()`` on the per-row paths
    (identity for the common non-negative case, handled by the caller),
    and a uint64-wrap here would disagree with ``hash()`` for negative
    keys. As composite ELEMENTS ints hash by value on every path, so
    the 'V' fold stays consistent."""
    if kcol.dtype.kind not in "SUV":
        return None
    if n == 0:
        return np.zeros(0, np.int64)
    h = _column_hashes(kcol, n)
    if h is None:
        return None
    return (h % np.uint64(num_dests)).astype(np.int64)


def _bytes_key_dests(kcol: np.ndarray, n: int, num_dests: int) -> np.ndarray:
    """Vectorized routing for fixed-width bytes/str key columns (kept as
    the named entry point for the 'S'/'U' case; see _column_hashes)."""
    d = _vector_key_dests(kcol, n, num_dests)
    assert d is not None  # 'S'/'U' always vectorizes
    return d


def _composite_key_dests(fcols: List[np.ndarray], n: int,
                         num_dests: int) -> Optional[np.ndarray]:
    """Vectorized destinations for a MULTI-FIELD key given separate
    field columns: stacks them into the structured form and delegates to
    ``_vector_key_dests`` so the ordered FNV fold exists in exactly ONE
    place (the 'V' branch of ``_column_hashes`` — keyby correctness
    depends on the folds staying bit-identical). None when a field
    column has no vectorized representation."""
    cols = {f"f{i}": c for i, c in enumerate(fcols)}
    st = _stack_key_fields(cols, list(cols), n)
    return _vector_key_dests(st, n, num_dests)


def _stack_key_fields(cols, key_fields, n: int,
                      where: str = "push_columns (keyby staging edge)"):
    """Structured key column for a composite key: the structured rows
    (.item()) are the same tuples the per-row path extracts, so
    downstream slot maps unify both forms of one key. Raises a
    descriptive WindFlowError (mirroring ``composite_keys_from_device``)
    instead of a bare KeyError when a key field is missing from the
    pushed columns."""
    missing = [f for f in key_fields if f not in cols]
    if missing:
        raise WindFlowError(
            f"{where}: composite key field(s) "
            f"{', '.join(repr(f) for f in missing)} missing from the "
            f"pushed columns (have: {sorted(map(str, cols))}); every "
            "field of a composite key must be present as a column")
    fcols = [np.asarray(cols[f])[:n] for f in key_fields]
    kcol = np.empty(n, np.dtype(
        [(f, c.dtype) for f, c in zip(key_fields, fcols)]))
    for f, c in zip(key_fields, fcols):
        kcol[f] = c
    return kcol


def composite_keys_from_device(batch: BatchTPU, key_fields) -> np.ndarray:
    """Structured key column for a composite-keyed consumer fed WITHOUT
    host key metadata (an unkeyed device edge upstream): D2H the key
    field columns and stack them. The fields must be device columns —
    non-numeric composite members only travel as keyed-staging host
    metadata."""
    from ..basic import WindFlowError
    cols = {}
    for f in key_fields:
        col = batch.fields.get(f)
        if col is None:
            raise WindFlowError(
                f"composite key field {f!r} is not a device column of "
                "this batch; non-numeric composite keys must be keyed at "
                "the staging edge (with_key_by on the operator fed by "
                "the CPU plane), which carries them as host metadata")
        cols[f] = np.asarray(col)
    return _stack_key_fields(cols, key_fields, batch.size)


def _scalar_fnv(lanes) -> int:
    """Scalar twin of the 'S'/'U' branch of ``_column_hashes`` (zero
    lanes skipped): per-row str/bytes keys must route identically to
    their columnar form."""
    h = _FNV_OFFSET
    for v in lanes:
        if v:
            h = ((h ^ v) * _FNV_PRIME) & _M64
    return h


def _scalar_elem_hash(v) -> Optional[int]:
    """Scalar twin of ``_column_hashes`` for one composite-key element;
    None for element types with no columnar representation (the whole
    key then falls back to CPython hash on every path)."""
    if isinstance(v, (np.datetime64, np.timedelta64)):
        # BEFORE the int branch: np.timedelta64 subclasses np.integer
        # (int() on it raises). Normalize units exactly like the
        # kind-'M'/'m' branch of _column_hashes so non-canonical-unit
        # scalars route with their columnar forms.
        unit = np.datetime_data(v.dtype)[0]
        if isinstance(v, np.datetime64):
            if unit in ("Y", "M", "W", "D"):
                v = v.astype("M8[D]")
            elif unit in ("h", "m", "s", "ms", "us"):
                v = v.astype("M8[us]")
        elif unit in ("W", "D", "h", "m", "s", "ms", "us"):
            v = v.astype("m8[us]")
        return int(v.view(np.int64)) & _M64
    if isinstance(v, (bool, np.bool_, int, np.integer)):
        return int(v) & _M64
    if isinstance(v, (float, np.floating)):
        f = float(v)
        # integral floats hash as their int value (dict equality unifies
        # 1 and 1.0, and -0.0 with 0) — the exact twin of the kind-'f'
        # branch in _column_hashes
        if f.is_integer() and abs(f) < 2.0**63:  # False for nan/inf
            return int(f) & _M64
        return int(np.float64(f).view(np.uint64))
    if isinstance(v, str):
        return _scalar_fnv(map(ord, v))
    if isinstance(v, bytes):
        return _scalar_fnv(v)
    if isinstance(v, _dt.date):       # datetime.datetime is a date too
        return int(np.datetime64(v).view(np.int64)) & _M64
    if isinstance(v, _dt.timedelta):
        return int(np.timedelta64(v).view(np.int64)) & _M64
    return None


def _dest_of_key(key, num_dests: int) -> int:
    """Per-row keyby destination, consistent with the vectorized columnar
    routing: FNV over codepoints for str (matching numpy 'U' columns) or
    bytes ('S' columns), an ordered FNV fold over elements for tuples /
    structured rows (matching stacked-column composite keys), CPython
    hash for everything else (ints route as identity either way)."""
    if isinstance(key, str):
        return _scalar_fnv(map(ord, key)) % num_dests
    if isinstance(key, bytes):
        return _scalar_fnv(key) % num_dests
    if isinstance(key, np.void) and key.dtype.names:
        key = key.item()  # structured row -> plain tuple
    if isinstance(key, tuple):
        h = _FNV_OFFSET
        for v in key:
            eh = _scalar_elem_hash(v)
            if eh is None:
                break
            h = ((h ^ eh) * _FNV_PRIME) & _M64
        else:
            return h % num_dests
    return hash(key) % num_dests


def _int_keys_hashable_as_identity(kcol: np.ndarray, n: int) -> bool:
    """True when ``kcol % num_dests`` routes exactly like the per-tuple
    ``hash(key) % num_dests`` of the CPU/TPU keyby emitters (keys must be
    non-negative ints below the Mersenne hash modulus)."""
    if kcol.dtype.kind == "u":
        return n == 0 or int(kcol.max()) < _HASH_MODULUS
    if kcol.dtype.kind == "i":
        return n == 0 or (int(kcol.min()) >= 0
                          and int(kcol.max()) < _HASH_MODULUS)
    return False


def gather_sub_batches(batch: BatchTPU, idxs: Sequence[np.ndarray],
                       host_keys: Optional[Sequence[Any]] = None
                       ) -> List[BatchTPU]:
    """Gather each of the row sets ``idxs`` of a device batch into a new
    (smaller) device batch without leaving HBM, from host-computed index
    vectors: ONE program for them all over a dict of columns, a gather a
    dtype group over a packed batch (``gather_columns_each``).
    ``host_keys[i]`` are the keys of ``idxs[i]``'s rows where the caller
    has them already. Shared by the keyed re-shard (a call a destination)
    and the device-plane splitting emitter (a call a batch: its program is
    compiled for the branches' capacity buckets together, which a split
    of steady proportions keeps to one or two)."""
    if not idxs:
        return []
    gathers = []
    for idx in idxs:
        gather = np.zeros(bucket_capacity(idx.size), dtype=np.int32)
        gather[:idx.size] = idx
        gathers.append(gather)
    subs = []
    for i, sub_fields in enumerate(gather_columns_each(batch.fields,
                                                       gathers)):
        idx = idxs[i]
        keys2 = None if host_keys is None else host_keys[i]
        if keys2 is None and batch.host_keys is not None:
            hk = batch.host_keys
            keys2 = (hk[idx] if isinstance(hk, np.ndarray)
                     else [hk[j] for j in idx])
        sub = BatchTPU(sub_fields, batch.ts_host[gathers[i]], idx.size,
                       batch.schema, batch.wm, keys2)
        sub.stream_tag = batch.stream_tag
        subs.append(sub.caused_by(batch))  # one of several from ``batch``
    return subs


class TPUKeyByEmitter(BasicEmitter, _D2HPipeline):
    """TPU->TPU keyed re-shard: per-destination sub-batches gathered on
    device with host-computed index vectors.

    Batches WITHOUT host key metadata (key computed on device upstream)
    need a D2H of the key column before routing; those go through the
    _D2HPipeline FIFO with an async copy in flight. Batches WITH metadata
    route immediately (after draining the FIFO, preserving order)."""

    def __init__(self, key_extractor: Callable, num_dests: int,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 key_field: Optional[str] = None,
                 depth: Optional[int] = None,
                 key_fields: Optional[Tuple[str, ...]] = None) -> None:
        super().__init__(num_dests, 0, execution_mode)
        self.key_extractor = key_extractor
        self.key_field = key_field
        self.key_fields = key_fields
        self._pipe_init("WF_KEYBY_PIPELINE_DEPTH", 2, depth)

    def _keys_of(self, batch: BatchTPU):
        keys = batch.keys_for(self.key_field or self.key_fields)
        if keys is not None:
            return keys
        if self.key_field is not None:
            from .batch import key_column_to_list
            return key_column_to_list(batch, self.key_field)
        if self.key_fields:
            return composite_keys_from_device(batch, self.key_fields)
        raise RuntimeError(
            "keyed TPU re-shard needs host key metadata or a field-name "
            "key extractor (with_key_by('field') or a tuple of fields)")

    def emit_device_batch(self, batch: BatchTPU) -> None:
        mine = self.key_field or self.key_fields
        if self.num_dests == 1:
            if batch.host_keys is not None:
                # where they are another operator's keys (a window's
                # result), the one consumer reads its own key column
                _maybe_prefetch_key(batch, self.key_field)
            self._drain()
            batch.id = self._next_ids[0]
            self._next_ids[0] += 1
            if self.stats is not None:
                self.stats.outputs_sent += batch.size
            self.ports[0].send(batch)
            return
        if mine and batch.keys_for(mine) is None:
            for f in ((self.key_field,) if self.key_field is not None
                      else self.key_fields):
                async_host_copy(batch.fields.get(f))
            self._pipe_add(batch)
            return
        self._drain()  # keep stream order ahead of an immediate route
        self._pipe_run(batch)

    def set_stats(self, stats) -> None:
        super().set_stats(stats)
        self._pipe_bind(stats)

    def flush(self) -> None:
        # BasicEmitter's propagate_punctuation/send_eos_all call flush()
        # first, so draining here covers every ordering point
        self._drain()
        super().flush()

    def _pipe_process(self, batch: BatchTPU) -> None:
        host_keys = self._keys_of(batch)
        dests = None
        if isinstance(host_keys, np.ndarray):
            if _int_keys_hashable_as_identity(host_keys[:batch.size],
                                              batch.size):
                # hash(n) == n for ints in [0, 2^61-1): vectorized routing
                dests = (host_keys[:batch.size].astype(np.int64)
                         % self.num_dests)
            else:
                # str/bytes lanes and structured (composite) rows both
                # vectorize; None falls through to the per-row path
                dests = _vector_key_dests(host_keys, batch.size,
                                          self.num_dests)
        if dests is None:
            dests = np.fromiter(
                (_dest_of_key(k, self.num_dests) for k in host_keys),
                dtype=np.int64, count=batch.size)
        for d in range(self.num_dests):
            idx = np.nonzero(dests == d)[0]
            if idx.size == 0:
                continue
            # a program a destination: how the keys fall varies by the
            # batch, and one program for all would compile anew for every
            # combination of the destinations' capacity buckets
            sub, = gather_sub_batches(
                batch, [idx],
                [host_keys[idx] if isinstance(host_keys, np.ndarray)
                 else [host_keys[j] for j in idx]])
            sub.key_origin = None    # keyed for the consumer, by its key
            sub.id = self._next_ids[d]
            self._next_ids[d] += 1
            if self.stats is not None:
                self.stats.outputs_sent += sub.size
            self.ports[d].send(sub)


class TPUSplittingEmitter(BasicEmitter, _D2HPipeline):
    """Device-plane split (reference ``wf/splitting_emitter_gpu.hpp:48-341``,
    wired at ``wf/multipipe.hpp:698-708``): routes per-branch sub-batches
    after a TPU operator. The reference transfers the whole batch to host
    and re-stages per branch; here the data stays in HBM — only the routing
    decision touches the host, and each branch receives a device gather of
    its rows (same shape as the keyed re-shard).

    ``splitting_logic`` forms:
    - a string field name: the int32/int64 column holds the branch index
      per row (vectorized: one column D2H, no per-tuple Python);
    - a ``SplitMask`` (``split(field, n, mask=True)``): the column holds
      a bitmask of branches per row, bit ``b`` for branch ``b``, so a row
      may go to several (the same one column D2H, a few whole-column
      operations, no per-row Python);
    - a callable payload -> int | iterable[int] | None (reference
      contract): rows are materialized once per batch to evaluate it.

    A branch that every row of a batch selects gets the batch whole
    (``Split_whole_batches``), one that some rows select a device gather
    of them (``Split_gathered_batches``), one that none selects the
    batch's watermark alone.
    """

    def __init__(self, splitting_logic, inner_emitters: List[BasicEmitter],
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 depth: Optional[int] = None) -> None:
        super().__init__(sum(e.num_dests for e in inner_emitters), 0,
                         execution_mode)
        self.splitting_logic = splitting_logic
        # the one column the routing reads, where the logic names one
        self._field = splitting_logic.field \
            if isinstance(splitting_logic, SplitMask) else (
                splitting_logic if isinstance(splitting_logic, str)
                else None)
        self.inner = inner_emitters
        # the routing decision needs a D2H read; pipeline it (_D2HPipeline)
        self._pipe_init("WF_SPLIT_PIPELINE_DEPTH", 2, depth)

    def set_stats(self, stats) -> None:
        self.stats = stats
        self._pipe_bind(stats)
        for e in self.inner:
            e.set_stats(stats)

    def _routing_column(self, batch: BatchTPU) -> np.ndarray:
        col = np.asarray(batch.fields[self._field])[:batch.size]
        if self.stats is not None:
            self.stats.device_bytes_d2h += int(col.nbytes)
        return col

    def _out_of_range(self, what: str, lo: int, hi: int, bound: int):
        op = self.stats.op_name if self.stats is not None else "?"
        return WindFlowError(
            f"split after {op!r}: field {self._field!r} holds {what} "
            f"{lo}..{hi} outside [0, {bound})")

    def _branch_rows(self, batch: BatchTPU) -> List[Optional[np.ndarray]]:
        """Row indices per branch (host-side routing decision); None for
        a branch that takes every row."""
        n_branches, n = len(self.inner), batch.size
        logic = self.splitting_logic
        if isinstance(logic, SplitMask):
            col = self._routing_column(batch)
            if col.dtype.kind not in "iu":
                raise WindFlowError(
                    f"split mask {self._field!r} is of dtype {col.dtype}, "
                    "not an integer bitmask")
            if not col.size:
                return [np.zeros(0, np.int64)] * n_branches
            lo, hi = int(col.min()), int(col.max())
            if lo < 0 or hi >= 1 << n_branches:
                raise self._out_of_range("branch mask", lo, hi,
                                         1 << n_branches)
            some = int(np.bitwise_or.reduce(col))
            every = int(np.bitwise_and.reduce(col))
            return [None if every >> b & 1 else
                    np.nonzero(col & (1 << b))[0] if some >> b & 1 else
                    np.zeros(0, np.int64) for b in range(n_branches)]
        if isinstance(logic, str):
            col = self._routing_column(batch)
            if col.size and (col.min() < 0 or col.max() >= n_branches):
                raise self._out_of_range("branch index", int(col.min()),
                                         int(col.max()), n_branches)
            sel = [np.nonzero(col == b)[0] for b in range(n_branches)]
        else:
            rows: List[list] = [[] for _ in range(n_branches)]
            if self.stats is not None:
                self.stats.device_bytes_d2h += batch.nbytes()
            from ..runtime.emitters import check_branch_index
            for i, (payload, _ts) in enumerate(batch.to_rows()):
                s = logic(payload)
                if s is None:
                    continue
                if isinstance(s, int):
                    rows[check_branch_index(s, n_branches)].append(i)
                else:
                    for b in s:
                        rows[check_branch_index(b, n_branches)].append(i)
            sel = [np.asarray(ix, dtype=np.int64) for ix in rows]
        return [None if n and idx.size == n else idx for idx in sel]

    def _pipe_process(self, batch: BatchTPU) -> None:
        per_branch = self._branch_rows(batch)
        part = [b for b, idx in enumerate(per_branch)
                if idx is not None and idx.size]
        gathered = dict(zip(part, gather_sub_batches(
            batch, [per_branch[b] for b in part])))
        st = self.stats
        for b, idx in enumerate(per_branch):
            if idx is None:
                # every row selected this branch: no gather needed (device
                # arrays are immutable; copy only the metadata wrapper)
                sub = batch.copy_for_dest()
                if st is not None:
                    st.split_whole_batches += 1
            elif idx.size == 0:
                # nothing of this batch for the branch, but its watermark:
                # a stage that aligns on this branch (a join after a
                # merge) must not wait for the next generated punctuation
                self.inner[b].propagate_punctuation(batch.wm)
                continue
            else:
                sub = gathered[b]
                if st is not None:
                    st.split_gathered_batches += 1
            self.inner[b].emit_device_batch(sub)

    def emit_device_batch(self, batch: BatchTPU) -> None:
        if self._field is not None:
            async_host_copy(batch.fields[self._field])
        else:
            batch.prefetch_host()  # callable logic reads every column
        self._pipe_add(batch)

    def on_idle(self) -> bool:
        # drain our routing FIFO, then the branch emitters' own FIFOs
        # (a TPU->CPU branch nests a TPUExitEmitter the worker can't see)
        did = bool(self._pending)
        self._drain()
        for e in self.inner:
            f = getattr(e, "on_idle", None)
            if f is not None:
                did = bool(f()) or did
        return did

    def propagate_punctuation(self, wm: int) -> None:
        self._drain()
        for e in self.inner:
            e.propagate_punctuation(wm)

    def flush(self) -> None:
        self._drain()
        for e in self.inner:
            e.flush()

    def send_eos_all(self) -> None:
        self._drain()
        for e in self.inner:
            e.send_eos_all()

    def send_barrier_all(self, barrier) -> None:
        self._drain()
        for e in self.inner:
            e.send_barrier_all(barrier)

    def eos_ports(self):
        return [p for e in self.inner for p in e.eos_ports()]

    def emitter_state(self) -> dict:
        return {"inner": [e.emitter_state() for e in self.inner]}

    def restore_emitter_state(self, state: dict) -> None:
        for e, st in zip(self.inner, state.get("inner", [])):
            e.restore_emitter_state(st)


class TPUColumnarExitEmitter(BasicEmitter, _D2HPipeline):
    """TPU -> columnar CPU sink: the exit WITHOUT row boxing (the dual
    of ``push_columns``; the reference exit iterates pinned memory
    without materializing objects, ``wf/batch_gpu_t.hpp:154-179``).
    Whole device batches flow to the sink replica, which converts each
    column once (``np.asarray``) and calls the columnar functor once per
    batch. D2H rides the same async-copy pipeline as the row exit."""

    def __init__(self, num_dests: int,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 depth: Optional[int] = None) -> None:
        super().__init__(num_dests, 0, execution_mode)
        self._pipe_init("WF_EXIT_PIPELINE_DEPTH", 4, depth)
        self._rr = 0

    def set_stats(self, stats) -> None:
        super().set_stats(stats)
        self._pipe_bind(stats)

    def emit_device_batch(self, batch: BatchTPU) -> None:
        batch.prefetch_host()
        self._pipe_add(batch)

    def _pipe_process(self, batch: BatchTPU) -> None:
        if self.stats is not None:
            self.stats.device_bytes_d2h += batch.nbytes()
        self._send_batch(self._rr, batch)
        self._rr = (self._rr + 1) % self.num_dests

    def flush(self) -> None:
        # propagate_punctuation/send_eos_all call flush() first, so
        # draining here keeps batches ordered ahead of every marker
        self._drain()
        super().flush()


class TPUExitEmitter(BasicEmitter, _D2HPipeline):
    """TPU->CPU: D2H the batch, then route rows through a wrapped CPU
    emitter (which owns the real ports and batching policy).

    The D2H is PIPELINED (_D2HPipeline): an arriving batch starts async
    host copies of its columns and enters the FIFO; rows materialize only
    when a later batch pushes it out, a punctuation/flush/EOS drains it,
    or the worker's idle tick (WF_IDLE_DRAIN_MS, default 50 ms) fires on
    a quiet stream. Ordering and watermark monotonicity hold; the delay
    bound is the idle tick on a quiet stream, and on a busy stream with
    sparse output batches one watermark-punctuation interval
    (DEFAULT_WM_INTERVAL_USEC) — set WF_EXIT_PIPELINE_DEPTH=0 for
    latency-sensitive exits. The reference
    gets the same overlap from ``prefetch2CPU`` on the batch's CUDA
    stream ahead of the host read (``batch_gpu_t.hpp:154-165``)."""

    def __init__(self, inner: BasicEmitter, depth: Optional[int] = None) -> None:
        super().__init__(inner.num_dests, inner.output_batch_size,
                         inner.execution_mode)
        self.inner = inner
        self._pipe_init("WF_EXIT_PIPELINE_DEPTH", 4, depth)

    def set_ports(self, ports) -> None:
        self.inner.set_ports(ports)
        self.ports = self.inner.ports

    def set_stats(self, stats) -> None:
        self.stats = stats
        self.inner.stats = stats
        self._pipe_bind(stats)

    def _pipe_process(self, batch: BatchTPU) -> None:
        if self.stats is not None:
            self.stats.device_bytes_d2h += batch.nbytes()
        if batch.trace_min:
            # one traced row re-materializes per traced batch: the inner
            # emitter consumes the stamp on its first emit
            self.inner.trace_ts = batch.trace_min
        for payload, ts in batch.to_rows():
            self.inner.emit(payload, ts, batch.wm)
        self.inner.trace_ts = 0

    def emit_device_batch(self, batch: BatchTPU) -> None:
        batch.prefetch_host()
        self._pipe_add(batch)

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        self._drain()  # single-row emits must not overtake queued batches
        self.inner.emit(payload, ts, wm, msg_id)

    def propagate_punctuation(self, wm: int) -> None:
        self._drain()  # rows behind the punctuation carry older watermarks
        self.inner.propagate_punctuation(wm)

    def flush(self) -> None:
        self._drain()
        self.inner.flush()

    def send_eos_all(self) -> None:
        self._drain()
        self.inner.send_eos_all()

    def send_barrier_all(self, barrier) -> None:
        self._drain()
        self.inner.send_barrier_all(barrier)

    def eos_ports(self):
        return self.inner.eos_ports()

    def emitter_state(self) -> dict:
        return self.inner.emitter_state()

    def restore_emitter_state(self, state: dict) -> None:
        self.inner.restore_emitter_state(state)
