"""Map / Filter / FlatMap / Reduce / Sink — the stateless/keyed CPU operators.

Parity (all per-tuple semantics, functor variants by arity):
- Map: ``wf/map.hpp:57-385``. A functor returning ``None`` is treated as
  in-place (mutated payload re-emitted); returning a value emits that value.
  ``copy_on_write`` shields broadcast-shared payloads (``wf/map.hpp:348``).
- Filter: ``wf/filter.hpp`` — predicate; dropped tuples counted.
- FlatMap: ``wf/flatmap.hpp`` + ``wf/shipper.hpp:58-182`` — user pushes 0..N
  results through a Shipper bound to the current (ts, wm).
- Reduce: ``wf/reduce.hpp:57-334`` — keyed running state (KEYBY mandatory);
  the updated state is copied and emitted after every update.
- Sink: ``wf/sink.hpp`` — consumes tuples; receives ``None`` once at EOS.
"""

from __future__ import annotations

import copy
from typing import Any, Callable, Optional

from ..basic import OpType, RoutingMode, WindFlowError
from .base import BasicOperator, BasicReplica, arity


# --------------------------------------------------------------------------
# Map
# --------------------------------------------------------------------------
class Map(BasicOperator):
    def __init__(self, func: Callable, name: str = "map", parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor: Optional[Callable] = None,
                 output_batch_size: int = 0) -> None:
        super().__init__(name, parallelism, input_routing, key_extractor,
                         output_batch_size)
        self.func = func
        self._riched = arity(func) >= 2

    def build_replicas(self) -> None:
        self.replicas = [MapReplica(self, i) for i in range(self.parallelism)]


class MapReplica(BasicReplica):
    def process(self, payload, ts, wm, tag):
        if self.copy_on_write:
            payload = copy.copy(payload)
        out = (self.op.func(payload, self.context) if self.op._riched
               else self.op.func(payload))
        if out is None:  # in-place variant
            out = payload
        self.emitter.emit(out, ts, wm)


# --------------------------------------------------------------------------
# Filter
# --------------------------------------------------------------------------
class Filter(BasicOperator):
    def __init__(self, predicate: Callable, name: str = "filter",
                 parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor: Optional[Callable] = None,
                 output_batch_size: int = 0) -> None:
        super().__init__(name, parallelism, input_routing, key_extractor,
                         output_batch_size)
        self.predicate = predicate
        self._riched = arity(predicate) >= 2

    def build_replicas(self) -> None:
        self.replicas = [FilterReplica(self, i) for i in range(self.parallelism)]


class FilterReplica(BasicReplica):
    def process(self, payload, ts, wm, tag):
        keep = (self.op.predicate(payload, self.context) if self.op._riched
                else self.op.predicate(payload))
        if keep:
            self.emitter.emit(payload, ts, wm)
        else:
            self.stats.inputs_ignored += 1


# --------------------------------------------------------------------------
# FlatMap
# --------------------------------------------------------------------------
class Shipper:
    """Bound to the in-flight tuple's (ts, wm); user pushes 0..N outputs."""

    __slots__ = ("_replica", "_ts", "_wm")

    def __init__(self, replica: "FlatMapReplica") -> None:
        self._replica = replica
        self._ts = 0
        self._wm = 0

    def push(self, payload: Any) -> None:
        self._replica.emitter.emit(payload, self._ts, self._wm)


class FlatMap(BasicOperator):
    def __init__(self, func: Callable, name: str = "flatmap",
                 parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor: Optional[Callable] = None,
                 output_batch_size: int = 0) -> None:
        super().__init__(name, parallelism, input_routing, key_extractor,
                         output_batch_size)
        self.func = func
        self._riched = arity(func) >= 3

    def build_replicas(self) -> None:
        self.replicas = [FlatMapReplica(self, i) for i in range(self.parallelism)]


class FlatMapReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        self.shipper = Shipper(self)

    def process(self, payload, ts, wm, tag):
        self.shipper._ts = ts
        self.shipper._wm = wm
        if self.op._riched:
            self.op.func(payload, self.shipper, self.context)
        else:
            self.op.func(payload, self.shipper)


# --------------------------------------------------------------------------
# Reduce
# --------------------------------------------------------------------------
class Reduce(BasicOperator):
    """``func(tuple, state) -> state`` (or mutate state and return None);
    requires KEYBY routing; not chainable (``wf/multipipe.hpp:1058-1060``)."""

    def __init__(self, func: Callable, key_extractor: Callable,
                 initial_state: Any = None, name: str = "reduce",
                 parallelism: int = 1, output_batch_size: int = 0) -> None:
        if key_extractor is None:
            raise WindFlowError("Reduce requires a key extractor (KEYBY)")
        super().__init__(name, parallelism, RoutingMode.KEYBY, key_extractor,
                         output_batch_size)
        self.func = func
        self.initial_state = initial_state
        self._riched = arity(func) >= 3

    @property
    def is_chainable(self) -> bool:
        return False

    def build_replicas(self) -> None:
        self.replicas = [ReduceReplica(self, i) for i in range(self.parallelism)]


class ReduceReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        self.key_state = {}

    def process(self, payload, ts, wm, tag):
        key = self.op.key_extractor(payload)
        state = self.key_state.get(key)
        if state is None:
            state = copy.deepcopy(self.op.initial_state)
        out = (self.op.func(payload, state, self.context) if self.op._riched
               else self.op.func(payload, state))
        if out is not None:
            state = out
        self.key_state[key] = state
        self.emitter.emit(copy.copy(state), ts, wm)

    # -- checkpointing -------------------------------------------------------
    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st["key_state"] = self.key_state
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        self.key_state = dict(state.get("key_state", {}))


# --------------------------------------------------------------------------
# Sink
# --------------------------------------------------------------------------
class Sink(BasicOperator):
    op_type = OpType.SINK
    # exactly-once mode (windflow_tpu.sinks.transactional): output
    # buffers per checkpoint epoch, pre-commits at the barrier as a
    # staged segment file and becomes visible (tmp+atomic-rename) only
    # when the coordinator finalizes the epoch
    supports_exactly_once = True

    def __init__(self, func: Callable, name: str = "sink", parallelism: int = 1,
                 input_routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor: Optional[Callable] = None,
                 accepts_columns: bool = False) -> None:
        super().__init__(name, parallelism, input_routing, key_extractor, 0)
        self.func = func
        # columnar consumer (the exit-side dual of push_columns): the
        # functor takes whole COLUMN batches, ``func(cols, ts)`` with
        # cols a dict of host numpy arrays — device-plane exits then
        # skip per-row boxing entirely (the reference exit iterates
        # pinned memory without materializing objects,
        # ``wf/batch_gpu_t.hpp:154-179``)
        self.accepts_columns = accepts_columns
        self._riched = arity(func) >= (3 if accepts_columns else 2)
        self.exactly_once = False
        self.txn_dir: Optional[str] = None

    def build_replicas(self) -> None:
        if self.exactly_once:
            cls = (TxnColumnarSinkReplica if self.accepts_columns
                   else TxnSinkReplica)
        else:
            cls = ColumnarSinkReplica if self.accepts_columns else SinkReplica
        self.replicas = [cls(self, i) for i in range(self.parallelism)]


class SinkReplica(BasicReplica):
    def __init__(self, op, idx):
        super().__init__(op, idx)
        # sinks record end-to-end latency of traced tuples (None when
        # sampling is off — the generic handle_msg hook stays dormant)
        self._e2e = self.stats.hist_e2e

    def process(self, payload, ts, wm, tag):
        if self.op._riched:
            self.op.func(payload, self.context)
        else:
            self.op.func(payload)

    def flush_on_termination(self) -> None:
        # EOS marker: reference passes an empty optional (wf/sink.hpp)
        if self.op._riched:
            self.op.func(None, self.context)
        else:
            self.op.func(None)


class ColumnarSinkReplica(BasicReplica):
    """Consumes whole device batches as host COLUMN dicts — one functor
    call per batch, no per-row Python objects on the exit path."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        self._e2e = self.stats.hist_e2e
        self._st_d2h = self.stats.stage("d2h")
        self._st_sink = self.stats.stage("sink")

    def handle_msg(self, ch: int, msg: Any) -> None:
        self.stats.start_svc()
        n = 1
        if msg.is_punct:
            self.stats.punct_received += 1
            self._advance_wm(msg.wm)
            self.on_punctuation(msg.wm)
        else:
            from ..tpu.batch import BatchTPU, host_columns
            if not isinstance(msg, BatchTPU):
                raise WindFlowError(
                    f"{self.op.name}: with_columns sink received a row "
                    f"message ({type(msg).__name__}); columnar sinks "
                    "consume device batches — drop with_columns or move "
                    "the producer to the device plane")
            import numpy as np
            n = msg.size
            self.stats.inputs_received += n
            self._advance_wm(msg.wm)
            if self.stats.sample_every:  # per batch, not per tuple
                self.stats._svc_rec = True
            if self._e2e is not None and msg.trace_min:
                from ..basic import current_time_usecs
                now = current_time_usecs()
                self._e2e.record(now - msg.trace_max)
                if msg.trace_max != msg.trace_min:
                    self._e2e.record(now - msg.trace_min)
            # the host read of each column (waits for its D2H), then the
            # user's functor
            with self._st_d2h(msg.bid, msg.cause):
                cols = {name: col[:n]
                        for name, col in host_columns(msg.fields).items()}
            ts = msg.ts_host[:n]
            self.context._set_meta(int(ts[-1]) if n else 0, self.cur_wm)
            with self._st_sink(msg.bid, msg.cause):
                self._consume(cols, ts)
        self.stats.end_svc(n)

    def _consume(self, cols, ts) -> None:
        """One host column batch -> the user functor (the exactly-once
        subclass buffers it into the current epoch instead)."""
        if self.op._riched:
            self.op.func(cols, ts, self.context)
        else:
            self.op.func(cols, ts)

    def flush_on_termination(self) -> None:
        if self.op._riched:
            self.op.func(None, None, self.context)
        else:
            self.op.func(None, None)


# --------------------------------------------------------------------------
# Exactly-once sinks (windflow_tpu.sinks.transactional): two-phase commit
# driven by the checkpoint coordinator. Separate subclasses so the default
# at-least-once hot path is byte-identical to before — the exactly-once
# machinery costs nothing unless with_exactly_once() selected it.
# --------------------------------------------------------------------------
class _TxnSinkMixin:
    """Chain-node hooks shared by the row and columnar transactional
    sinks; the 2PC state machine lives in ``EpochTxnDriver``."""

    def _init_txn(self) -> None:
        from ..sinks.transactional import (EpochTxnDriver, SegmentBackend,
                                           txn_dir_for)
        self.txn_root = txn_dir_for(self.op.name, self.idx, self.op.txn_dir)
        self._txn = EpochTxnDriver(SegmentBackend(self.txn_root), self.stats,
                                   deliver=self._deliver)
        # instance attribute so the worker's idle tick drives commits
        # (plain sinks have no on_idle and stay off the idle-tick path)
        self.on_idle = self._txn.poll

    # -- worker / coordinator hooks (runtime/worker.py) --------------------
    def bind_txn_coordinator(self, coordinator) -> None:
        self._txn.bind(coordinator)

    def precommit_epoch(self, ckpt_id: int) -> None:
        self._txn.precommit_epoch(ckpt_id)

    def handle_msg(self, ch: int, msg: Any) -> None:
        # commit finalized epochs from our OWN thread before the next
        # message (the finalize listener only flips a watermark); the
        # fast path inside poll() is one int compare per message
        t = self._txn
        if t._pending and min(t._pending) <= t._commit_ready:
            t.poll()
        super().handle_msg(ch, msg)

    def flush_on_termination(self) -> None:
        # EOS in exactly-once mode: commit what is finalized, stage the
        # post-barrier tail as one last pending epoch. Functor delivery
        # of still-pending epochs (and the EOS None marker) happens in
        # txn_complete once the whole graph finished cleanly.
        self._txn.seal_tail()

    def txn_complete(self) -> None:
        """Called by ``PipeGraph.wait_end`` on a clean finish (worker
        joined, no errors): commit every remaining epoch in order, then
        hand the functor its EOS marker."""
        self._txn.complete_all()
        self._eos_marker()

    # -- checkpoint snapshot / restore -------------------------------------
    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st.update(self._txn.snapshot())
        return st

    def restore_state(self, state: dict) -> None:
        super().restore_state(state)
        self._txn.restore(state)


class TxnSinkReplica(_TxnSinkMixin, SinkReplica):
    """Row sink in exactly-once mode: tuples buffer per epoch; the
    committed ``epoch_*.seg`` files under ``txn_root`` are the durable
    output stream, and the functor sees each record exactly once, at
    commit time (epoch order)."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        self._init_txn()

    def process(self, payload, ts, wm, tag):
        self._txn.buffer.append((payload, ts))

    def _deliver(self, records) -> None:
        for payload, ts in records:
            self.context._set_meta(ts, self.cur_wm)
            if self.op._riched:
                self.op.func(payload, self.context)
            else:
                self.op.func(payload)

    def _eos_marker(self) -> None:
        SinkReplica.flush_on_termination(self)


class TxnColumnarSinkReplica(_TxnSinkMixin, ColumnarSinkReplica):
    """Columnar sink in exactly-once mode: whole host column batches
    buffer per epoch (the arrays are already host copies at this point),
    one functor call per batch at commit time."""

    def __init__(self, op, idx):
        super().__init__(op, idx)
        self._init_txn()

    def _consume(self, cols, ts) -> None:
        self._txn.buffer.append((cols, ts))

    def _deliver(self, records) -> None:
        for cols, ts in records:
            if self.op._riched:
                self.op.func(cols, ts, self.context)
            else:
                self.op.func(cols, ts)

    def _eos_marker(self) -> None:
        ColumnarSinkReplica.flush_on_termination(self)
