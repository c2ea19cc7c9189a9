"""Emitters: the routing plane on the producer side.

Parity notes:
- Protocol mirrors ``wf/basic_emitter.hpp:49-121`` (emit, propagate
  punctuation, flush, clone-per-replica); the reference's function-pointer
  ``doEmit`` devirtualization is unnecessary in Python — the analogous
  optimization here is micro-batching, which amortizes per-message costs and
  is also what feeds the device plane.
- Forward/round-robin: ``wf/forward_emitter.hpp``; KeyBy hash routing with
  watermark punctuation generation: ``wf/keyby_emitter.hpp:210-259,305-376``;
  Broadcast multicast: ``wf/broadcast_emitter.hpp``; Splitting tree emitter:
  ``wf/splitting_emitter.hpp:48-341``.
- Watermark-punctuation cadence: every ``DEFAULT_WM_AMOUNT`` emitted tuples
  the emitter checks whether ``DEFAULT_WM_INTERVAL_USEC`` elapsed and, if so,
  flushes partial batches and sends a punctuation carrying the producer's
  current watermark so idle destinations keep making event-time progress
  (``wf/basic.hpp:199-216``).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

from ..basic import (DEFAULT_WM_AMOUNT, DEFAULT_WM_INTERVAL_USEC,
                     ExecutionMode, RoutingMode, current_time_usecs)
from ..message import Batch, Single, make_punctuation
from .channel import Port


class BasicEmitter:
    """Base: owns destination ports, optional micro-batching, per-destination
    id counters (DETERMINISTIC ordering), punctuation cadence."""

    mode: RoutingMode = RoutingMode.NONE

    def __init__(self, num_dests: int, output_batch_size: int = 0,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 punct_generation: bool = True) -> None:
        self.num_dests = num_dests
        self.output_batch_size = output_batch_size
        self.execution_mode = execution_mode
        self.punct_generation = punct_generation  # off for inline chain edges
        self.ports: List[Port] = []  # wired by the topology layer
        self._next_ids = [0] * num_dests
        self._emit_count = 0
        self._last_punct_usec = current_time_usecs()
        self.stats = None  # optional StatsRecord of the owning replica
        # transient latency-tracing origin stamp: the owning replica (or
        # source shipper) sets it just before emit; the first message
        # created while it is non-zero carries it and clears it
        # (monitoring/tracing.py — 0 means "current tuple untraced")
        self.trace_ts = 0

    # -- wiring ------------------------------------------------------------
    def set_stats(self, stats) -> None:
        self.stats = stats

    def set_ports(self, ports: Sequence[Port]) -> None:
        assert len(ports) == self.num_dests, (len(ports), self.num_dests)
        self.ports = list(ports)

    # -- core send helpers -------------------------------------------------
    def _send_single(self, dest: int, payload: Any, ts: int, wm: int,
                     msg_id: Optional[int] = None) -> None:
        """``msg_id`` overrides the per-destination counter: window replicas
        stamp result/pane identifiers consumed by downstream ID-sequencing
        collectors (reference ``doEmit`` identifier argument)."""
        msg = Single(payload,
                     self._next_ids[dest] if msg_id is None else msg_id,
                     ts, wm)
        if self.trace_ts:
            msg.trace_ts = self.trace_ts
            self.trace_ts = 0
        self._next_ids[dest] += 1
        if self.stats is not None:
            self.stats.outputs_sent += 1
        self.ports[dest].send(msg)

    def _send_batch(self, dest: int, batch: Batch) -> None:
        batch.id = self._next_ids[dest]
        self._next_ids[dest] += 1
        if self.stats is not None:
            self.stats.outputs_sent += batch.size
        self.ports[dest].send(batch)

    def _send_punct(self, dest: int, wm: int) -> None:
        p = make_punctuation(wm)
        p.id = self._next_ids[dest]
        self._next_ids[dest] += 1
        if self.stats is not None:
            self.stats.punct_sent += 1
        self.ports[dest].send(p)

    # -- punctuation cadence (generate_punctuation, keyby_emitter.hpp:305) --
    def _maybe_generate_punctuation(self, wm: int) -> None:
        if not self.punct_generation or self.execution_mode is not ExecutionMode.DEFAULT:
            return
        self._emit_count += 1
        if self._emit_count % DEFAULT_WM_AMOUNT != 0:
            return
        now = current_time_usecs()
        if now - self._last_punct_usec < DEFAULT_WM_INTERVAL_USEC:
            return
        self._last_punct_usec = now
        self.propagate_punctuation(wm)

    # -- public API --------------------------------------------------------
    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        raise NotImplementedError

    def emit_columns(self, cols, ts_arr, wm: int, trace_rows=None) -> None:
        """Columnar push (SourceShipper.push_columns). Generic emitters
        materialize dict rows; the device staging emitter overrides this
        with a vectorized path that never touches individual tuples.
        ``trace_rows`` (optional int indices into the block) marks the
        traced cohort: each marked row re-arms ``trace_ts`` so sampling
        matches the row path exactly."""
        names = list(cols)
        pulled = [cols[n] for n in names]
        t0 = self.trace_ts
        marks = None
        nxt = -1
        if t0 and trace_rows is not None and len(trace_rows):
            self.trace_ts = 0
            marks = iter(trace_rows)
            nxt = int(next(marks, -1))
        for i in range(len(ts_arr)):
            if i == nxt:
                self.trace_ts = t0
                nxt = int(next(marks, -1))
            self.emit({n: p[i].item() for n, p in zip(names, pulled)},
                      int(ts_arr[i]), wm)

    def propagate_punctuation(self, wm: int) -> None:
        """Flush partial batches then punctuate every destination; flushing
        first preserves per-channel watermark monotonicity."""
        self.flush()
        for d in range(self.num_dests):
            self._send_punct(d, wm)

    def flush(self) -> None:
        """Send any partially-filled output batches (EOS / punctuation)."""

    def send_eos_all(self) -> None:
        self.flush()
        for port in self.ports:
            port.send_eos()

    def send_barrier_all(self, barrier) -> None:
        """Checkpoint-barrier propagation: flush partial batches FIRST so
        every already-emitted tuple stays pre-barrier on its channel, then
        send the barrier on every edge (one per port, like EOS — never
        batched, never reordered)."""
        self.flush()
        for port in self.ports:
            port.send(barrier.copy_for_dest())

    def eos_ports(self) -> Sequence[Port]:
        """All queue ports (for emergency EOS propagation on worker error)."""
        return self.ports

    # -- checkpointing: routing counters travel with the replica blob ------
    # (per-destination ids keep DETERMINISTIC-mode collectors' monotonic-id
    # contract across a restore; the round-robin cursor keeps FORWARD
    # placement deterministic)
    def emitter_state(self) -> dict:
        st = {"next_ids": list(self._next_ids),
              "emit_count": self._emit_count}
        rr = getattr(self, "_rr", None)
        if rr is not None:
            st["rr"] = rr
        return st

    def restore_emitter_state(self, state: dict) -> None:
        ids = state.get("next_ids")
        if ids is not None and len(ids) == len(self._next_ids):
            self._next_ids = list(ids)
        self._emit_count = state.get("emit_count", 0)
        if "rr" in state and hasattr(self, "_rr"):
            self._rr = state["rr"]


class ForwardEmitter(BasicEmitter):
    """FORWARD / REBALANCING: round-robin across destinations; with batching,
    fills one batch at a time and round-robins full batches
    (``wf/forward_emitter.hpp``)."""

    mode = RoutingMode.FORWARD

    def __init__(self, num_dests: int, output_batch_size: int = 0,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT) -> None:
        super().__init__(num_dests, output_batch_size, execution_mode)
        self._rr = 0
        self._batch: Optional[Batch] = None

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        if self.output_batch_size <= 0:
            self._send_single(self._rr, payload, ts, wm, msg_id)
            self._rr = (self._rr + 1) % self.num_dests
        else:
            if self._batch is None:
                self._batch = Batch()
            self._batch.add_tuple(payload, ts, wm)
            if self.trace_ts:
                self._batch.note_trace(self.trace_ts)
                self.trace_ts = 0
            if self._batch.size >= self.output_batch_size:
                self._send_batch(self._rr, self._batch)
                self._rr = (self._rr + 1) % self.num_dests
                self._batch = None
        self._maybe_generate_punctuation(wm)

    def flush(self) -> None:
        if self._batch is not None and self._batch.size > 0:
            self._send_batch(self._rr, self._batch)
            self._rr = (self._rr + 1) % self.num_dests
            self._batch = None


class KeyByEmitter(BasicEmitter):
    """KEYBY: ``dest = hash(key(payload)) % num_dests`` with per-destination
    output batches (``wf/keyby_emitter.hpp:210-259``)."""

    mode = RoutingMode.KEYBY

    def __init__(self, key_extractor: Callable[[Any], Any], num_dests: int,
                 output_batch_size: int = 0,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT) -> None:
        super().__init__(num_dests, output_batch_size, execution_mode)
        self.key_extractor = key_extractor
        self._batches: List[Optional[Batch]] = [None] * num_dests

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        dest = hash(self.key_extractor(payload)) % self.num_dests
        if self.output_batch_size <= 0:
            self._send_single(dest, payload, ts, wm, msg_id)
        else:
            b = self._batches[dest]
            if b is None:
                b = self._batches[dest] = Batch()
            b.add_tuple(payload, ts, wm)
            if self.trace_ts:
                b.note_trace(self.trace_ts)
                self.trace_ts = 0
            if b.size >= self.output_batch_size:
                self._send_batch(dest, b)
                self._batches[dest] = None
        self._maybe_generate_punctuation(wm)

    def flush(self) -> None:
        for d, b in enumerate(self._batches):
            if b is not None and b.size > 0:
                self._send_batch(d, b)
                self._batches[d] = None


class BroadcastEmitter(BasicEmitter):
    """BROADCAST: every destination receives a copy
    (``wf/broadcast_emitter.hpp``; the reference shares one refcounted message,
    we copy — payload objects are shared, so broadcast-fed in-place operators
    must copy-on-write, ``wf/map.hpp:348``)."""

    mode = RoutingMode.BROADCAST

    def __init__(self, num_dests: int, output_batch_size: int = 0,
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT) -> None:
        super().__init__(num_dests, output_batch_size, execution_mode)
        self._batch: Optional[Batch] = None

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        if self.output_batch_size <= 0:
            for d in range(self.num_dests):
                self._send_single(d, payload, ts, wm, msg_id)
        else:
            if self._batch is None:
                self._batch = Batch()
            self._batch.add_tuple(payload, ts, wm)
            if self.trace_ts:
                self._batch.note_trace(self.trace_ts)
                self.trace_ts = 0
            if self._batch.size >= self.output_batch_size:
                self._broadcast_batch(self._batch)
                self._batch = None
        self._maybe_generate_punctuation(wm)

    def _broadcast_batch(self, batch: Batch) -> None:
        for d in range(self.num_dests):
            self._send_batch(d, batch.copy_for_dest() if d > 0 else batch)

    def flush(self) -> None:
        if self._batch is not None and self._batch.size > 0:
            self._broadcast_batch(self._batch)
            self._batch = None


class SplitMask:
    """The multicast form of a split's logic (``MultiPipe.split(field, n,
    mask=True)``): ``field`` holds a bitmask of branches a row, bit ``b``
    set sending the row to branch ``b`` and 0 dropping it, the columnar
    sibling of a splitting function that returns a vector of indices."""

    __slots__ = ("field",)

    def __init__(self, field: str) -> None:
        self.field = field

    def branches(self, t) -> list:
        """The branch indices of one tuple (the host plane's form)."""
        m = int(t[self.field] if isinstance(t, dict)
                else getattr(t, self.field))
        if m < 0:
            from ..basic import WindFlowError
            raise WindFlowError(f"split mask {self.field!r} holds {m} < 0")
        return [b for b in range(m.bit_length()) if m >> b & 1]


def check_branch_index(s: int, n_branches: int) -> int:
    """Shared split-branch validation (CPU and device planes)."""
    if not 0 <= s < n_branches:
        from ..basic import WindFlowError
        raise WindFlowError(
            f"splitting logic returned branch index {s} outside "
            f"[0, {n_branches})")
    return s


class SplittingEmitter(BasicEmitter):
    """Tree emitter for MultiPipe::split: user logic selects branch index(es);
    one inner emitter per branch (``wf/splitting_emitter.hpp:48-341``)."""

    mode = RoutingMode.NONE

    def __init__(self, splitting_logic: Callable[[Any], Any],
                 inner_emitters: List[BasicEmitter],
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT) -> None:
        super().__init__(sum(e.num_dests for e in inner_emitters), 0, execution_mode)
        self.splitting_logic = splitting_logic
        self.inner = inner_emitters

    def set_ports(self, ports: Sequence[Port]) -> None:
        # ports are laid out branch-by-branch in order
        self.ports = list(ports)
        off = 0
        for e in self.inner:
            e.set_ports(ports[off:off + e.num_dests])
            off += e.num_dests

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:
        sel = self.splitting_logic(payload)
        if sel is None:
            self.trace_ts = 0
            return
        t0 = self.trace_ts
        if t0:
            self.trace_ts = 0
        n = len(self.inner)
        if isinstance(sel, int):
            inner = self.inner[check_branch_index(sel, n)]
            inner.trace_ts = t0
            inner.emit(payload, ts, wm, msg_id)
        else:
            for s in sel:
                inner = self.inner[check_branch_index(s, n)]
                inner.trace_ts = t0
                inner.emit(payload, ts, wm, msg_id)

    def propagate_punctuation(self, wm: int) -> None:
        for e in self.inner:
            e.propagate_punctuation(wm)

    def flush(self) -> None:
        for e in self.inner:
            e.flush()

    def send_eos_all(self) -> None:
        for e in self.inner:
            e.send_eos_all()

    def send_barrier_all(self, barrier) -> None:
        for e in self.inner:
            e.send_barrier_all(barrier)

    def eos_ports(self):
        return [p for e in self.inner for p in e.eos_ports()]

    def emitter_state(self) -> dict:
        return {"inner": [e.emitter_state() for e in self.inner]}

    def restore_emitter_state(self, state: dict) -> None:
        inner = state.get("inner", [])
        for e, st in zip(self.inner, inner):
            e.restore_emitter_state(st)


class NullEmitter(BasicEmitter):
    """Terminal operators (Sink) have no output."""

    def __init__(self) -> None:
        super().__init__(0, 0)

    def emit(self, payload: Any, ts: int, wm: int,
             msg_id: Optional[int] = None) -> None:  # pragma: no cover
        raise RuntimeError("Sink cannot emit")

    def propagate_punctuation(self, wm: int) -> None:
        pass

    def send_eos_all(self) -> None:
        pass
