"""Bounded channels and ports connecting replicas.

This is the FastFlow replacement (reference L0). WindFlow replicas are
FastFlow nodes joined by lock-free SPSC queues with pinned threads
(``SURVEY.md`` L0); here every consumer worker owns one bounded MPSC
``Channel`` that merges all of its input edges (like ``ff_minode``), and each
producer edge is a ``QueuePort`` stamping the consumer-side channel index
(``ff::ff_minode::get_channel_id`` equivalent). Chained (fused) stages talk
through ``InlinePort`` — a plain function call, the analog of FastFlow's
``combine_with_laststage`` thread fusion (``wf/multipipe.hpp:576-582``).

A native C++ SPSC ring (windflow_tpu/native) can replace the stdlib deque
backing transparently; the Python fallback keeps zero hard dependencies.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, List, Optional, Tuple

from ..basic import DEFAULT_BUFFER_CAPACITY, SupervisorTeardown
from ..message import EOS_SENTINEL
from ..monitoring.tracing import StageCounters


def _teardown() -> SupervisorTeardown:
    return SupervisorTeardown(
        "channel closed: the supervisor is rebuilding the runtime plane")


class Channel:
    """Bounded blocking MPSC queue of ``(channel_idx, msg)`` pairs.

    Bounded => backpressure, like FastFlow's FF_BOUNDED_BUFFER mode.
    """

    __slots__ = ("_q", "_lock", "_not_empty", "_not_full", "capacity",
                 "n_inputs", "depth_max", "_st_put", "_st_get",
                 "closed")

    def __init__(self, capacity: int = DEFAULT_BUFFER_CAPACITY) -> None:
        self._q: deque = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self.capacity = capacity
        self.n_inputs = 0  # number of producer edges; assigned at wiring
        # supervised teardown (windflow_tpu.supervision): close() poisons
        # the channel — every blocked and future put/get raises
        # SupervisorTeardown so the whole plane unwinds without an EOS
        # cascade. One bool check on paths that already hold the lock.
        self.closed = False
        # backpressure / occupancy instrumentation (monitoring plane):
        # producers blocked on a full queue (this stage IS the bottleneck)
        # vs the consumer blocked on an empty one (it is starved): the
        # ``wait:put`` / ``wait:get`` stages, entered only on the blocked
        # paths — the uncontended hot path pays one compare for the
        # high-water mark. Counted on the consumer's StatsRecord once the
        # graph wires it (``bind_stats``), privately until then.
        self.depth_max = 0
        self.bind_stats(StageCounters())

    def bind_stats(self, stats: StageCounters) -> None:
        """Count this channel's waits on ``stats`` (the consuming
        replica's record: ``Queue_blocked_put/get_usec``) and name their
        spans after it."""
        self._st_put = stats.stage("put")
        self._st_get = stats.stage("get")

    @property
    def puts_blocked(self) -> int:
        return self._st_put.count

    @property
    def blocked_put_ns(self) -> int:
        return self._st_put.total_ns

    @property
    def blocked_get_ns(self) -> int:
        return self._st_get.total_ns

    def register_input(self) -> int:
        """Returns the channel index assigned to a new producer edge."""
        idx = self.n_inputs
        self.n_inputs += 1
        return idx

    def put(self, ch_idx: int, msg: Any) -> None:
        with self._not_full:
            if self.closed:
                raise _teardown()
            if len(self._q) >= self.capacity:
                with self._st_put():
                    while len(self._q) >= self.capacity:
                        self._not_full.wait()
                        if self.closed:
                            raise _teardown()
            self._q.append((ch_idx, msg))
            if len(self._q) > self.depth_max:
                self.depth_max = len(self._q)
            self._not_empty.notify()

    def get(self, timeout: Optional[float] = None) -> Optional[Tuple[int, Any]]:
        """Blocking pop; with ``timeout`` (seconds) returns None if the
        channel stays empty that long (the worker's idle tick). The timeout
        is a single deadline: spurious wakeups / raced notifies do not
        restart it, so the idle tick is never delayed past ``timeout``."""
        if timeout is None:
            with self._not_empty:
                if not self._q:
                    if self.closed:
                        raise _teardown()
                    with self._st_get():
                        while not self._q:
                            self._not_empty.wait()
                            if self.closed and not self._q:
                                raise _teardown()
                item = self._q.popleft()
                self._not_full.notify()
                return item
        deadline = time.monotonic() + timeout
        with self._not_empty:
            if not self._q:
                if self.closed:
                    raise _teardown()
                with self._st_get() as span:
                    while not self._q:
                        if self.closed:
                            raise _teardown()
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            # counted, but kept out of the ring: idle
                            # ticks would flood it on a quiet stream
                            span.silent = True
                            return None
                        self._not_empty.wait(remaining)
            item = self._q.popleft()
            self._not_full.notify()
            return item

    def get_nowait(self) -> Optional[Tuple[int, Any]]:
        with self._lock:
            if not self._q:
                return None
            item = self._q.popleft()
            self._not_full.notify()
            return item

    def close(self) -> None:
        """Poison the channel (supervised teardown): every blocked and
        future put/get raises ``SupervisorTeardown``. Buffered messages
        still drain through ``get`` — only an EMPTY closed channel
        raises on the consumer side, so a worker unwinds at a message
        boundary, never mid-prefix. Idempotent."""
        with self._lock:
            self.closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def __len__(self) -> int:
        with self._lock:
            return len(self._q)


class Port:
    """Destination of an emitter edge."""

    __slots__ = ()

    def send(self, msg: Any) -> None:
        raise NotImplementedError

    def send_eos(self) -> None:
        raise NotImplementedError


class QueuePort(Port):
    """Edge to a replica running in another thread."""

    __slots__ = ("channel", "ch_idx")

    def __init__(self, channel: Channel) -> None:
        self.channel = channel
        self.ch_idx = channel.register_input()

    def send(self, msg: Any) -> None:
        self.channel.put(self.ch_idx, msg)

    def send_eos(self) -> None:
        self.channel.put(self.ch_idx, EOS_SENTINEL)


class InlinePort(Port):
    """Edge to a replica fused in the same thread (chaining). ``send`` is a
    synchronous call into the downstream replica's message handler."""

    __slots__ = ("node",)

    def __init__(self, node: Any) -> None:
        self.node = node  # object with handle_msg(ch, msg); single channel 0

    def send(self, msg: Any) -> None:
        self.node.handle_msg(0, msg)

    def send_eos(self) -> None:
        # EOS through a chain is driven by the worker's termination cascade
        # (Worker.run), not by in-band sentinels.
        pass
