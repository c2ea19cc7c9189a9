"""Message/buffer recycling.

Parity: ``wf/recycling.hpp`` / ``wf/recycling_gpu.hpp`` — every reference
emitter owns an MPMC pool; consumers return messages to the producer's pool
instead of freeing, avoiding allocator pressure on the hot path.

In the Python plane, message lifetime is garbage-collected and the hot
allocations that matter are the STAGING BUFFERS of the device boundary
(one numpy array per dtype group per staged batch, the schema's columns of
that dtype end to end: ``tpu/batch.py`` ``StagingBuffers``). ``ArrayPool``
keeps free lists keyed by (dtype, shape); the staging path acquires
buffers from it and ``InFlightRecycler`` returns them once the device transfer is
COMMITTED (``device_put``'s host read can complete asynchronously when
dispatch queues deepen — premature reuse corrupts in-flight batches).
Set WF_NO_RECYCLING=1 to disable, mirroring the reference's macro."""

from __future__ import annotations

import os
import threading
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

RECYCLING_ENABLED = os.environ.get("WF_NO_RECYCLING", "0") != "1"


class ArrayPool:
    """Thread-safe free lists of numpy buffers keyed by (dtype, shape).
    A buffer comes back as it was released, NOT zeroed: the stager writes
    the rows it has and zeroes the rest at ship time, so each byte is
    written once (``StagingBuffers.put``)."""

    def __init__(self, max_per_bucket: int = 32) -> None:
        self._free: Dict[Tuple[str, Tuple[int, ...]],
                         List[np.ndarray]] = defaultdict(list)
        self._lock = threading.Lock()
        self.max_per_bucket = max_per_bucket
        self.hits = 0
        self.misses = 0

    def acquire(self, dtype, shape) -> np.ndarray:
        """``shape``: an int (1-D) or a tuple."""
        if isinstance(shape, int):
            shape = (shape,)
        key = (str(np.dtype(dtype)), tuple(shape))
        if RECYCLING_ENABLED:
            with self._lock:
                bucket = self._free.get(key)
                if bucket:
                    self.hits += 1
                    return bucket.pop()
        self.misses += 1
        return np.empty(shape, dtype=dtype)

    def release(self, arr: np.ndarray) -> None:
        if not RECYCLING_ENABLED:
            return
        key = (str(arr.dtype), arr.shape)
        with self._lock:
            bucket = self._free[key]
            if len(bucket) < self.max_per_bucket:
                bucket.append(arr)


class InFlightRecycler:
    """Safe staging-buffer recycling over async H2D transfers.

    ``jax.device_put``'s read of the host buffer is DEFERRED: it executes
    when the async dispatch queue reaches it, so a staging buffer must not
    be touched until that read provably happened. ``jax.Array.is_ready()``
    is NOT that signal — it reports True while the read is still queued
    (verified empirically on the CPU backend: mutating the buffer after a
    True ``is_ready()`` corrupts the device array). The only sound signal
    is ``block_until_ready()`` returning, so this recycler keeps a bounded
    FIFO of in-flight batches (device arrays + the host buffers that fed
    them) and releases buffers to the ``ArrayPool`` ONLY on the blocking
    pop once depth exceeds ``max_in_flight``. At depth N the transfer
    being waited on was enqueued N batches ago — normally long done, so
    the block is free; when it isn't, the stall is exactly the
    backpressure the reference gets from an exhausted recycling pool
    (``wf/recycling_gpu.hpp:68-88``, in-transit counter
    ``wf/batch_gpu_t.hpp:66``; double-buffered staging
    ``wf/keyby_emitter_gpu.hpp:443-505``)."""

    def __init__(self, pool: ArrayPool, max_in_flight: Optional[int] = None,
                 force: bool = False) -> None:
        from collections import deque
        self.pool = pool
        if max_in_flight is None:
            # deferred device commits (WF_DISPATCH_DEPTH, the consumer's
            # dispatch pipeline) park H2D reads behind queued programs:
            # keep this FIFO comfortably deeper than the dispatch queue
            # so the blocking pop lands on transfers whose programs have
            # long since run instead of stalling on a parked one
            from .runtime.dispatch import dispatch_depth
            max_in_flight = max(8, 4 * dispatch_depth())
        self.max_in_flight = max_in_flight
        self._q = deque()  # (device arrays tuple, host buffers list)
        # Platform gate: the CPU backend's device_put may ALIAS the host
        # buffer indefinitely (zero-copy) — no Python-visible point where
        # reuse becomes safe, not even block_until_ready (verified: data
        # corrupts after it under dispatch-queue pressure). Accelerator
        # backends transfer with ImmutableUntilTransferCompletes
        # semantics, where the array's ready future IS the release
        # signal. ``force`` is for unit tests of the FIFO mechanics.
        if force:
            self.enabled = RECYCLING_ENABLED
        else:
            import jax
            self.enabled = (RECYCLING_ENABLED
                            and jax.default_backend() != "cpu")

    def track(self, dev_arrays, host_buffers) -> None:
        if not self.enabled:
            return
        self._q.append((tuple(dev_arrays), list(host_buffers)))
        while len(self._q) > self.max_in_flight:
            self._release_oldest()

    def _release_oldest(self) -> None:
        devs, bufs = self._q.popleft()
        for d in devs:
            d.block_until_ready()  # guarantees the host read is over
        for b in bufs:
            self.pool.release(b)

    def drain(self) -> None:
        """Release every tracked buffer (blocking; flush/EOS path)."""
        while self._q:
            self._release_oldest()


class ObjectPool:
    """Generic free list for message objects (Batch and friends)."""

    def __init__(self, factory, reset, max_size: int = 256) -> None:
        self._factory = factory
        self._reset = reset
        self._free: list = []
        self._lock = threading.Lock()
        self.max_size = max_size

    def acquire(self):
        if RECYCLING_ENABLED:
            with self._lock:
                if self._free:
                    obj = self._free.pop()
                    self._reset(obj)
                    return obj
        return self._factory()

    def release(self, obj) -> None:
        if not RECYCLING_ENABLED:
            return
        with self._lock:
            if len(self._free) < self.max_size:
                self._free.append(obj)
