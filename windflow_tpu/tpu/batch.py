"""BatchTPU: a micro-batch resident in device HBM.

This is the ``batch_tpu_t`` called for by BASELINE.json — the sibling of the
reference's ``Batch_GPU_t`` (``wf/batch_gpu_t.hpp:51-243``): a device buffer
of tuples plus key metadata, with the same message protocol (watermark,
punctuation flag, stream tag) as the CPU batches.

Differences by design (TPU/XLA instead of CUDA):
- storage is columnar (struct-of-arrays) because XLA programs want vector
  lanes, not arrays of structs;
- capacity is a power-of-two bucket with an explicit host-side ``size``
  (pad+mask replaces the reference's variable-size batches — fixed shapes
  avoid re-compiles, SURVEY.md §7 step 3b);
- instead of the reference's per-key linked index chains
  (``start_idxs_gpu``/``map_idxs_gpu``), keyed operators use a dense
  ``key_slots`` int32 column (host dictionary key -> slot id), which is the
  sort/segment-friendly encoding XLA wants;
- there is no per-batch CUDA stream: JAX dispatch is async and XLA orders
  executions on the device queue, which plays the same overlap role
  (``batch_gpu_t.hpp:64`` per-batch stream + double buffering).

``ts`` stays host-side int64 (microsecond timestamps outlive int32); device
code needing event time rebases per batch (see ffat_tpu).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..message import StreamMsg
from ..monitoring.tracing import next_batch_id
from .schema import TupleSchema


def key_column_to_list(batch: "BatchTPU", field: str) -> list:
    """D2H of the key column as a host list (one C call, no per-item
    boxing loops)."""
    return np.asarray(batch.fields[field])[:batch.size].tolist()


def key_column_np(batch: "BatchTPU", field: str) -> np.ndarray:
    """D2H of the key column as the RAW numpy array — the vectorized
    twin of ``key_column_to_list`` for consumers that never materialize
    Python keys (the dispatch pipeline's host-prep stage: tolist +
    re-asarray would box every key twice per batch)."""
    return np.asarray(batch.fields[field])[:batch.size]


def bucket_capacity(n: int, minimum: int = 8) -> int:
    c = minimum
    while c < n:
        c <<= 1
    return c


class BatchTPU(StreamMsg):
    __slots__ = ("fields", "ts_host", "size", "capacity", "wm", "is_punct",
                 "stream_tag", "id", "schema", "host_keys", "key_slots",
                 "slot_of_key", "trace_min", "trace_max", "bid", "cause")

    def __init__(self, fields: Dict[str, Any], ts_host: np.ndarray, size: int,
                 schema: TupleSchema, wm: int = 0,
                 host_keys: Optional[List[Any]] = None,
                 key_slots: Any = None,
                 slot_of_key: Optional[Dict[Any, int]] = None) -> None:
        self.fields = fields  # name -> jax.Array (capacity,)
        self.ts_host = ts_host  # np.int64 (capacity,)
        self.size = size
        self.capacity = len(ts_host)
        self.wm = wm
        self.is_punct = False
        self.stream_tag = 0
        self.id = 0
        self.schema = schema
        # keyed metadata (present on keyby-staged batches):
        self.host_keys = host_keys  # list of python keys, len == size
        self.key_slots = key_slots  # jax int32 (capacity,): dense slot ids
        self.slot_of_key = slot_of_key  # key -> slot id for this batch
        # latency-tracing origin stamps: min/max over traced constituents
        # (0 = none traced; monitoring/tracing.py)
        self.trace_min = 0
        self.trace_max = 0
        # host-timeline identity (monitoring/tracing.py): ``bid`` is given
        # once at the staging edge and travels with every batch derived
        # from this one, so its spans line up from wf:stage to wf:sink;
        # a batch another batch's commit MADE (a window fire, a re-shard
        # split) gets its own ``bid`` and names that batch as ``cause``.
        # ``id`` above stays the per-channel sequence number
        self.bid = 0
        self.cause = 0

    # -- protocol ----------------------------------------------------------
    def min_watermark(self) -> int:
        return self.wm

    def __len__(self) -> int:
        return self.size

    def nbytes(self) -> int:
        return sum(int(np.dtype(v.dtype).itemsize) * self.capacity
                   for v in self.fields.values())

    # -- construction ------------------------------------------------------
    @staticmethod
    def stage(rows: Sequence[Tuple[Any, int]], schema: TupleSchema,
              wm: int, keys: Optional[List[Any]] = None,
              capacity: Optional[int] = None,
              recycler=None) -> "BatchTPU":
        """CPU->TPU: columnarize and device_put (async dispatch; the
        reference's pinned staging + async H2D, ``keyby_emitter_gpu.hpp:
        443-505``). With ``recycler`` (an ``InFlightRecycler``) the column
        buffers come from its pool and are returned once the transfer is
        committed — device_put's host read can complete asynchronously
        once the dispatch queue deepens, so premature reuse corrupts
        in-flight batches (the hazard the reference tracks with in-transit
        counters, ``batch_gpu_t.hpp:66``)."""
        import jax

        cap = capacity or bucket_capacity(len(rows))
        pooled = recycler is not None and recycler.enabled
        cols, ts = schema.to_columns(rows, cap,
                                     recycler.pool if pooled else None)
        dev_fields = {name: jax.device_put(col) for name, col in cols.items()}
        if pooled:
            recycler.track(dev_fields.values(), cols.values())
        # per-batch slot ids are computed by the consuming keyed operator
        # (TPUReplicaBase.batch_slots); host_keys is the canonical metadata
        return BatchTPU(dev_fields, ts, len(rows), schema, wm, keys)

    @staticmethod
    def stage_columns(cols: Dict[str, np.ndarray], ts: np.ndarray,
                      schema: TupleSchema, wm: int,
                      keys: Optional[List[Any]] = None,
                      recycler=None) -> "BatchTPU":
        """CPU->TPU from COLUMNS (push_columns fast path): pad each numpy
        column to the capacity bucket and device_put — no per-tuple
        Python at all."""
        import jax

        n = len(ts)
        cap = bucket_capacity(n)
        pooled = recycler is not None and recycler.enabled
        dev_fields = {}
        staged = []
        for name, dt in schema.fields.items():
            src = cols[name]
            # one vectorized copy into a private buffer: the caller may
            # freely reuse its arrays (device_put can defer-read/alias the
            # host buffer, see InFlightRecycler)
            buf = (recycler.pool.acquire(dt, cap) if pooled
                   else np.zeros(cap, dtype=dt))
            buf[:n] = src
            dev_fields[name] = jax.device_put(buf)
            staged.append(buf)
        if pooled:
            recycler.track(dev_fields.values(), staged)
        ts2 = np.zeros(cap, dtype=np.int64)
        ts2[:n] = ts
        return BatchTPU(dev_fields, ts2, n, schema, wm, keys)

    @staticmethod
    def stage_prefilled(cols: Dict[str, np.ndarray], ts: np.ndarray,
                        n: int, schema: TupleSchema, wm: int,
                        keys: Optional[Any] = None,
                        recycler=None) -> "BatchTPU":
        """CPU->TPU from staging buffers ALREADY padded to the capacity
        bucket and filled in place (TPUStageEmitter's block-append path):
        just ``device_put`` — the single host copy per column happened at
        append time. Ownership of ``cols``/``ts`` transfers to the batch:
        the caller must not touch them again (device_put may alias the
        host buffer); with ``recycler`` the field buffers return to its
        pool once the H2D commits."""
        import jax

        dev_fields = {name: jax.device_put(cols[name])
                      for name in schema.fields}
        if recycler is not None and recycler.enabled:
            recycler.track(dev_fields.values(),
                           [cols[name] for name in schema.fields])
        return BatchTPU(dev_fields, ts, n, schema, wm, keys)

    # -- exit to host ------------------------------------------------------
    def prefetch_host(self) -> None:
        """Start async D2H of every column (the reference's
        ``prefetch2CPU``, ``batch_gpu_t_u.hpp:203``). A synchronous fetch
        of a fresh device buffer waits for the program that produces it
        and then for the copy; issuing the copies early lets them
        overlap each other and subsequent compute, after which
        ``np.asarray`` reads the cached host copy."""
        for v in self.fields.values():
            f = getattr(v, "copy_to_host_async", None)
            if f is not None:
                f()

    def to_rows(self) -> List[Tuple[Any, int]]:
        """TPU->CPU (the reference's ``transfer2CPU``,
        ``batch_gpu_t.hpp:154-165``)."""
        host_cols = {name: np.asarray(v) for name, v in self.fields.items()}
        return self.schema.from_columns(host_cols, self.ts_host, self.size)

    def copy_trace_from(self, src: "BatchTPU") -> "BatchTPU":
        """Propagate origin stamps and timeline identity from the batch
        this one derives from (operator outputs, compactions, copies)."""
        self.trace_min = src.trace_min
        self.trace_max = src.trace_max
        self.bid = src.bid
        self.cause = src.cause
        return self

    def caused_by(self, src: "BatchTPU") -> "BatchTPU":
        """A NEW batch made while committing ``src`` (one of several
        gathered from it): origin stamps travel, the identity is fresh
        and names ``src`` as its cause."""
        self.copy_trace_from(src)
        self.bid = next_batch_id()
        self.cause = src.bid
        return self

    def with_fields(self, new_fields: Dict[str, Any]) -> "BatchTPU":
        """Same metadata, new device columns (in-place operator output)."""
        b = BatchTPU(new_fields, self.ts_host, self.size, self.schema,
                     self.wm, self.host_keys, self.key_slots,
                     self.slot_of_key)
        b.stream_tag = self.stream_tag
        b.id = self.id
        return b.copy_trace_from(self)

    def copy_for_dest(self) -> "BatchTPU":
        """Broadcast copy: device arrays are immutable, sharing is safe."""
        b = BatchTPU(dict(self.fields), self.ts_host, self.size, self.schema,
                     self.wm, self.host_keys, self.key_slots,
                     self.slot_of_key)
        b.stream_tag = self.stream_tag
        b.id = self.id
        return b.copy_trace_from(self)

    @property
    def num_keys(self) -> int:
        return len(self.slot_of_key) if self.slot_of_key is not None else 0

    def __repr__(self) -> str:  # pragma: no cover
        return (f"<BatchTPU n={self.size}/{self.capacity} wm={self.wm} "
                f"keys={self.num_keys}>")
