"""BatchTPU: a micro-batch resident in device HBM.

This is the ``batch_tpu_t`` called for by BASELINE.json — the sibling of the
reference's ``Batch_GPU_t`` (``wf/batch_gpu_t.hpp:51-243``): a device buffer
of tuples plus key metadata, with the same message protocol (watermark,
punctuation flag, stream tag) as the CPU batches.

Differences by design (TPU/XLA instead of CUDA):
- storage is columnar (struct-of-arrays) because XLA programs want vector
  lanes, not arrays of structs;
- a batch STAGED from the host crosses in one transfer per device dtype,
  not one per column (a ``device_put`` costs a quarter of a millisecond a
  call on a v5e, whatever it carries): ``StagingBuffers`` lays the
  schema's columns of one dtype end to end in one host buffer,
  ``PackedFields`` is the ``fields`` mapping over the device copies, and
  a column is a static slice taken inside the program that reads it.
  Batches a device program made keep a plain dict of columns;
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

import functools
from collections.abc import Mapping
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..message import StreamMsg
from ..monitoring.tracing import StageCounters, next_batch_id
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


class PackedLayout:
    """Where each column of a schema lies in the packed form: columns are
    grouped by the dtype they have ON THE DEVICE (``device_put``
    canonicalises: without x64 an int64 column is int32 there), in schema
    order, and column ``row`` of group ``g`` is elements ``[row * cap,
    (row + 1) * cap)`` of that group's flat ``(rows * cap,)`` buffer — the
    layout a column of its own had, so its slice stays aligned (``cap``
    is a power of two). Hashable and capacity-free: it is the pytree aux
    data of ``PackedFields``, one treedef per schema."""

    __slots__ = ("key", "index", "dtypes", "rows", "_hash")

    def __init__(self, schema: TupleSchema) -> None:
        from jax.dtypes import canonicalize_dtype

        groups: Dict[np.dtype, int] = {}  # device dtype -> columns so far
        self.index = {}  # name -> (group, row), in schema order
        for name, dt in schema.fields.items():
            dt = np.dtype(canonicalize_dtype(dt))
            row = groups.get(dt, 0)
            groups[dt] = row + 1
            self.index[name] = (list(groups).index(dt), row)
        self.dtypes = tuple(groups)
        self.rows = tuple(groups.values())
        self.key = tuple((name, str(self.dtypes[g]), g, row)
                         for name, (g, row) in self.index.items())
        self._hash = hash(self.key)

    @staticmethod
    def of(schema: TupleSchema) -> "PackedLayout":
        lay = getattr(schema, "_packed_layout", None)
        if lay is None:
            lay = schema._packed_layout = PackedLayout(schema)
        return lay

    def __eq__(self, other) -> bool:
        return self is other or (type(other) is PackedLayout
                                 and self.key == other.key)

    def __hash__(self) -> int:
        return self._hash

    def nbytes(self, capacity: int) -> int:
        return capacity * sum(dt.itemsize * r
                              for dt, r in zip(self.dtypes, self.rows))


class PackedFields(Mapping):
    """``BatchTPU.fields`` of a staged batch: name -> column over one
    device buffer per dtype group. A pytree node whose leaves are the
    group buffers, so a jitted program takes ``len(groups)`` arguments
    where it took one per column, and inside it ``fields[name]`` is a
    static slice XLA fuses into its consumer. Outside a program the same
    index MATERIALISES (and caches) a device slice: the slow path of a
    host reader, counted on the staging operator's
    ``Stage_unpacked_columns`` so a hot path that falls onto it is seen.
    Read-only; a group of one column is that column, no slice."""

    __slots__ = ("bufs", "layout", "_cols", "_counters")

    def __init__(self, bufs: Tuple[Any, ...], layout: PackedLayout,
                 counters: Optional[StageCounters] = None) -> None:
        _device_side()
        self.bufs = bufs
        self.layout = layout
        self._cols: Dict[str, Any] = {}
        self._counters = counters

    def __getitem__(self, name: str) -> Any:
        col = self._cols.get(name)
        if col is None:
            g, row = self.layout.index[name]
            col = self.bufs[g]
            rows = self.layout.rows[g]
            if rows > 1:
                cap = col.shape[-1] // rows
                if self._counters is not None \
                        and not isinstance(col, _device_side()[0]):
                    self._counters.unpacked_columns += 1
                col = col[..., row * cap:(row + 1) * cap]
            self._cols[name] = col
        return col

    def __contains__(self, name) -> bool:
        return name in self.layout.index

    def __iter__(self) -> Iterator[str]:
        return iter(self.layout.index)

    def __len__(self) -> int:
        return len(self.layout.index)

    def host_columns(self, names=None) -> Dict[str, np.ndarray]:
        """Columns ``names`` (default: all) on the host from ONE read per
        group (row views of it): what a host reader of a whole batch
        wants, and no device slice is made."""
        host: Dict[int, np.ndarray] = {}
        out = {}
        for name in (self if names is None else names):
            g, row = self.layout.index[name]
            h = host.get(g)
            if h is None:
                h = host[g] = np.asarray(self.bufs[g])
            cap = h.shape[0] // self.layout.rows[g]
            out[name] = h[row * cap:(row + 1) * cap]
        return out

    def gather(self, idx: Any) -> "PackedFields":
        """Rows ``idx`` of every column: one gather a group, and the
        result is packed like this one."""
        gather = _device_side()[1]
        return PackedFields(
            tuple(gather(b, idx, rows)
                  for b, rows in zip(self.bufs, self.layout.rows)),
            self.layout, self._counters)


@functools.cache
def _device_side() -> Tuple[type, Any]:
    """``(jax.core.Tracer, the jitted per-group gather)``, and registers
    ``PackedFields`` as a pytree node — on first use, because importing
    this module must not import jax (the CPU plane never pays for it)."""
    import jax

    jax.tree_util.register_pytree_node(
        PackedFields, lambda f: (f.bufs, f.layout),
        lambda layout, bufs: PackedFields(tuple(bufs), layout))
    gather = jax.jit(
        lambda buf, idx, rows: buf.reshape(rows, -1)[:, idx].reshape(-1),
        static_argnums=2)
    return jax.core.Tracer, gather


def field_dtype(fields: Mapping, name: str) -> np.dtype:
    """Device dtype of one column without touching it."""
    if isinstance(fields, PackedFields):
        return fields.layout.dtypes[fields.layout.index[name][0]]
    return np.dtype(fields[name].dtype)


def host_columns(fields: Mapping, names=None) -> Dict[str, np.ndarray]:
    """Columns ``names`` (default: all) of a batch's ``fields`` as host
    arrays."""
    if isinstance(fields, PackedFields):
        return fields.host_columns(names)
    return {name: np.asarray(fields[name])
            for name in (fields if names is None else names)}


def gather_columns(fields: Mapping, idx: Any) -> Mapping:
    """Rows ``idx`` (a device index vector) of every column, on the
    device, in ONE program: a gather per dtype group of a packed batch,
    and of a dict of columns too (``_gather_dict``)."""
    if isinstance(fields, PackedFields):
        return fields.gather(idx)
    return _gather_dict()(dict(fields), (idx,))[0]


def gather_columns_each(fields: Mapping, idxs: Sequence[np.ndarray]
                        ) -> List[Mapping]:
    """``gather_columns`` for each of the HOST index vectors ``idxs``
    (the branches of a device split, the destinations of a keyed
    re-shard). Of a dict of columns ONE program gathers them all and
    carries the indices over with its operands: a ``device_put`` and a
    launch an index were, on the chip's host, ~1 and ~2 ms of the
    emitting operator's thread each, and each a call after which it
    waits for the interpreter again."""
    if isinstance(fields, PackedFields):
        import jax

        return [fields.gather(jax.device_put(idx)) for idx in idxs]
    return list(_gather_dict()(dict(fields), tuple(idxs)))


@functools.cache
def _gather_dict():
    """The jitted gather of a dict of columns by each of a tuple of index
    vectors: the columns of one dtype stacked once and gathered together,
    once an index. Column by column outside a program it was a launch a
    column (a device split of a 15-column batch into two branches: 30
    launches, ~90 ms of the operator's thread a block, PR 38), and on the
    device a gather costs by the INDEX, whatever rows ride on it (eight
    columns of 16,384 lanes: 1.2 ms apart, 0.2 ms stacked)."""
    import jax
    import jax.numpy as jnp

    def gather(fields, idxs):
        groups: Dict[Any, List[str]] = {}
        for name, v in fields.items():
            groups.setdefault((v.dtype, v.shape), []).append(name)
        outs = [{} for _ in idxs]
        for names in groups.values():
            block = jnp.stack([fields[k] for k in names])
            for out, idx in zip(outs, idxs):
                out.update(zip(names, block[:, idx]))
        return tuple({name: out[name] for name in fields} for out in outs)

    return jax.jit(gather)


class StagingBuffers:
    """The host side of one staged batch: one contiguous buffer per dtype
    group (``groups``) and ``cols``, a row view of it per column, which is
    what the staging copies write. With an enabled ``recycler`` (an
    ``InFlightRecycler``) the buffers come from its pool and return to it
    once the transfer is committed. Buffers come UNINITIALISED: ``put``
    zeroes the pad rows ``[n, cap)`` of every column before the transfer,
    so each byte is written once and the pad rows read zero on the device
    as they always have."""

    __slots__ = ("layout", "capacity", "groups", "cols", "_recycler")

    def __init__(self, schema: TupleSchema, capacity: int,
                 recycler=None) -> None:
        lay = self.layout = PackedLayout.of(schema)
        self.capacity = capacity
        if recycler is not None and not recycler.enabled:
            recycler = None
        self._recycler = recycler
        pool = recycler.pool if recycler is not None else None
        self.groups = [
            (pool.acquire(dt, rows * capacity) if pool is not None
             else np.empty(rows * capacity, dtype=dt))
            for dt, rows in zip(lay.dtypes, lay.rows)]
        self.cols = {name: self.groups[g][row * capacity:
                                          (row + 1) * capacity]
                     for name, (g, row) in lay.index.items()}

    def fill(self, cols: Dict[str, np.ndarray], n: int) -> "StagingBuffers":
        """Copy the first ``n`` rows of each column in (one vectorized
        copy a column)."""
        for name, buf in self.cols.items():
            buf[:n] = cols[name][:n]
        return self

    def put(self, n: int,
            counters: Optional[StageCounters] = None) -> PackedFields:
        """Zero the pad rows, ``device_put`` each group (async dispatch;
        counted on ``counters`` as ``Stage_h2d_puts``) and hand the
        buffers to the recycler. The caller must not touch them again:
        ``device_put`` may alias the host buffer, and its read of it can
        complete asynchronously once the dispatch queue deepens, so
        premature reuse corrupts in-flight batches (the hazard the
        reference tracks with in-transit counters, ``batch_gpu_t.hpp:66``;
        pinned staging + async H2D, ``keyby_emitter_gpu.hpp:443-505``)."""
        import jax

        cap = self.capacity
        if n < cap:
            for buf, rows in zip(self.groups, self.layout.rows):
                buf.reshape(rows, cap)[:, n:] = 0
        dev = tuple(jax.device_put(buf) for buf in self.groups)
        if counters is not None:
            counters.h2d_puts += len(dev)
        if self._recycler is not None:
            self._recycler.track(dev, self.groups)
        return PackedFields(dev, self.layout, counters)


def row_schema(fields: Mapping, schema: Optional[TupleSchema]
               ) -> TupleSchema:
    """The schema of a batch an operator made from one with ``schema``.
    An operator that adds, drops or renames columns changes what a row
    is: the schema then follows the columns (rows leave the device as
    dicts), else a row exit would rebuild the INPUT's rows and lose what
    the operator made. Rows of a user's type stay of that type while the
    columns still hold its fields (more columns are then helpers of the
    device plane)."""
    names = fields.keys()
    if schema is not None and (
            names == schema.fields.keys()
            or (schema.constructor is not None
                and names >= schema.fields.keys())):
        return schema
    return TupleSchema({name: np.dtype(v.dtype)
                        for name, v in fields.items()})


class ChunkedKeys:
    """The keys of a fired batch by CHUNK: ``counts[i]`` consecutive rows
    hold ``keys[i]`` (an array of int keys, else a list). What a window
    operator hands ``BatchTPU`` in place of a key a row: most consumers
    of fired windows read no host keys, and the one that does expands
    them on its first read (``BatchTPU.host_keys``)."""

    __slots__ = ("keys", "counts")

    def __init__(self, keys, counts: np.ndarray) -> None:
        self.keys = keys
        self.counts = counts

    def expand(self):
        if isinstance(self.keys, np.ndarray):
            return np.repeat(self.keys, self.counts)  # numpy, no boxing
        # composite/object keys (callable extractors)
        return [key for key, n in zip(self.keys, self.counts.tolist())
                for _ in range(n)]


def async_host_copy(*arrays: Any) -> None:
    """Start the async host copy of device arrays (nothing for a plain
    numpy array or None): a later ``np.asarray`` / ``int()`` reads the
    copy that has landed instead of waiting for the transfer too."""
    for arr in arrays:
        f = getattr(arr, "copy_to_host_async", None)
        if f is not None:
            f()


class BatchTPU(StreamMsg):
    __slots__ = ("fields", "ts_host", "size", "capacity", "wm", "is_punct",
                 "stream_tag", "id", "schema", "_host_keys", "key_slots",
                 "slot_of_key", "trace_min", "trace_max", "bid", "cause",
                 "key_origin")

    def __init__(self, fields: Dict[str, Any], ts_host: np.ndarray, size: int,
                 schema: TupleSchema, wm: int = 0,
                 host_keys: Optional[List[Any]] = None,
                 key_slots: Any = None,
                 slot_of_key: Optional[Dict[Any, int]] = None) -> None:
        # name -> jax.Array (capacity,): a PackedFields on a batch staged
        # from the host, a dict on one a device program made
        self.fields = fields
        self.ts_host = ts_host  # np.int64 (capacity,)
        self.size = size
        self.capacity = len(ts_host)
        self.wm = wm
        self.is_punct = False
        self.stream_tag = 0
        self.id = 0
        self.schema = schema
        # keyed metadata (present on keyby-staged batches):
        # python keys (a list, or an array of ints), len == size; or
        # ``ChunkedKeys``, expanded where ``host_keys`` is first read
        self._host_keys = host_keys
        self.key_slots = key_slots  # jax int32 (capacity,): dense slot ids
        self.slot_of_key = slot_of_key  # key -> slot id for this batch
        # the field (or tuple of fields) ``host_keys`` are the values of,
        # where the operator that made this batch keyed it by its OWN
        # key (a window or reduce result): a keyed consumer that names
        # another field reads that column (``keys_for``). None: staged
        # for the consumer's key, whatever it is
        self.key_origin = None
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
        f = self.fields
        if isinstance(f, PackedFields):  # no column is touched for this
            return f.layout.nbytes(self.capacity)
        return sum(int(np.dtype(v.dtype).itemsize) * self.capacity
                   for v in f.values())

    # -- construction ------------------------------------------------------
    # CPU->TPU, three ways in and one way across: ``stage_prefilled``.
    # ``recycler`` pools the staging buffers, ``counters`` (the staging
    # operator's) counts the transfers and later host-side unpacking.
    @staticmethod
    def stage(rows: Sequence[Tuple[Any, int]], schema: TupleSchema,
              wm: int, keys: Optional[List[Any]] = None,
              capacity: Optional[int] = None, recycler=None,
              counters: Optional[StageCounters] = None) -> "BatchTPU":
        """From ROWS: columnarize, then copy into staging buffers.
        Per-batch slot ids are computed by the consuming keyed operator
        (TPUReplicaBase.batch_slots); host_keys is the canonical
        metadata."""
        n = len(rows)
        cap = capacity or bucket_capacity(n)
        cols, ts = schema.to_columns(rows, cap)
        staging = StagingBuffers(schema, cap, recycler).fill(cols, n)
        return BatchTPU.stage_prefilled(staging, ts, n, schema, wm, keys,
                                        counters)

    @staticmethod
    def stage_columns(cols: Dict[str, np.ndarray], ts: np.ndarray,
                      schema: TupleSchema, wm: int,
                      keys: Optional[List[Any]] = None, recycler=None,
                      counters: Optional[StageCounters] = None
                      ) -> "BatchTPU":
        """From COLUMNS (push_columns fast path; no per-tuple Python): one
        vectorized copy of each column into private staging buffers at
        the capacity bucket, so the caller may freely reuse its arrays."""
        n = len(ts)
        cap = bucket_capacity(n)
        staging = StagingBuffers(schema, cap, recycler).fill(cols, n)
        ts2 = np.zeros(cap, dtype=np.int64)
        ts2[:n] = ts
        return BatchTPU.stage_prefilled(staging, ts2, n, schema, wm, keys,
                                        counters)

    @staticmethod
    def stage_prefilled(staging: StagingBuffers, ts: np.ndarray, n: int,
                        schema: TupleSchema, wm: int,
                        keys: Optional[Any] = None,
                        counters: Optional[StageCounters] = None
                        ) -> "BatchTPU":
        """From staging buffers whose first ``n`` rows were filled in
        place (TPUStageEmitter's block-append path): one ``device_put``
        per dtype group (``StagingBuffers.put``). Ownership of
        ``staging``/``ts`` transfers to the batch."""
        return BatchTPU(staging.put(n, counters), ts, n, schema, wm, keys)

    # -- exit to host ------------------------------------------------------
    def prefetch_host(self) -> None:
        """Start async D2H of every column (the reference's
        ``prefetch2CPU``, ``batch_gpu_t_u.hpp:203``). A synchronous fetch
        of a fresh device buffer waits for the program that produces it
        and then for the copy; issuing the copies early lets them
        overlap each other and subsequent compute, after which
        ``np.asarray`` reads the cached host copy."""
        async_host_copy(*self.fields.values())

    def to_rows(self) -> List[Tuple[Any, int]]:
        """TPU->CPU (the reference's ``transfer2CPU``,
        ``batch_gpu_t.hpp:154-165``)."""
        return self.schema.from_columns(host_columns(self.fields),
                                        self.ts_host, self.size)

    def copy_trace_from(self, src: "BatchTPU") -> "BatchTPU":
        """Propagate origin stamps and timeline identity from the batch
        this one derives from (operator outputs, compactions, copies),
        and whose key its host keys are (a batch that replaces them says
        so itself, after this)."""
        self.key_origin = src.key_origin
        self.trace_min = src.trace_min
        self.trace_max = src.trace_max
        self.bid = src.bid
        self.cause = src.cause
        return self

    @property
    def host_keys(self):
        keys = self._host_keys
        if isinstance(keys, ChunkedKeys):
            keys = self._host_keys = keys.expand()
        return keys

    @host_keys.setter
    def host_keys(self, keys) -> None:
        self._host_keys = keys

    def caused_by(self, src: "BatchTPU") -> "BatchTPU":
        """A NEW batch made while committing ``src`` (one of several
        gathered from it): origin stamps travel, the identity is fresh
        and names ``src`` as its cause."""
        self.copy_trace_from(src)
        self.bid = next_batch_id()
        self.cause = src.bid
        return self

    def keys_for(self, mine):
        """``host_keys`` where they are the keys of a consumer keyed by
        ``mine`` (a field name, a tuple of them, or None for a callable
        extractor), else None: the consumer then reads its own
        column(s). Keys a producer made by its own key (``key_origin``)
        are another operator's unless the consumer names the same
        field(s), or names none this batch holds."""
        origin = self.key_origin
        if origin is None or not mine or mine == origin:
            return self.host_keys
        names = (mine,) if isinstance(mine, str) else mine
        if all(f in self.fields for f in names):
            return None
        return self.host_keys

    def with_fields(self, new_fields: Dict[str, Any]) -> "BatchTPU":
        """Same metadata, new device columns (in-place operator output)."""
        schema = row_schema(new_fields, self.schema)
        b = BatchTPU(new_fields, self.ts_host, self.size, schema,
                     self.wm, self._host_keys, self.key_slots,
                     self.slot_of_key)
        b.stream_tag = self.stream_tag
        b.id = self.id
        return b.copy_trace_from(self)

    def copy_for_dest(self) -> "BatchTPU":
        """Broadcast copy: device arrays are immutable, sharing is safe."""
        b = BatchTPU(self.fields, self.ts_host, self.size, self.schema,
                     self.wm, self._host_keys, self.key_slots,
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
