"""A staged batch crosses as one packed buffer per dtype group
(``tpu/batch.py``: ``StagingBuffers`` -> ``PackedFields``). Everything a
program or an emitter computes from it must equal, byte for byte, what it
computes from the same columns staged one ``device_put`` each; reused
staging buffers must never leak old rows; and the staging counters must
say how many transfers a batch cost and when a reader fell off the packed
path."""

import numpy as np
import pytest

from windflow_tpu.basic import WinType
from windflow_tpu.monitoring.tracing import StageCounters
from windflow_tpu.recycling import ArrayPool, InFlightRecycler
from windflow_tpu.tpu.batch import (BatchTPU, PackedFields, PackedLayout,
                                    StagingBuffers)
from windflow_tpu.tpu.schema import TupleSchema

CAP = 64
SCHEMAS = {
    # name -> (field dtypes, rows of a CAP-row bucket that are filled)
    "one_dtype": ({"key": np.int32, "a": np.int32, "b": np.int32}, CAP),
    "mixed": ({"key": np.int32, "x": np.float32, "a": np.int32,
               "flag": np.bool_, "w": np.int64}, CAP),
    "one_column": ({"key": np.int32}, CAP),
    "partial": ({"key": np.int32, "a": np.int32, "b": np.int32}, 37),
}
GROUPS = {"one_dtype": 1, "mixed": 3, "one_column": 1, "partial": 1}


def _columns(fields, n, seed):
    rng = np.random.default_rng(seed)
    cols = {}
    for name, dt in fields.items():
        if name == "key":
            cols[name] = rng.integers(0, 7, n).astype(dt)
        elif np.dtype(dt).kind == "b":
            cols[name] = rng.integers(0, 2, n).astype(dt)
        elif np.dtype(dt).kind == "f":
            cols[name] = rng.random(n).astype(dt)
        else:
            cols[name] = rng.integers(1, 1000, n).astype(dt)
    ts = (1000 * seed + np.cumsum(rng.integers(1, 9, n))).astype(np.int64)
    return cols, ts


def _both_forms(case, seed, counters=None):
    """The same columns as a staged (packed) batch and as the per-column
    batch the staging edge used to build: one padded ``device_put`` a
    column, made here without any of the packed code."""
    import jax

    fields, n = SCHEMAS[case]
    schema = TupleSchema(fields)
    cols, ts = _columns(fields, n, seed)
    keys = cols["key"].copy()
    wm = int(ts[-1])
    packed = BatchTPU.stage_columns(cols, ts, schema, wm, keys,
                                    counters=counters)
    assert isinstance(packed.fields, PackedFields)
    assert packed.capacity == CAP
    per_col = {}
    for name, dt in fields.items():
        buf = np.zeros(CAP, dtype=dt)
        buf[:n] = cols[name]
        per_col[name] = jax.device_put(buf)
    ts2 = np.zeros(CAP, dtype=np.int64)
    ts2[:n] = ts
    return packed, BatchTPU(per_col, ts2, n, schema, wm, keys.copy())


def _snapshot(batch):
    """Everything of an emitted batch that a consumer can see, as bytes."""
    cols = {name: (str(np.asarray(v).dtype), np.asarray(v).tobytes())
            for name, v in batch.fields.items()}
    keys = batch.host_keys
    return (batch.size, batch.capacity, batch.wm, cols,
            np.asarray(batch.ts_host).tobytes(),
            None if keys is None else np.asarray(keys).tolist())


class _Capture:
    """Stands where a replica's emitter (or an emitter's port) would."""

    def __init__(self):
        self.out = []

    def emit_device_batch(self, b):
        self.out.append(_snapshot(b))

    def send(self, b):
        self.out.append(_snapshot(b))

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass

    def flush(self):
        pass


def _through_chain(batches):
    """``Filter_TPU`` chained with ``Map_TPU``: one fused program."""
    from windflow_tpu.tpu.fused_ops import FusedTPUReplica
    from windflow_tpu.tpu.ops_tpu import Filter_TPU, Map_TPU

    rep = FusedTPUReplica(
        [Filter_TPU(lambda f: f["key"] % 3 != 0, name="keep"),
         Map_TPU(lambda f: {**f, "key2": f["key"] * 2 + 1}, name="twice")],
        0)
    cap = _Capture()
    rep.set_emitter(cap)
    for b in batches:
        rep.handle_msg(0, b)
    rep.terminate()
    return cap.out, rep.stats


def _through_ffat(batches):
    """``Ffat_Windows_TPU`` fed the staged batch directly."""
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU

    op = Ffat_Windows_TPU(
        lift=lambda f: {"value": f["key"] + 1},
        combine=lambda a, b: {"value": a["value"] + b["value"]},
        key_extractor="key", win_len=40, slide_len=20,
        win_type=WinType.TB, lateness=0, num_win_per_batch=8,
        key_capacity=8, name="win")
    op.build_replicas()
    rep = op.replicas[0]
    cap = _Capture()
    rep.emitter = cap
    for b in batches:
        rep.handle_msg(0, b)
    rep.terminate()
    return cap.out, rep.stats


def _through_reshard(batches):
    """The keyed TPU->TPU re-shard: a device gather per destination."""
    from windflow_tpu.tpu.emitters_tpu import TPUKeyByEmitter

    em = TPUKeyByEmitter(lambda t: t["key"], 3, key_field="key")
    ports = [_Capture() for _ in range(3)]
    em.set_ports(ports)
    for b in batches:
        em.emit_device_batch(b)
    em.flush()
    return [p.out for p in ports], None


PATHS = {"chain": _through_chain, "ffat": _through_ffat,
         "reshard": _through_reshard}


@pytest.mark.parametrize("case", list(SCHEMAS))
@pytest.mark.parametrize("path", list(PATHS))
def test_packed_equals_per_column(path, case):
    forms = [_both_forms(case, seed) for seed in (1, 2, 3)]
    got, _ = PATHS[path]([p for p, _ in forms])
    want, _ = PATHS[path]([c for _, c in forms])
    assert got == want
    flat = got if path != "reshard" else [b for port in got for b in port]
    assert flat and sum(b[0] for b in flat) > 0, "differential is vacuous"


@pytest.mark.parametrize("case", list(SCHEMAS))
def test_layout_groups_and_bytes(case):
    """One transfer per device dtype, counted; ``nbytes()`` is what the
    per-column batch's was (an int64 column is int32 on the device) and
    costs no slice."""
    ctr = StageCounters("src")
    packed, per_col = _both_forms(case, 5, ctr)
    lay = packed.fields.layout
    assert len(packed.fields.bufs) == len(lay.dtypes) == GROUPS[case]
    assert ctr.h2d_puts == GROUPS[case]
    assert packed.nbytes() == per_col.nbytes()
    assert ctr.unpacked_columns == 0
    assert list(packed.fields) == list(SCHEMAS[case][0])  # schema order
    # pad rows are zero in every column
    n = packed.size
    for name, col in packed.fields.host_columns().items():
        assert not col[n:].any(), name
        assert col[:n].tobytes() == np.asarray(
            per_col.fields[name])[:n].tobytes(), name
    assert ctr.unpacked_columns == 0  # a whole-batch host read slices none


def test_unpacked_columns_counts_host_slices():
    ctr = StageCounters("src")
    packed, per_col = _both_forms("mixed", 9, ctr)
    a = np.asarray(packed.fields["a"])
    assert ctr.unpacked_columns == 1
    assert a.tobytes() == np.asarray(per_col.fields["a"]).tobytes()
    packed.fields["a"]  # cached: the slice is made once
    assert ctr.unpacked_columns == 1
    packed.fields["x"]  # a group of one column is that column
    assert ctr.unpacked_columns == 1
    assert "key" in packed.fields and "nope" not in packed.fields
    assert ctr.unpacked_columns == 1
    packed.to_rows()  # reads each group once, slices nothing
    assert ctr.unpacked_columns == 1
    dict(packed.fields)  # every remaining column of a shared group
    assert ctr.unpacked_columns == 3  # "key" and "w" ("a" was cached)


def test_one_treedef_per_schema_compiles_once():
    """Batches of equal schemas (distinct ``TupleSchema`` objects) share
    one pytree structure: the chain's program compiles for the first
    batch and never again."""
    batches = [_both_forms("mixed", seed)[0] for seed in range(6)]
    assert len({id(b.schema) for b in batches}) == 6
    assert len({PackedLayout.of(b.schema) for b in batches}) == 1
    out, stats = _through_chain(batches)
    assert len(out) == 6
    assert stats.compile_count == 1
    assert stats.device_programs_run == 6


@pytest.mark.parametrize("how", ["stage", "stage_columns", "stage_prefilled"])
def test_reused_packed_buffer_stays_exact(how):
    """More batches than ``max_in_flight``, distinct data, full and
    partial sizes in turn, through a FORCED recycler: pooled group
    buffers come back dirty, so a partial batch shows the aliasing hazard
    at once (stale rows of the buffer's last use in its pad rows, or in a
    neighbouring column). Each batch is read right after it is staged:
    on the CPU backend ``device_put`` may alias the host buffer, so an
    older batch legitimately changes once its buffer is reused
    (``test_recycling.py``)."""
    fields = {"k": np.int32, "v": np.float32, "c": np.int32}
    schema = TupleSchema(fields)
    pool = ArrayPool()
    rec = InFlightRecycler(pool, max_in_flight=2, force=True)
    ctr = StageCounters("src")
    for i in range(12):
        n = CAP if i % 2 == 0 else 5 + i
        k = (np.arange(n) + 1000 * i).astype(np.int32)
        v = (np.arange(n) * 0.5 + i).astype(np.float32)
        c = (7 * np.arange(n) - i).astype(np.int32)
        ts = np.arange(n, dtype=np.int64)
        if how == "stage":
            rows = [({"k": int(k[j]), "v": float(v[j]), "c": int(c[j])},
                     int(ts[j])) for j in range(n)]
            b = BatchTPU.stage(rows, schema, 0, None, CAP, rec, ctr)
        elif how == "stage_columns":
            b = BatchTPU.stage_columns({"k": k, "v": v, "c": c}, ts, schema,
                                       0, None, rec, ctr)
        else:
            st = StagingBuffers(schema, CAP, rec)
            st.cols["k"][:n], st.cols["v"][:n], st.cols["c"][:n] = k, v, c
            ts2 = np.zeros(CAP, np.int64)
            ts2[:n] = ts
            b = BatchTPU.stage_prefilled(st, ts2, n, schema, 0, None, ctr)
        host = b.fields.host_columns()
        for name, want in (("k", k), ("v", v), ("c", c)):
            assert host[name][:n].tobytes() == want.tobytes(), (i, name)
            assert not host[name][n:].any(), (i, name)
    assert pool.hits > 0, "no buffer was reused: the test shows nothing"
    assert ctr.h2d_puts == 12 * 2  # int32 and float32 groups
    rec.drain()


def _run_graph(fields, n_blocks, rows=CAP):
    """A ``ysb``-shaped graph over ``fields``: columnar source -> filter
    chained with map (one program) -> keyed window -> columnar sink."""
    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder,
                                  Filter_TPU_Builder, Map_TPU_Builder)

    got = []

    def source(shipper, ctx):
        for i in range(n_blocks):
            cols, ts = _columns(fields, rows, i + 1)
            shipper.push_columns(cols, ts)
            shipper.set_next_watermark(int(ts[-1]))

    def sink(cols, ts):
        if cols is not None:
            got.append({k: np.asarray(v).copy() for k, v in cols.items()})

    g = PipeGraph("packed", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    g.add_source(Source_Builder(source).with_name("src")
                 .with_output_batch_size(rows).build()) \
     .add(Filter_TPU_Builder(lambda f: f["key"] % 3 != 0)
          .with_name("views").build()) \
     .chain(Map_TPU_Builder(lambda f: {"campaign": f["key"],
                                       "one": f["key"] * 0 + 1})
            .with_name("join").build()) \
     .add(Ffat_Windows_TPU_Builder(
              lambda f: {"count": f["one"]},
              lambda a, b: {"count": a["count"] + b["count"]})
          .with_key_by("campaign").with_tb_windows(200, 200)
          .with_num_win_per_batch(8).with_key_capacity(8)
          .with_name("win").build()) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    g.run()
    ops = {o["name"]: o["replicas"] for o in g.get_stats()["Operators"]}
    return got, ops


@pytest.mark.parametrize("case", ["one_dtype", "mixed"])
def test_graph_counters(case):
    """On the source of a ``ysb``-shaped graph: ``Stage_h2d_puts`` over
    ``Stage_batches`` is the number of dtype groups, no column is ever
    sliced out on the host, ``Device_bytes_H2D`` is what one transfer
    per column carried, and the chain compiles once."""
    fields = SCHEMAS[case][0]
    n_blocks = 6
    got, ops = _run_graph(fields, n_blocks)
    src = ops["src"][0]
    assert src["Stage_batches"] == n_blocks
    assert src["Stage_h2d_puts"] == GROUPS[case] * n_blocks
    assert src["Stage_unpacked_columns"] == 0
    per_row = sum(min(np.dtype(dt).itemsize, 4) for dt in fields.values())
    assert src["Device_bytes_H2D"] == n_blocks * CAP * per_row
    chain = next(r[0] for name, r in ops.items() if "views" in name)
    assert chain["Compile_count"] == 1
    assert chain["Device_programs_run"] == n_blocks
    # and the windows count every kept event once
    kept = sum(int((_columns(fields, CAP, i + 1)[0]["key"] % 3 != 0).sum())
               for i in range(n_blocks))
    counted = sum(int(c["count"][c["valid"].astype(bool)].sum())
                  if "valid" in c else int(c["count"].sum()) for c in got)
    assert counted == kept
