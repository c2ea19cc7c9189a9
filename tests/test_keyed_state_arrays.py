"""Keyed device state whose leaves are arrays (a key's top three, a
``(3,)`` value vector and a ``(3,)`` id vector), through the standalone
stateful ``Map_TPU`` and ``Filter_TPU`` and through a fused chain with a
stateful member, each held to a per-tuple model across batches and
across a table growth; ``with_key_capacity``, the grid scan's counters,
and the refusals where array leaves are not taken."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy, WindFlowError)
from windflow_tpu.tpu import Filter_TPU_Builder, Map_TPU_Builder

TOP = 3
N, N_KEYS = 1_500, 100          # 100 keys: past the default 64 slots


def top_state():
    return {"v": np.full(TOP, -1, np.int32), "id": np.full(TOP, -1, np.int32)}


def enter(row, held):
    """The row against its key's top three (values descending, ties to
    the earlier id): its rank (0 where it does not enter), the id it
    pushes out, and the row in its place."""
    v, i = row["value"], row["seq"]
    rank = 1 + jnp.sum((held["v"] >= v) & (held["id"] >= 0))
    enters = rank <= TOP
    at = jnp.arange(TOP)

    def place(old, new):
        down = jnp.concatenate([old[:1], old[:-1]])
        return jnp.where(at < rank - 1, old,
                         jnp.where(at == rank - 1, new, down))

    out = {**row, "rank": jnp.where(enters, rank, 0).astype(jnp.int32),
           "evicted": jnp.where(enters, held["id"][TOP - 1], -1)}
    return out, {"v": jnp.where(enters, place(held["v"], v), held["v"]),
                 "id": jnp.where(enters, place(held["id"], i), held["id"])}


def stream(seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, N_KEYS, N), rng.integers(0, 40, N)


def model(keys, values):
    """Per row, in arrival order: (rank, evicted id), and each key's
    final top three as ids."""
    held, rows = {}, {}
    for i, (k, v) in enumerate(zip(keys.tolist(), values.tolist())):
        h = held.setdefault(k, [])
        rank = 1 + sum(1 for x, _ in h if x >= v)
        if rank <= TOP:
            rows[i] = (rank, h[TOP - 1][1] if len(h) == TOP else -1)
            h.insert(rank - 1, (v, i))
            del h[TOP:]
        else:
            rows[i] = (0, -1)
    return rows, {k: [i for _, i in h] for k, h in held.items()}


def run_graph(op_or_ops, seed=3, batch=64):
    keys, values = stream(seed)
    got, lock = [], threading.Lock()

    def src(shipper, ctx=None):
        for i in range(N):
            shipper.push({"key": int(keys[i]), "value": int(values[i]),
                          "seq": i})

    def sink(t):
        if t is not None:
            with lock:
                got.append(t)

    g = PipeGraph("arrays", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    p = g.add_source(Source_Builder(src).with_output_batch_size(batch)
                     .build())
    ops = op_or_ops if isinstance(op_or_ops, list) else [op_or_ops]
    p = p.add(ops[0])
    for op in ops[1:]:
        p = p.chain(op)
    p.add_sink(Sink_Builder(sink).build())
    g.run()
    stats = {o["name"]: o["replicas"] for o in g.get_stats()["Operators"]}
    return got, stats, keys, values


def top_map(name="top", capacity=None, parallelism=1):
    b = (Map_TPU_Builder(enter).with_key_by("key").with_state(top_state())
         .with_parallelism(parallelism).with_name(name))
    return (b.with_key_capacity(capacity) if capacity else b).build()


@pytest.mark.parametrize("parallelism", [1, 2])
def test_a_stateful_map_with_array_leaves_answers_every_row(parallelism):
    got, stats, keys, values = run_graph(top_map(parallelism=parallelism))
    want, _ = model(keys, values)
    assert {r["seq"]: (int(r["rank"]), int(r["evicted"])) for r in got} \
        == want
    reps = stats["top"]
    # 100 keys over the replicas: a 64-slot table doubles where it must
    assert [r["Key_capacity_growths"] for r in reps] == [
        int(r["Key_slots_live"] > 64) for r in reps]
    assert sum(r["Keys_admitted"] for r in reps) == N_KEYS
    assert sum(r["Scan_rows"] for r in reps) == N


def test_a_stateful_filter_with_array_leaves_keeps_the_rows_that_enter():
    flt = (Filter_TPU_Builder(lambda row, held: (
               enter(row, held)[0]["rank"] > 0, enter(row, held)[1]))
           .with_key_by("key").with_state(top_state())
           .with_name("keep").build())
    got, stats, keys, values = run_graph(flt)
    want, _ = model(keys, values)
    assert sorted(r["seq"] for r in got) == [
        i for i, (rank, _) in want.items() if rank > 0]
    assert stats["keep"][0]["Key_capacity_growths"] == 1


def test_a_fused_chain_with_an_array_state_member_rebuilds_the_top_three():
    """stateful map -> filter -> map as ONE program a batch; the rows
    that leave replay into every key's final top three."""
    ops = [top_map("top"),
           Filter_TPU_Builder(lambda f: f["rank"] > 0).with_name("f").build(),
           Map_TPU_Builder(lambda f: {**f, "twice": f["value"] * 2})
           .with_name("m").build()]
    got, stats, keys, values = run_graph(ops)
    want, final = model(keys, values)
    assert {r["seq"]: (int(r["rank"]), int(r["evicted"])) for r in got} \
        == {i: w for i, w in want.items() if w[0] > 0}
    assert all(r["twice"] == 2 * r["value"] for r in got)
    lists = {}
    for r in sorted(got, key=lambda r: r["seq"]):
        h = lists.setdefault(r["key"], [])
        h.insert(int(r["rank"]) - 1, r["seq"])
        del h[TOP:]
    assert lists == final
    rec = stats["top∘f∘m"][0]
    assert rec["Fused_ops"] == 3 and rec["Scan_rows"] == N
    assert rec["Key_capacity_growths"] == 1 and rec["Keys_admitted"] == 100
    assert rec["Scan_host_total_usec"] > 0


def test_with_key_capacity_allocates_the_table_once():
    got, stats, keys, values = run_graph(top_map(capacity=4096))
    want, _ = model(keys, values)
    assert {r["seq"]: (int(r["rank"]), int(r["evicted"])) for r in got} \
        == want
    r = stats["top"][0]
    assert r["Key_capacity_growths"] == 0
    assert r["Key_slots_live"] == r["Keys_admitted"] == N_KEYS
    assert r["Scan_programs"] == r["Dispatch_batches"]
    assert r["Scan_cells"] >= r["Scan_rows"] == N
    assert r["Scan_programs"] <= r["Scan_depth"] <= r["Scan_cells"]


def test_the_table_of_a_given_capacity_is_laid_out_by_leaf():
    from windflow_tpu.tpu.keymap import KeySlotMap
    from windflow_tpu.tpu.ops_tpu import StatefulMapTPUReplica, state_table
    table = state_table(top_state(), 8)
    assert {k: v.shape for k, v in table.items()} == {
        "v": (8, TOP), "id": (8, TOP)}
    assert (np.asarray(table["v"]) == -1).all()
    op = top_map(capacity=1 << 23)
    rep = StatefulMapTPUReplica(op, 0)
    assert rep.engine.table_capacity == 1 << 23
    # the key directory's direct table covers the capacity from the
    # first batch on, past LUT_MAX, and its ids fit up to twice it
    km = rep.engine._keymap
    assert list(km.slots_of(None, np.array([5, 1000]), 2)) == [0, 1]
    assert len(km._lut) == 1 << 23 > km.LUT_MAX
    assert list(km.slots_of(None, np.array([(1 << 24) - 1, 5]), 2)) == [2, 0]
    assert len(km._lut) == 1 << 24 and km._sorted is None
    assert KeySlotMap()._lut_max == KeySlotMap.LUT_MAX
    # a scalar state with no capacity keeps its 64 slots
    plain = (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
             .with_state(np.int32(0)).build())
    assert StatefulMapTPUReplica(plain, 0).engine.table_capacity == 64


def test_what_array_leaves_and_a_key_capacity_are_refused_by_name():
    with pytest.raises(WindFlowError, match="stateless operator keeps none"):
        Map_TPU_Builder(lambda f: f).with_key_capacity(8).build()
    with pytest.raises(WindFlowError, match="stateless operator keeps none"):
        Filter_TPU_Builder(lambda f: f["x"] > 0).with_key_capacity(8).build()
    with pytest.raises(WindFlowError, match="scalar state leaves only"):
        (Map_TPU_Builder(enter).with_key_by("key").with_state(top_state())
         .with_tiering(hot_capacity=16).build())
    with pytest.raises(WindFlowError, match="hot_capacity alone"):
        (Map_TPU_Builder(lambda r, s: (r, s)).with_key_by("key")
         .with_state(np.float32(0)).with_tiering(hot_capacity=16)
         .with_key_capacity(64).build())
    for builder in (Map_TPU_Builder, Filter_TPU_Builder):
        with pytest.raises(WindFlowError, match="sharded_grid_scan"):
            (builder(enter).with_key_by("key").with_state(top_state())
             .with_mesh(n_devices=1).build())
        with pytest.raises(WindFlowError, match="with_mesh\\(key_capacity"):
            (builder(lambda r, s: (r, s)).with_key_by("key")
             .with_state(np.float32(0)).with_key_capacity(64)
             .with_mesh(n_devices=1).build())
