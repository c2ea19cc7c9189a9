"""Pool substrate tests (reference wf/recycling.hpp capability; the
staging path's use of the pools is in test_packed_staging.py)."""

import threading

import numpy as np

from windflow_tpu.recycling import ArrayPool, ObjectPool


def test_array_pool_reuse_and_zeroing():
    """A buffer comes back as it was released: the pool does NOT fill it
    (the stager writes its rows and zeroes the pad rows at ship time, so
    each byte is written once); keyed by dtype and full shape."""
    from windflow_tpu.tpu.batch import StagingBuffers
    from windflow_tpu.tpu.schema import TupleSchema

    pool = ArrayPool(max_per_bucket=4)
    a = pool.acquire(np.int32, 64)
    a[:] = 7
    pool.release(a)
    b = pool.acquire(np.int32, 64)
    assert b is a  # reused
    assert (b == 7).all()  # not zeroed on reacquire
    c = pool.acquire(np.float32, 64)
    assert c is not a and c.dtype == np.float32
    d = pool.acquire(np.int32, (2, 32))  # same bytes, another shape
    assert d is not a and d.shape == (2, 32)
    pool.release(b)

    # the stager owns the zeroing: a dirty pooled buffer ships with its
    # pad rows zero, column by column
    from windflow_tpu.recycling import InFlightRecycler
    st = StagingBuffers(TupleSchema({"x": np.int32, "y": np.int32}), 32,
                        InFlightRecycler(pool, force=True))
    assert st.groups[0] is a and (a == 7).all()
    st.fill({"x": np.arange(5), "y": np.arange(5) + 10}, 5)
    f = st.put(5)
    assert np.asarray(f["x"]).tolist() == list(range(5)) + [0] * 27
    assert np.asarray(f["y"]).tolist() == list(range(10, 15)) + [0] * 27


def test_array_pool_bucket_cap():
    pool = ArrayPool(max_per_bucket=2)
    arrs = [pool.acquire(np.int64, 8) for _ in range(5)]
    for a in arrs:
        pool.release(a)
    assert len(pool._free[(str(np.dtype(np.int64)), (8,))]) == 2


def test_object_pool_threaded():
    made = []

    def factory():
        o = {"v": 0}
        made.append(o)
        return o

    pool = ObjectPool(factory, reset=lambda o: o.update(v=0), max_size=16)

    def worker():
        for _ in range(500):
            o = pool.acquire()
            o["v"] += 1
            pool.release(o)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert len(made) <= 32  # heavy reuse, not 2000 allocations


def test_in_flight_recycler_fifo_mechanics():
    """Bounded FIFO: beyond max_in_flight the oldest transfer is waited on
    and its buffers return to the pool (force=True: the mechanics are
    platform-independent; content safety is only guaranteed on accelerator
    backends, see test_staging_recycling_gated_on_cpu)."""
    import jax
    from windflow_tpu.recycling import InFlightRecycler

    pool = ArrayPool()
    rec = InFlightRecycler(pool, max_in_flight=2, force=True)
    for _ in range(6):
        host = pool.acquire(np.int32, 32)
        dev = jax.device_put(np.asarray(host))  # copy: content irrelevant
        rec.track([dev], [host])
    assert len(rec._q) == 2  # 4 released via the blocking pop
    key = (str(np.dtype(np.int32)), (32,))
    # released buffers were immediately re-acquired each iteration: only
    # the latest release is still free, and 3 acquires were pool hits
    assert len(pool._free[key]) == 1
    assert pool.hits == 3 and pool.misses == 3
    rec.drain()
    assert len(rec._q) == 0
    assert len(pool._free[key]) == 3


def test_staging_recycling_gated_on_cpu():
    """On the CPU backend device_put may alias the staging buffer with NO
    safe release point (not even block_until_ready) — the recycler must
    self-disable so staged batches keep exclusive buffers."""
    import jax
    from windflow_tpu.recycling import ArrayPool, InFlightRecycler
    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.schema import TupleSchema

    rec = InFlightRecycler(ArrayPool(), max_in_flight=4)
    assert jax.default_backend() == "cpu" and not rec.enabled

    # correctness holds regardless of gating: every staged batch keeps its
    # own values even when batches are staged back-to-back under load
    schema = TupleSchema({"v": np.int32})
    batches = []
    for i in range(40):
        rows = [({"v": i * 100 + j}, j) for j in range(16)]
        batches.append((i, BatchTPU.stage(rows, schema, 0, capacity=16,
                                          recycler=rec)))
    for i, b in batches:
        vals = np.asarray(b.fields["v"])[:16]
        assert (vals == np.arange(16) + i * 100).all(), (i, vals)
