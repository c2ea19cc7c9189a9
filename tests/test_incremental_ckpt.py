"""Incremental + async checkpointing (``WF_CKPT_DELTA`` /
``WF_CKPT_ASYNC`` / ``WF_CKPT_FULL_EVERY``).

Covers the three rungs of the delta plane plus its store semantics:

- delta-node unit round-trips (``checkpoint.delta``);
- content-addressed blob refs: an unchanged payload is a manifest ref,
  not a rewrite, and restores byte-identically through the ancestor;
- retention vs delta chains: ``prune`` keeps every epoch a retained
  manifest references (refs) or depends on (deps) — the regression
  where retain-K dropped a live delta base;
- ``verify()`` flags every epoch whose chain passes through a corrupt
  ancestor;
- the megabatch ``lax.scan`` carry accumulates touched-slot bitmaps
  across all K folded batches;
- dense -> tiered adoption of (delta-latest) checkpoints, and tiered
  WAL-delta restore;
- the randomized Zipf differential: {full, delta, delta+async} over one
  schedule produce identical outputs AND byte-identical materialized
  engine state at every retained rung, including after a supervised
  kill mid-stream.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

from windflow_tpu.checkpoint import CheckpointStore
from windflow_tpu.checkpoint import delta as ckpt_delta
from windflow_tpu.checkpoint.store import (CorruptCheckpointError,
                                           blob_name)


# ---------------------------------------------------------------------------
# delta-node unit round-trips
# ---------------------------------------------------------------------------
def test_delta_make_resolve_roundtrip():
    base = {"table": {"acc": np.arange(10.0), "cnt": np.arange(10)},
            "slot_of_key": {1: 0, 2: 1}, "cap": 10}
    node = ckpt_delta.make_delta(
        3,
        rows={"table": {"slots": np.array([2, 5]),
                        "leaves": [np.array([20.0, 50.0]),
                                   np.array([7, 9])]}},
        replace={"slot_of_key": {1: 0, 2: 1, 3: 2}, "cap": 10})
    assert ckpt_delta.is_delta(node)
    assert ckpt_delta.delta_bases(node) == {3}
    full = ckpt_delta.materialize(node, {3: base})
    assert set(full) == {"table", "slot_of_key", "cap"}
    want_acc = np.arange(10.0)
    want_acc[[2, 5]] = [20.0, 50.0]
    want_cnt = np.arange(10)
    want_cnt[[2, 5]] = [7, 9]
    np.testing.assert_array_equal(full["table"]["acc"], want_acc)
    np.testing.assert_array_equal(full["table"]["cnt"], want_cnt)
    assert full["slot_of_key"] == {1: 0, 2: 1, 3: 2}
    # the base is never mutated in place
    np.testing.assert_array_equal(base["table"]["acc"], np.arange(10.0))


def test_delta_nested_in_blob_tree():
    # a delta node at a sub-path applies against the SAME path of the
    # base blob; sibling subtrees pass through untouched
    base_blob = {"scan": {"table": np.zeros(4), "cap": 4},
                 "wm": 17}
    node = ckpt_delta.make_delta(
        1, rows={"table": {"slots": np.array([1]),
                           "leaves": [np.array([9.0])]}},
        replace={"cap": 4})
    state = {"scan": node, "wm": 23}
    full = ckpt_delta.materialize(state, {1: base_blob})
    np.testing.assert_array_equal(full["scan"]["table"],
                                  np.array([0.0, 9.0, 0.0, 0.0]))
    assert full["wm"] == 23
    # missing base must fail loudly, not produce partial state
    with pytest.raises(ValueError):
        ckpt_delta.resolve(state, {2: base_blob})


def test_delta_carry_fields():
    # carry copies fields verbatim from the base at ZERO delta bytes —
    # the key directory rides here when no key registered since base
    nk = 10_000
    base = {"table": np.zeros(nk),
            "slot_of_key": {i: i for i in range(nk)}, "cap": nk}
    rows = {"table": {"slots": np.array([2]),
                      "leaves": [np.array([7.0])]}}
    node = ckpt_delta.make_delta(1, rows=rows,
                                 carry=["slot_of_key", "cap"])
    fat = ckpt_delta.make_delta(
        1, rows=rows, replace={"slot_of_key": base["slot_of_key"],
                               "cap": nk})
    import pickle
    assert len(pickle.dumps(node)) < len(pickle.dumps(fat)) / 100
    full = ckpt_delta.materialize(node, {1: base})
    assert full["slot_of_key"] == base["slot_of_key"]
    assert full["cap"] == nk
    want = np.zeros(nk)
    want[2] = 7.0
    np.testing.assert_array_equal(full["table"], want)


def test_delta_shards_patch():
    base = {"table_shards": [{"v": np.zeros(3)}, {"v": np.ones(3)}]}
    node = ckpt_delta.make_delta(
        2, shards={"table_shards": [None,
                                    {"slots": np.array([0]),
                                     "leaves": [np.array([5.0])]}]})
    full = ckpt_delta.materialize({"s": node}, {2: {"s": base}})
    np.testing.assert_array_equal(full["s"]["table_shards"][0]["v"],
                                  np.zeros(3))
    np.testing.assert_array_equal(full["s"]["table_shards"][1]["v"],
                                  np.array([5.0, 1.0, 1.0]))


def test_delta_eligibility_gates(tmp_path, monkeypatch):
    monkeypatch.setenv("WF_CKPT_DELTA", "1")
    monkeypatch.setenv("WF_CKPT_FULL_EVERY", "3")
    st = CheckpointStore(str(tmp_path))
    st.begin(1)
    st.write_blob(1, "op", 0, {"x": 1})
    st.commit(1, {})
    ctx = ckpt_delta.SnapshotContext(2, st)
    # committed base + cadence not due -> eligible
    assert ckpt_delta.delta_eligible(1, 0, ctx)
    assert ckpt_delta.delta_eligible(1, 1, ctx)
    # full cadence due
    assert not ckpt_delta.delta_eligible(1, 2, ctx)
    # base never committed
    assert not ckpt_delta.delta_eligible(7, 0, ctx)
    # no capture context (retirement snapshots) -> always full
    assert not ckpt_delta.delta_eligible(1, 0, None)
    monkeypatch.setenv("WF_CKPT_DELTA", "0")
    assert not ckpt_delta.delta_eligible(1, 0, ctx)


# ---------------------------------------------------------------------------
# store: refs, retention closure, verify closure
# ---------------------------------------------------------------------------
def test_store_ref_dedup_unchanged_blob(tmp_path, monkeypatch):
    monkeypatch.setenv("WF_CKPT_DELTA", "1")
    st = CheckpointStore(str(tmp_path))
    state = {"pos": 42, "buf": np.arange(100)}
    st.begin(1)
    st.write_blob(1, "op", 0, state)
    st.commit(1, {})
    st.begin(2)
    st.write_blob(2, "op", 0, state)  # identical payload
    st.write_blob(2, "other", 0, {"pos": 2})
    st.commit(2, {})
    fname = blob_name("op", 0)
    m2 = CheckpointStore.load_manifest(st._dirname(2))
    assert m2["refs"] == {fname: 1}
    assert not os.path.exists(os.path.join(st._dirname(2), fname))
    assert st.delta_blobs >= 1
    # restore resolves the ref through the ancestor's physical blob
    loaded = st.load_states(st._dirname(2), m2)
    np.testing.assert_array_equal(loaded[("op", 0)]["buf"],
                                  np.arange(100))
    # and the offline sweep verifies the ref'd blob at its ancestor
    assert all(r["ok"] for r in st.verify().values())


def _chain_store(root, retain=10):
    """Epoch 1 = full, epochs 2..5 = deltas patching base 1 (the
    engine's base-is-last-full discipline)."""
    st = CheckpointStore(root, retain=retain)
    st.begin(1)
    st.write_blob(1, "op", 0, {"pos": 1, "table": np.arange(8.0)})
    st.commit(1, {})
    for cid in (2, 3, 4, 5):
        node = ckpt_delta.make_delta(
            1, rows={"table": {"slots": np.array([cid % 8]),
                               "leaves": [np.array([cid * 10.0])]}},
            replace={"pos": cid})
        st.begin(cid)
        st.write_blob(cid, "op", 0, node)
        st.commit(cid, {})
    return st


def test_prune_keeps_delta_bases(tmp_path, monkeypatch):
    # retain=2 keeps {4, 5}; both depend on base 1 — the regression fix:
    # retention must keep the transitive dep closure, not just last K
    monkeypatch.setenv("WF_CKPT_DELTA", "1")
    st = _chain_store(str(tmp_path), retain=2)
    assert set(st.completed_ids()) == {1, 4, 5}
    assert os.path.isdir(st._dirname(1))
    assert not os.path.isdir(st._dirname(2))
    cid, d, man = CheckpointStore.resolve(str(tmp_path))
    assert cid == 5
    full = st.load_states(d, man)[("op", 0)]
    assert full["pos"] == 5
    np.testing.assert_array_equal(
        full["table"],
        np.array([0.0, 1.0, 2.0, 3.0, 4.0, 50.0, 6.0, 7.0]))


def test_prune_keeps_ref_ancestors(tmp_path, monkeypatch):
    # unchanged payloads: epochs 2..5 hold refs into epoch 1's physical
    # blob; pruning to retain=2 must keep epoch 1 alive for them
    monkeypatch.setenv("WF_CKPT_DELTA", "1")
    st = CheckpointStore(str(tmp_path), retain=2)
    state = {"frozen": np.arange(64)}
    for cid in (1, 2, 3, 4, 5):
        st.begin(cid)
        st.write_blob(cid, "op", 0, state)
        st.write_blob(cid, "mover", 0, {"pos": cid})
        st.commit(cid, {})
    assert set(st.completed_ids()) == {1, 4, 5}
    cid, d, man = CheckpointStore.resolve(str(tmp_path))
    loaded = st.load_states(d, man)
    np.testing.assert_array_equal(loaded[("op", 0)]["frozen"],
                                  np.arange(64))
    assert loaded[("mover", 0)]["pos"] == 5


def test_verify_flags_every_dependent(tmp_path, monkeypatch):
    monkeypatch.setenv("WF_CKPT_DELTA", "1")
    st = _chain_store(str(tmp_path))
    fname = blob_name("op", 0)
    path = os.path.join(st._dirname(1), fname)
    with open(path, "r+b") as f:
        f.seek(3)
        b = f.read(1)
        f.seek(3)
        f.write(bytes([b[0] ^ 0xFF]))
    rep = CheckpointStore(str(tmp_path)).verify()
    # one corrupt ancestor poisons itself AND every epoch whose chain
    # passes through it
    assert sorted(cid for cid, r in rep.items() if not r["ok"]) \
        == [1, 2, 3, 4, 5]
    with pytest.raises(CorruptCheckpointError):
        st2 = CheckpointStore(str(tmp_path))
        cid, d, man = CheckpointStore.resolve(str(tmp_path))
        st2.load_states(d, man)


def test_async_upload_failure_fails_epoch_loudly(tmp_path, monkeypatch):
    """A crash/OSError mid async upload must fail the EPOCH, never
    commit a partial manifest: coordinator-level contract, checked here
    at the store layer — an uncommitted staging dir is invisible."""
    st = CheckpointStore(str(tmp_path))
    st.begin(1)
    st.write_blob(1, "op", 0, {"pos": 1})
    # upload died before commit: nothing visible, latest() is None
    assert st.completed_ids() == []
    assert st.latest() is None
    # a later epoch commits fine and prune clears the dead staging dir
    st.begin(2)
    st.write_blob(2, "op", 0, {"pos": 2})
    st.commit(2, {})
    assert st.completed_ids() == [2]
    assert not os.path.isdir(st._dirname(1, staging=True))


# ---------------------------------------------------------------------------
# megabatch scan carry: dirty bits survive all K folded batches
# ---------------------------------------------------------------------------
def test_megabatch_dirty_bitmap_carry():
    import jax

    from windflow_tpu.runtime.dispatch import DeviceDispatchQueue
    from windflow_tpu.tpu import Map_TPU_Builder
    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.fused_ops import FusedTPUReplica
    from windflow_tpu.tpu.ops_tpu import Map_TPU
    from windflow_tpu.tpu.schema import TupleSchema

    K, B, GROUPS = 4, 64, 8  # 2K batches, each touching its own 8 keys

    class _Sink:
        def emit_device_batch(self, b):
            pass

        def set_stats(self, s):
            pass

    sm = (Map_TPU_Builder(
            lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                             st + row["v"]))
          .with_state(np.float32(0)).with_key_by("k")
          .with_name("sm").build())
    fr = FusedTPUReplica([sm, Map_TPU(lambda f: f, name="id")], 0)
    fr.dispatch = DeviceDispatchQueue(stats=fr.stats, depth=K,
                                      megabatch=K)
    fr.set_emitter(_Sink())

    schema = TupleSchema({"k": np.int32, "v": np.float32})
    rng = np.random.default_rng(0)
    touched = set()
    n_batches = 2 * K
    for j in range(n_batches):
        keys = (j * GROUPS
                + rng.integers(0, GROUPS, B)).astype(np.int64)
        touched.update(keys.tolist())
        cols = {"k": jax.device_put(keys.astype(np.int32)),
                "v": jax.device_put(np.ones(B, np.float32))}
        fr.handle_msg(0, BatchTPU(cols, np.arange(B, dtype=np.int64), B,
                                  schema, host_keys=keys))
    progs_before_drain = fr.stats.device_programs_run
    fr.dispatch.drain()
    # the megabatch path actually folded batches into lax.scan programs
    assert progs_before_drain < n_batches

    eng = [s.engine for s in fr.specs if s.engine is not None][0]
    assert eng.dirty is not None
    dirty = np.asarray(jax.device_get(eng.dirty)).astype(bool)
    # every key touched by ANY of the folded batches is marked: the
    # scan carry must accumulate bitmaps across all K iterations
    for key in sorted(touched):
        slot = eng.slot_of_key[key]
        assert dirty[slot], f"key {key} (slot {slot}) lost its dirty bit"
    # and only registered slots are marked
    marked = set(np.nonzero(dirty)[0].tolist())
    assert marked == {eng.slot_of_key[k] for k in touched}


# ---------------------------------------------------------------------------
# pipeline differentials
# ---------------------------------------------------------------------------
class _ScanSource:
    """Replayable keyed pusher with commit-waited checkpoints (each
    requested epoch is on disk before the stream continues, making the
    epoch <-> position mapping deterministic across modes)."""

    def __init__(self, keys, vals, store, ckpt_at=(), crash_at=None):
        self.keys, self.vals = keys, vals
        self.store = store
        self.ckpt_at = set(ckpt_at)
        self.crash_at = crash_at
        self.crashes = 0
        self.pos = 0

    def __call__(self, shipper):
        st = CheckpointStore(self.store)
        n = len(self.keys)
        while self.pos < n:
            if self.crash_at is not None and self.pos == self.crash_at \
                    and self.crashes < 1:
                self.crashes += 1
                raise _Boom(f"killed at tuple {self.pos}")
            i = self.pos
            shipper.push({"k": int(self.keys[i]),
                          "v": float(self.vals[i])})
            self.pos += 1
            if self.pos in self.ckpt_at:
                before = st.latest() or 0
                shipper.request_checkpoint()
                deadline = time.time() + 20
                while (st.latest() or 0) <= before \
                        and time.time() < deadline:
                    time.sleep(0.002)

    def snapshot_position(self):
        return self.pos

    def restore(self, pos):
        self.pos = pos


class _Boom(Exception):
    pass


def _top3(row, st):
    """A key's three largest values (an array leaf, descending): the row
    leaves with their sum."""
    import jax.numpy as jnp
    top = jnp.sort(jnp.concatenate([st, row["v"][None]]))[::-1][:3]
    return {"k": row["k"], "v": jnp.sum(jnp.maximum(top, 0))}, top


def _scan_graph(store, src, rows, tiered=False, supervised=False,
                retain=8, hot_capacity=8, top3=False):
    from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                              Source_Builder, TimePolicy)
    from windflow_tpu.tpu import Map_TPU_Builder

    g = PipeGraph("inc_ckpt", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_checkpointing(store_dir=store, retain=retain)
    if supervised:
        from windflow_tpu import RestartPolicy
        g.with_supervision(RestartPolicy(max_restarts=4, backoff_s=0.02,
                                         backoff_max_s=0.2))
    if top3:
        mb = (Map_TPU_Builder(_top3)
              .with_state(np.full(3, -1, np.float32)).with_key_by("k")
              .with_key_capacity(16).with_name("scan"))
    else:
        mb = (Map_TPU_Builder(
                lambda row, st: ({"k": row["k"], "v": st + row["v"]},
                                 st + row["v"]))
              .with_state(np.float32(0)).with_key_by("k")
              .with_name("scan"))
    if tiered:
        mb = mb.with_tiering(policy="lru", hot_capacity=hot_capacity)

    def sink(t):
        if t is not None:
            rows.append((int(t["k"]), float(t["v"])))

    g.add_source(Source_Builder(src).with_name("src")
                 .with_output_batch_size(8).build()) \
        .add(mb.build()) \
        .add_sink(Sink_Builder(sink).with_name("snk").build())
    return g


_MODE_ENV = {
    "full": {"WF_CKPT_DELTA": "0", "WF_CKPT_ASYNC": "0"},
    "delta": {"WF_CKPT_DELTA": "1", "WF_CKPT_ASYNC": "0",
              "WF_CKPT_FULL_EVERY": "3"},
    "delta_async": {"WF_CKPT_DELTA": "1", "WF_CKPT_ASYNC": "1",
                    "WF_CKPT_FULL_EVERY": "3"},
}


def _set_mode(monkeypatch, mode):
    for k, v in _MODE_ENV[mode].items():
        monkeypatch.setenv(k, v)


def _tree_equal(a, b, path=""):
    if isinstance(a, dict):
        assert isinstance(b, dict) and set(a) == set(b), \
            f"{path}: keys {set(a)} != {set(b)}"
        for k in a:
            _tree_equal(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{path}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            _tree_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        assert np.asarray(a).dtype == np.asarray(b).dtype, \
            f"{path}: dtype {np.asarray(a).dtype} != {np.asarray(b).dtype}"
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=path)
    else:
        assert a == b, f"{path}: {a!r} != {b!r}"


def _no_wm(src_state):
    # source blobs carry an ingress-time watermark and emitter batch-id
    # counters that are timing-derived; the replay contract is the
    # stream position
    return src_state["position"]


def test_zipf_differential_full_delta_async(tmp_path, monkeypatch):
    """One randomized Zipf schedule through {full, delta, delta+async}:
    identical sink outputs, and the materialized engine state of EVERY
    retained rung is byte-identical to the full-snapshot mode's — a
    delta chain restores to exactly what a full snapshot would have."""
    n, nk = 1200, 64
    rng = np.random.default_rng(7)
    keys = (rng.zipf(1.4, size=n) - 1) % nk
    vals = rng.integers(1, 100, size=n).astype(np.float64)
    # 5 commit-waited epochs; under FULL_EVERY=3 the delta modes write
    # 1=F, 2=d(1), 3=d(1), 4=F, 5=d(4)
    ckpt_at = [200, 400, 600, 800, n]

    outs, stores, stats = {}, {}, {}
    for mode in ("full", "delta", "delta_async"):
        _set_mode(monkeypatch, mode)
        store = str(tmp_path / mode)
        rows = []
        g = _scan_graph(store, _ScanSource(keys, vals, store, ckpt_at),
                        rows)
        g.run()
        outs[mode] = sorted(rows)
        stores[mode] = store
        stats[mode] = g.get_stats().get("Checkpoints", {})

    assert outs["delta"] == outs["full"]
    assert outs["delta_async"] == outs["full"]
    # the delta modes actually wrote deltas / uploaded asynchronously
    assert stats["delta"].get("Checkpoint_delta_blobs", 0) >= 1
    assert stats["delta_async"].get("Checkpoint_async_uploads", 0) >= 1
    assert stats["delta_async"].get("Checkpoint_async_pending", 1) == 0

    ref = CheckpointStore(stores["full"])
    rungs = ref.completed_ids()
    assert len(rungs) == len(ckpt_at)
    for mode in ("delta", "delta_async"):
        _set_mode(monkeypatch, mode)
        st = CheckpointStore(stores[mode])
        assert st.completed_ids() == rungs
        for cid in rungs:
            d_ref = ref._dirname(cid)
            d_m = st._dirname(cid)
            want = ref.load_states(d_ref, ref.load_manifest(d_ref))
            got = st.load_states(d_m, st.load_manifest(d_m))
            # engine state must materialize byte-identically; the
            # replica-generic fields carry wall-clock watermarks that
            # legitimately differ between runs
            _tree_equal(want[("scan", 0)]["scan"],
                        got[("scan", 0)]["scan"], f"epoch{cid}.scan")
            assert _no_wm(want[("src", 0)]) == _no_wm(got[("src", 0)])


def test_zipf_differential_survives_kill(tmp_path, monkeypatch):
    """delta+async with a supervised kill mid-stream: recovery restores
    from a delta rung and the FINAL epoch's materialized state equals
    the full-mode final state at the same stream position."""
    n, nk = 1000, 48
    rng = np.random.default_rng(23)
    keys = (rng.zipf(1.4, size=n) - 1) % nk
    vals = rng.integers(1, 100, size=n).astype(np.float64)
    ckpt_at = [250, 500, n]

    _set_mode(monkeypatch, "full")
    gold_store = str(tmp_path / "gold")
    g = _scan_graph(gold_store,
                    _ScanSource(keys, vals, gold_store, ckpt_at), [])
    g.run()
    ref = CheckpointStore(gold_store)
    last = ref.completed_ids()[-1]
    want = ref.load_states(ref._dirname(last),
                           ref.load_manifest(ref._dirname(last)))

    _set_mode(monkeypatch, "delta_async")
    store = str(tmp_path / "killed")
    src = _ScanSource(keys, vals, store, ckpt_at, crash_at=700)
    g2 = _scan_graph(store, src, [], supervised=True)
    g2.run()  # recovers in-process
    sup = g2.get_stats().get("Supervision", {})
    assert sup.get("Supervision_restarts", 0) == 1
    st = CheckpointStore(store)
    last2 = st.completed_ids()[-1]
    got = st.load_states(st._dirname(last2),
                         st.load_manifest(st._dirname(last2)))
    _tree_equal(want[("scan", 0)]["scan"], got[("scan", 0)]["scan"],
                "final.scan")
    assert _no_wm(want[("src", 0)]) == _no_wm(got[("src", 0)])


@pytest.mark.parametrize("mode", ["full", "delta"])
def test_array_leaf_table_recovers_from_its_snapshots(tmp_path, monkeypatch,
                                                       mode):
    """A table whose leaf is a (3,) vector, 16 slots growing to 64 under
    48 keys: a supervised kill mid-stream restores from the latest
    snapshot (a delta rung under ``delta``), the final epoch's table
    equals an uncrashed full-mode run's, and the outputs are its."""
    n, nk = 900, 48
    rng = np.random.default_rng(29)
    keys = rng.integers(0, nk, size=n)
    vals = rng.integers(1, 100, size=n).astype(np.float64)
    ckpt_at = [200, 450, 600, n]

    _set_mode(monkeypatch, "full")
    gold_store = str(tmp_path / "gold")
    gold_rows = []
    _scan_graph(gold_store, _ScanSource(keys, vals, gold_store, ckpt_at),
                gold_rows, top3=True).run()
    ref = CheckpointStore(gold_store)
    last = ref.completed_ids()[-1]
    want = ref.load_states(ref._dirname(last),
                           ref.load_manifest(ref._dirname(last)))
    table = want[("scan", 0)]["scan"]["table"]
    assert table.shape == (64, 3) and (table[:nk].max(axis=1) > 0).all()

    _set_mode(monkeypatch, mode)
    store = str(tmp_path / mode)
    rows = []
    g = _scan_graph(store, _ScanSource(keys, vals, store, ckpt_at,
                                       crash_at=700),
                    rows, supervised=True, top3=True)
    g.run()
    assert g.get_stats()["Supervision"]["Supervision_restarts"] == 1
    if mode == "delta":
        assert g.get_stats()["Checkpoints"]["Checkpoint_delta_blobs"] >= 1
    st = CheckpointStore(store)
    last2 = st.completed_ids()[-1]
    got = st.load_states(st._dirname(last2),
                         st.load_manifest(st._dirname(last2)))
    _tree_equal(want[("scan", 0)]["scan"], got[("scan", 0)]["scan"],
                "final.scan")
    # a plain sink is at least once: the replayed stretch re-emits
    assert set(rows) == set(gold_rows)


def test_dense_delta_checkpoint_adopted_by_tiered(tmp_path, monkeypatch):
    """A DELTA-latest dense checkpoint restores into a tiered engine:
    load_states materializes the chain to a full dense blob, the tiered
    engine adopts it, and the continued stream matches the golden."""
    n, nk = 960, 24
    keys = np.arange(n) % nk
    vals = np.ones(n)
    half = n // 2

    _set_mode(monkeypatch, "full")
    gold_rows = []
    gold_store = str(tmp_path / "gold")
    _scan_graph(gold_store,
                _ScanSource(keys, vals, gold_store), gold_rows).run()
    golden_tail = sorted(gold_rows[half:])

    # phase A: dense run with deltas, stops at half (latest epoch is a
    # delta under FULL_EVERY=3)
    _set_mode(monkeypatch, "delta")
    store = str(tmp_path / "store")
    src_a = _ScanSource(keys[:half], vals[:half], store,
                        ckpt_at=[300, 420, half])
    _scan_graph(store, src_a, []).run()
    st = CheckpointStore(store)
    assert len(st.completed_ids()) == 3
    m_last = st.load_manifest(st._dirname(st.completed_ids()[-1]))
    assert m_last.get("deps"), "latest epoch should be a delta"

    # phase B: a TIERED graph restores from the delta-latest checkpoint
    # and streams the second half
    rows_b = []
    src_b = _ScanSource(keys, vals, store)
    # the hot tier must fit the dense checkpoint's distinct key set —
    # adoption refuses (KeyCapacityError) otherwise
    g = _scan_graph(store, src_b, rows_b, tiered=True, hot_capacity=32)
    g.run(restore_from=store)
    assert sorted(rows_b) == golden_tail


def test_tiered_wal_delta_roundtrip(tmp_path, monkeypatch):
    """Tiered engine under deltas: epochs snapshot dirty hot rows plus
    the cold-store WAL; restoring the delta-latest into a fresh tiered
    graph continues byte-identically."""
    n, nk = 960, 24  # hot tier 8 slots -> most keys live cold
    keys = np.arange(n) % nk
    vals = np.ones(n)
    half = n // 2

    _set_mode(monkeypatch, "full")
    gold_rows = []
    gold_store = str(tmp_path / "gold")
    _scan_graph(gold_store, _ScanSource(keys, vals, gold_store),
                gold_rows, tiered=True).run()
    golden_tail = sorted(gold_rows[half:])

    _set_mode(monkeypatch, "delta")
    store = str(tmp_path / "store")
    src_a = _ScanSource(keys[:half], vals[:half], store,
                        ckpt_at=[300, 420, half])
    _scan_graph(store, src_a, [], tiered=True).run()
    st = CheckpointStore(store)
    m_last = st.load_manifest(st._dirname(st.completed_ids()[-1]))
    assert m_last.get("deps"), "latest tiered epoch should be a delta"

    rows_b = []
    g = _scan_graph(store, _ScanSource(keys, vals, store), rows_b,
                    tiered=True)
    g.run(restore_from=store)
    assert sorted(rows_b) == golden_tail
