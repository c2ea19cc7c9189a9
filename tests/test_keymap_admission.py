"""The keyed grid scan's host half without per-key Python or a sort: a
batch's new keys admitted in one operation (``KeySlotMap._admit``), key
for key the slots the per-key loop gives, and the touched rows numbered
in one pass (``_KeyedStateScan._grid_meta``), held against the
``np.unique`` numbering it replaced at table sizes under and over four
times the batch, through a table growth and under ``with_tiering``."""

import numpy as np
import pytest

from windflow_tpu import WindFlowError
from windflow_tpu.tpu.keymap import KeySlotMap, group_positions
from windflow_tpu.tpu.ops_tpu import _KeyedStateScan, op_batch_keys_np

from test_keyed_state_arrays import model, run_graph, top_map
from test_tiered_state import ReplaySource, _run_graph, _running_sum_op


# ---------------------------------------------------------------------------
# the key map's admission
# ---------------------------------------------------------------------------
def batches(space, rng, steps=40, fresh=200, again=100):
    """Batches of ``fresh`` draws over ``space`` ids (the search path's
    are sparse 62-bit ids) with ``again`` ids of the batch before, so
    every batch mixes known keys, repeats and new ones."""
    last = np.zeros(0, np.int64)
    for _ in range(steps):
        hi = 2**62 if space == "search" else 5_000
        new = rng.integers(0, hi, fresh)
        old = rng.choice(last, again) if len(last) else new[:again]
        keys = np.concatenate([new, old, new[:again]])
        rng.shuffle(keys)
        last = keys
        yield keys


@pytest.mark.parametrize("space", ["table", "search"])
def test_a_batch_is_admitted_with_the_slots_of_the_per_key_loop(space):
    calls = []
    whole = KeySlotMap()
    per_key = KeySlotMap(on_new=lambda k, s: calls.append((k, s)))
    many = KeySlotMap(on_new_many=lambda k, s: None)
    rng = np.random.default_rng(5)
    with_new = admitted = 0
    for keys in batches(space, rng):
        before = len(whole)
        got = [m.slots_of(keys, keys, len(keys)) for m in
               (whole, per_key, many)]
        with_new += len(whole) > before
        admitted += len(whole) - before
        assert got[0].tolist() == got[1].tolist() == got[2].tolist()
        # the same directory in the same order (a checkpoint pickles it)
        assert list(whole.slot_of_key.items()) \
            == list(per_key.slot_of_key.items()) \
            == list(many.slot_of_key.items())
        # give back a third of the live keys: their slots are reused
        # last in, first out by the next batch
        live = np.array(list(whole.slot_of_key), dtype=np.int64)
        gone = rng.choice(live, len(live) // 3, replace=False)
        for m in (whole, per_key, many):
            m.release(gone)
        assert whole.free == per_key.free == many.free
    assert (whole._lut is None) == (space == "search")
    assert whole.batch_admits == many.batch_admits == with_new
    assert per_key.batch_admits == 0 and len(calls) == admitted


def test_an_owner_with_a_per_key_callback_is_called_once_a_key():
    calls = []
    m = KeySlotMap(on_new=lambda k, s: calls.append((k, s)))
    a = np.array([7, 3, 7, 11, 3])
    assert m.slots_of(a, a, 5).tolist() == [1, 0, 1, 2, 0]
    m.release(np.array([3]))
    b = np.array([11, 40, 41])
    assert m.slots_of(b, b, 3).tolist() == [2, 0, 3]
    # the new keys in sorted order, one call each, with the slot each is
    # given: the freed one first, then the next past the high-water mark
    assert calls == [(3, 0), (7, 1), (11, 2), (40, 0), (41, 3)]
    assert m.batch_admits == 0


@pytest.mark.parametrize("space", ["table", "search"])
def test_a_refused_batch_leaves_directory_free_list_and_index(space):
    limit = [1 << 30]

    def refuse(keys, slots):
        if slots.max() >= limit[0]:
            raise WindFlowError("over capacity")

    m = KeySlotMap(on_new_many=refuse)
    rng = np.random.default_rng(9)
    it = batches(space, rng, steps=3)
    for keys in it:
        m.slots_of(keys, keys, len(keys))
        m.release(np.array(list(m.slot_of_key)[::4], dtype=np.int64))
    limit[0] = m.n_slots            # no slot past the high-water mark
    directory, free = list(m.slot_of_key.items()), list(m.free)
    lut = None if m._lut is None else (m._lut.copy(), m._base)
    srt = None if m._sorted is None else tuple(a.copy() for a in m._sorted)
    known = np.array([k for k, _ in directory[:50]], dtype=np.int64)
    hi = 2**62 if space == "search" else 5_000
    fresh = np.setdiff1d(rng.integers(0, hi, len(free) + 50),
                         [k for k, _ in directory])
    assert len(fresh) > len(free)
    b = np.concatenate([known, fresh])
    for _ in range(2):              # the retry refuses alike
        with pytest.raises(WindFlowError, match="capacity"):
            m.slots_of(b, b, len(b))
        assert list(m.slot_of_key.items()) == directory
        assert m.free == free
        if lut is None:
            assert m._lut is None
        else:
            assert np.array_equal(m._lut, lut[0]) and m._base == lut[1]
        if srt is not None:
            assert all(np.array_equal(x, y) for x, y in zip(m._sorted, srt))
    assert m.batch_admits == 3


# ---------------------------------------------------------------------------
# the grid's numbering of the touched rows
# ---------------------------------------------------------------------------
@pytest.fixture
def checked_grid(monkeypatch):
    """Every ``_grid_meta`` of the run held against the ``np.unique``
    numbering: what failed, and per grid scan ``(rows, table capacity,
    whether the scan admitted keys)``."""
    bad, seen = [], []
    meta_of = _KeyedStateScan._grid_meta

    def checked(self, batch):
        n_keys = len(self.slot_of_key)
        grid_idx, valid, touched, tmask, M, KB = meta_of(self, batch)
        n = batch.size
        keys, keys_arr = op_batch_keys_np(self.op, batch)
        gslots = self._keymap.slots_of(keys, keys_arr, n)  # all known now
        ref_touched, ref_local = np.unique(gslots, return_inverse=True)
        _, ref_within = group_positions(ref_local, len(ref_touched))
        ref_M = 1 << int(ref_within.max()).bit_length() if n else 1
        ref_KB = 1 << max(0, len(ref_touched) - 1).bit_length()
        k = int(tmask.sum())
        cell = grid_idx[:n]
        checks = {
            "touched set": np.array_equal(np.sort(touched[:k]), ref_touched),
            "own key": np.array_equal(touched[cell // M], gslots),
            "own rank": np.array_equal(cell % M, ref_within),
            "shapes": (M, KB) == (ref_M, ref_KB) and not tmask[k:].any(),
            "valid": valid[:n].all() and not valid[n:].any(),
            "scratch reset": bool((self._mark == -1).all()),
            "scratch size": len(self._mark) == self.table_capacity,
        }
        bad.extend(name for name, ok in checks.items() if not ok)
        seen.append((n, self.table_capacity,
                     len(self.slot_of_key) > n_keys))
        return grid_idx, valid, touched, tmask, M, KB

    monkeypatch.setattr(_KeyedStateScan, "_grid_meta", checked)
    return bad, seen


@pytest.mark.parametrize("capacity,batch", [
    (None, 64),      # 64 slots, doubled to 128: the table under 4x a batch
    (4096, 64),      # the table 16 times the batch
    (1 << 16, 512),  # 128 times
])
def test_the_one_pass_numbering_is_the_sorted_one_up_to_order(
        checked_grid, capacity, batch):
    bad, seen = checked_grid
    got, stats, keys, values = run_graph(top_map(capacity=capacity),
                                         batch=batch)
    want, _ = model(keys, values)
    assert {r["seq"]: (int(r["rank"]), int(r["evicted"])) for r in got} \
        == want
    assert not bad and seen
    caps = sorted({c for _, c, _ in seen})
    assert caps == ([64, 128] if capacity is None else [capacity])
    assert any(c <= 4 * n for n, c, _ in seen) == (capacity is None)
    r = stats["top"][0]
    assert r["Scan_programs"] == len(seen)
    assert r["Scan_batch_admits"] == sum(a for _, _, a in seen) >= 1


@pytest.mark.parametrize("hot", [8, 64])
def test_the_one_pass_numbering_under_tiering(checked_grid, hot):
    bad, seen = checked_grid
    n, nk = 600, 24
    dense_g, dense_rows = _run_graph(
        f"numbering_dense_{hot}", ReplaySource(n, nk, seed=3),
        _running_sum_op("scan"))
    dense_g.run()
    tiered_g, tiered_rows = _run_graph(
        f"numbering_tiered_{hot}", ReplaySource(n, nk, seed=3),
        _running_sum_op("scan", tiering=dict(hot_capacity=hot)))
    tiered_g.run()
    assert not bad
    assert len(dense_rows) == n and sorted(tiered_rows) == sorted(dense_rows)
    assert {c for _, c, _ in seen} == {64, hot}
    stats = {o["name"]: o["replicas"][0]
             for o in tiered_g.get_stats()["Operators"]}
    # the tier plan registers a batch's keys before the lookup: nothing
    # is left for the key map to admit
    assert stats["scan"]["Scan_batch_admits"] == 0
    assert stats["scan"]["Scan_programs"] >= 1
