"""Time-based fire plans by the CHUNK (``tpu/ffat_tpu.py``, PR 39): the
host hands a time-based program the count-based layout, a few words a
firing key slot with the slot's key among them (``plan_views``), and the
program expands its own lanes, group indices and evicted leaves
(``plan_lanes``); the device key table is gone. Held here against what
the parent commit built on the host, by lane (``_lanes`` + ``_pack_plan``,
kept below as the plain reference), for every way a time-based operator
plans: by range in one round and in many, a ``G_CAP`` cut, a ragged plan
on the lane walk, a budget given, gap windows and the
end-of-stream flush. The replica is driven directly, on the CPU backend,
as ``test_ffat_grouped_fire.py`` drives it."""

import numpy as np
import pytest

from test_ffat_grouped_fire import (PANE, SCHEMA, aligned_stream, batch,
                                    make_replica, run)
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.ffat_tpu import (G_CAP, join_key_words,
                                       key_words_of, plan_lanes)
from windflow_tpu.tpu.schema import TupleSchema


# ---------------------------------------------------------------------------
# the parent's host code, the plain reference
# ---------------------------------------------------------------------------
def parent_lanes(rep, start0, k, ml):
    """The parent's ``_lanes``: ``(round, start, length)`` a lane."""
    rnd = np.arange(int(k.sum())) - np.repeat(np.cumsum(k) - k, k)
    starts = np.repeat(start0, k) + rnd * rep.slide_units
    lens = np.minimum(rep.win_units, np.repeat(ml, k) + 1 - starts)
    return rnd, starts, lens


def parent_ranges(rep, starts, lens):
    return np.unique((starts % rep.F) * rep.F + lens, return_inverse=True)


def parent_plan(rep, chunks, W, lanes, ranges):
    """The parent's ``_pack_plan``, decoded: the six fire rows (slot,
    start, len, wid, mask, group) at width ``W``, the group table and
    the set of evicted leaves, (node, slot) pairs of the node-major
    forest."""
    c_slots, c_start0, c_k, c_wid0, c_ml = chunks
    rnd, starts, lens = lanes
    n = rnd.size
    fire = np.zeros((6, W), np.int64)
    fire[0, :n] = np.repeat(c_slots, c_k)
    fire[1, :n] = starts % rep.F
    fire[2, :n] = lens
    fire[3, :n] = np.repeat(c_wid0, c_k) + rnd
    fire[4, :n] = 1
    groups = np.zeros((G_CAP + 1, 2), np.int64)
    if ranges is not None:
        pairs, group = ranges
        groups[:pairs.size, 0] = pairs // rep.F
        groups[:pairs.size, 1] = pairs % rep.F
        groups[G_CAP, 0] = pairs.size
        fire[5, :n] = group
    ne = np.maximum(0, np.minimum(c_start0 + c_k * rep.slide_units, c_ml + 1)
                    - c_start0)
    ep = (np.repeat(c_start0, ne) + np.arange(int(ne.sum()))
          - np.repeat(np.cumsum(ne) - ne, ne))
    evicted = set(zip((rep.F + ep % rep.F).tolist(),
                      np.repeat(c_slots, ne).tolist()))
    return (fire, groups, evicted), int(groups[G_CAP, 0])


def parent_pack_fire_arrays(rep, chunks, W):
    """The parent's ``_pack_fire_arrays`` of a time-based plan."""
    lanes = parent_lanes(rep, chunks[1], chunks[2], chunks[4])
    ranges = parent_ranges(rep, lanes[1], lanes[2])
    return parent_plan(rep, chunks, W, lanes,
                       None if ranges[0].size > G_CAP else ranges)


def parent_plan_program(rep, slots, k):
    """The parent's ``_plan_program``, letter for letter but the plan it
    returns decoded (``parent_plan``)."""
    su = rep.slide_units
    start0, end = rep.next_fire[slots], rep.max_leaf[slots] + 1
    for W in dict.fromkeys((rep.W_wide, rep.W_cap)):
        take = rep._clip(k, W)
        reach = (int(take.max()) - 1) * su + rep.win_units
        _, i_s = np.unique(start0, return_inverse=True)
        _, i_e = np.unique(np.minimum(end - start0, reach),
                           return_inverse=True)
        _, rep_, cls = np.unique(
            (i_s * (int(i_e.max()) + 1) + i_e) * (W + 1) + take,
            return_index=True, return_inverse=True)
        q_take = take[rep_]
        q_rnd, q_starts, q_lens = parent_lanes(
            rep, start0[rep_], q_take, end[rep_] - 1)
        pairs, q_group = parent_ranges(rep, q_starts, q_lens)
        if pairs.size <= G_CAP:
            break
        first = np.full(pairs.size, W, dtype=np.int64)
        np.minimum.at(first, q_group, q_rnd)
        r = int(np.partition(first, G_CAP)[G_CAP])
        if r:
            kept = first < r
            pairs = pairs[kept]
            q_group = (np.cumsum(kept) - 1)[q_group]
            take = np.minimum(take, r)
            rep.stats.fire_range_cuts += 1
            break
    else:
        pairs = None
    rnd = np.arange(int(take.sum())) - np.repeat(np.cumsum(take) - take, take)
    lane = np.repeat((np.cumsum(q_take) - q_take)[cls], take) + rnd
    chunks = rep._take(slots, take)
    plan, n_groups = parent_plan(
        rep, chunks, W, (rnd, q_starts[lane], q_lens[lane]),
        None if pairs is None else (pairs, q_group[lane]))
    return (chunks, rnd.size, plan, n_groups, W,
            rep._chunk_keys(chunks[0])), take


def as_parent(rep):
    """``rep`` planning as the parent did: its programs' plans are the
    parent's, decoded (they are compared, never run)."""
    rep._plan_program = lambda slots, k: parent_plan_program(rep, slots, k)
    rep._pack_fire_arrays = lambda chunks, W, keys, pairs: \
        parent_pack_fire_arrays(rep, chunks, W)
    return rep


def expanded(rep, pack, W):
    """What the program makes of a plan: its lanes' rows, the evicted
    leaves and the key column."""
    import jax
    out = jax.jit(plan_lanes, static_argnums=range(1, 8))(
        pack, W, rep.K_cap, rep.F, rep.win_units, rep.slide_units, True,
        rep._key_words())
    slots, starts, lens, wids, mask, group, evict, words = (
        np.asarray(a) for a in out)
    keys = np.asarray(jax.jit(join_key_words, static_argnums=1)(
        words, rep._key_dtype))
    live = evict[:, evict[0] < 2 * rep.F]
    assert (live[0] >= rep.F).all() and (live[1] < rep.K_cap).all()
    pairs = set(zip(*live.tolist()))
    assert len(pairs) == live.shape[1]
    return np.stack([slots, starts, lens, wids, mask, group]), pairs, keys


# ---------------------------------------------------------------------------
# 1. the program expands what the parent laid out by lane
# ---------------------------------------------------------------------------
# every case: the operator (window, slide in panes; ``budget`` None:
# sized by the plan) and its slots' state: a slot's first window ``w0``
# (its ``next_fire`` w0 * slide), its data's extent past it, the
# frontier in panes (None: the end-of-stream flush)
RNG = np.random.default_rng(39)
N = 40
PLANS = {
    # q5: a slide closes once a batch, every slot fires one window; the
    # slots were admitted at five different windows, their data ends
    # anywhere: a few ranges, by range
    "one_round_by_range": dict(
        win=5, slide=1, w0=100 + (RNG.random(N) < 0.2),
        span=RNG.integers(1, 9, N), frontier=105),
    # sg2: every slot fires eight windows in step, one range a round
    "multi_round_by_range": dict(
        win=12, slide=1, w0=np.full(N, 64), span=np.full(N, 20),
        frontier=64 + 12 + 7),
    # four starts and twelve rounds of shrinking windows: 48 ranges,
    # cut at the last whole round that keeps 32 (the rounds left make
    # the next program)
    "g_cap_cut": dict(
        win=12, slide=1, w0=64 + 6 * (np.arange(N) % 4),
        span=np.full(N, 12), frontier=64 + 18 + 23),
    # every slot anchored at its own window: 40 ranges in the first
    # round alone, by lane at the narrow width
    "ragged_on_the_lane_walk": dict(
        win=30, slide=1, w0=200 + np.arange(N), span=np.ones(N, np.int64),
        frontier=200 + N + 30),
    # ysb: a budget given, a cap on the width: the rounds split over
    # programs
    "tiers_of_a_budget": dict(
        win=4, slide=4, w0=np.full(N, 30), span=np.full(N, 3),
        frontier=40, budget=16),
    "gap_windows": dict(
        win=2, slide=3, w0=50 + RNG.integers(0, 3, N),
        span=RNG.integers(1, 11, N), frontier=(50 + 4) * 3),
    "end_of_stream_flush": dict(
        win=6, slide=2, w0=40 + RNG.integers(0, 2, N),
        span=RNG.integers(1, 13, N), frontier=None),
}


def planned(case, parent):
    c = PLANS[case]
    rep = make_replica(win=c["win"], slide=c["slide"],
                       budget=c.get("budget"), keys=64)
    if parent:
        as_parent(rep)
    rep._cap_seen = 1024        # the width may grow to hold the plan
    slots = [rep._keymap.slot(7_000_000 + 13 * i) for i in range(N)]
    nf = np.asarray(c["w0"]) * rep.slide_units
    rep.next_fire[slots], rep.fired[slots] = nf, c["w0"]
    rep.max_leaf[slots] = nf + np.asarray(c["span"]) - 1
    partial = c["frontier"] is None
    progs = list(rep._programs(c["frontier"], partial, lambda: None))
    return rep, progs


@pytest.mark.parametrize("case", sorted(PLANS))
def test_a_time_based_plan_by_the_chunk_expands_to_the_parents_lanes(case):
    new, got = planned(case, parent=False)
    old, want = planned(case, parent=True)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        (g_chunks, g_n, pack, g_groups, g_W, g_keys, g_owed) = g
        (w_chunks, w_n, (fire, table, evicted), w_groups, w_W, w_keys,
         w_owed) = w
        for a, b in zip(g_chunks, w_chunks):
            assert (a == b).all()
        assert (g_n, g_groups, g_W, g_owed) == (w_n, w_groups, w_W, w_owed)
        # one row a firing slot, never one a lane
        assert pack.size == new._plan_len(g_W) \
            == 1 + 70 + 6 * min(new.K_cap, g_W)
        rows, gone, keys = expanded(new, pack, g_W)
        assert (rows == fire).all()
        assert gone == evicted
        assert (pack[1:67].reshape(G_CAP + 1, 2) == table).all()
        # each lane's key, as the parent's table gathered it
        assert (keys == np.where(fire[4] == 1,
                                 new._keys_np[fire[0]], 0)).all()
        assert (g_keys == w_keys).all()
    assert new.stats.fire_range_cuts == old.stats.fire_range_cuts
    for a in ("next_fire", "fired", "max_leaf"):
        assert (getattr(new, a) == getattr(old, a)).all()
    assert new._keymap.free == old._keymap.free
    shapes = {"one_round_by_range": (1, 0), "multi_round_by_range": (1, 0),
              "g_cap_cut": (2, 1), "ragged_on_the_lane_walk": (1, 0)}
    if case in shapes:
        assert (len(got), new.stats.fire_range_cuts) == shapes[case]
    if case == "ragged_on_the_lane_walk":
        assert got[0][3] == 0 and got[0][4] == new.W_cap
    elif not case.startswith("tiers"):
        assert all(p[3] > 0 for p in got)
    else:
        assert len(got) > 2 and {p[4] for p in got} == {16}


# ---------------------------------------------------------------------------
# 2. what leaves: rows, keys, event times
# ---------------------------------------------------------------------------
def emitted(rep):
    """Every fired batch's rows as ``(key, wid, valid, ts)``, the key
    from the column the program built."""
    out = []
    emit = rep.emitter.emit_device_batch

    def seen(b):
        cols = {n: np.asarray(c)[:b.size] for n, c in b.fields.items()}
        assert (np.asarray(b.host_keys) == cols["key"]).all()
        out.extend(zip(cols["key"].tolist(), cols["wid"].tolist(),
                       cols["valid"].tolist(), b.ts_host[:b.size].tolist()))
        emit(b)

    rep.emitter.emit_device_batch = seen
    return out


def keyed_stream(per_batch):
    """12 keys far from their slots (``1000 k + 17``), every key a
    reading in every pane, the watermark at the end of each batch."""
    out = aligned_stream(12, 40, per_batch, np.random.default_rng(3),
                         whole=True)
    for b in out:
        b.host_keys = np.asarray(b.host_keys) * 1000 + 17
        b.fields["key"] = b.fields["key"] * 1000 + 17
    return out


@pytest.mark.parametrize("kw,per_batch", [
    (dict(budget=None, keys=24, win=5, slide=1), 1),     # one round
    (dict(budget=None, keys=24, win=12, slide=1), 8),    # eight rounds
    (dict(budget=8, keys=24, win=4, slide=2), 3),        # a budget given
    (dict(budget=None, keys=24, win=2, slide=3), 4),     # gap windows
], ids=["one_round", "multi_round", "budget", "gaps"])
def test_fired_rows_carry_their_keys_and_their_window_ends(kw, per_batch):
    """The rows are the lane walk's (whose answers the grouped tests
    hold against the ordered fold), each with its chunk's key and the
    parent's event time, ``wid * slide + win - 1``."""
    rep = make_replica(**kw)
    rows = emitted(rep)
    lane = make_replica(lane_only=True, **kw)
    assert run(rep, keyed_stream(per_batch)) == run(
        lane, keyed_stream(per_batch))
    win, slide = kw["win"] * PANE, kw["slide"] * PANE
    assert len(rows) > 100
    for key, wid, _ok, ts in rows:
        assert key % 1000 == 17 and key // 1000 < 12
        assert ts == wid * slide + win - 1


def test_fire_one_round_plans_counts_the_plans_of_one_window_a_chunk():
    """``Fire_one_round_plans``, counted where ``Fire_programs`` is: a
    slide a batch (``q5``) makes every plan one round; the end-of-stream
    flush, eight slides a batch (``sg2``) and count-based windows that
    fire many windows a key make none."""
    one = make_replica(budget=None, keys=24, win=5, slide=1)
    for b in keyed_stream(1):
        one.handle_msg(0, b)
    one.dispatch.drain(forced=True)
    st = one.stats
    assert st.fire_one_round_plans == st.fire_programs > 30
    assert st.fire_plan_rows == 12 * st.fire_programs == st.windows_fired
    n = st.fire_programs
    one.flush_on_termination()
    assert st.fire_one_round_plans == n < st.fire_programs
    many = make_replica(budget=None, keys=24, win=12, slide=1)
    run(many, keyed_stream(8))
    assert many.stats.fire_one_round_plans == 0 < many.stats.fire_programs
    assert many.stats.to_dict()["Fire_one_round_plans"] == 0
    from windflow_tpu.basic import WinType
    cb = make_replica(budget=64, keys=4, win=8, slide=1,
                      win_type=WinType.CB)
    run(cb, aligned_stream(3, 40, 10, np.random.default_rng(1)))
    assert cb.stats.fire_one_round_plans == 0 < cb.stats.fire_programs


def test_a_key_that_takes_back_a_reclaimed_slot_fires_with_its_own_key():
    """Every key lives two panes and is forgotten: its slot goes back and
    a new key takes it while the old key's last windows are still to
    leave (commits are deferred). Each fired row carries the key whose
    events it holds, never the slot's next holder."""
    from test_ffat_key_reclaim import model, sums

    rep = make_replica(budget=None, keys=4)
    emitted(rep)            # the column the program built is the keys'
    events, rng = [], np.random.default_rng(8)
    for p in range(50):
        now = [(5000 + 3 * q + j, p, float(rng.integers(1, 9)))
               for q in (p - 1, p) if q >= 0 for j in range(3)]
        events += now
        rep.handle_msg(0, batch(*zip(*now), p))
    rep.flush_on_termination()
    st = rep.stats
    assert st.keys_admitted == st.keys_reclaimed == 150
    assert rep._keymap.n_slots < 40         # slots were taken back
    assert sums(rep.emitter.rows) == model(events)


# ---------------------------------------------------------------------------
# 3. keys: the column a program builds from the words its plan carries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,keys", [
    (np.int32, [0, -7, 2**31 - 1, -2**31]),
    (np.uint32, [0, 2**32 - 1, 2**31 + 3]),
    (np.int16, [-300, 32767]),
    (np.uint8, [0, 255]),
    (np.int64, [1, -5, 2**31 + 7, 2**40 + 3, -(2**35) - 1]),
    (np.uint64, [0, 2**63 + 5, 2**32]),
])
def test_key_words_rejoin_as_the_key_columns_dtype(dtype, keys):
    import jax

    want = np.asarray(keys, dtype)
    n_words = 2 if want.itemsize > 4 else 1
    with jax.enable_x64(want.itemsize > 4):
        words = key_words_of(want.view(np.int64) if want.itemsize > 4
                             else want.astype(np.int64), n_words)
        assert words.dtype == np.int32 and words.shape == (n_words,
                                                           want.size)
        got = np.asarray(jax.jit(join_key_words, static_argnums=1)(
            words, np.dtype(dtype)))
    assert got.dtype == want.dtype and (got == want).all()


S64 = TupleSchema({"key": np.int64, "v": np.float32})


def batch64(keys, panes, vals, wm_pane):
    import jax
    keys = np.asarray(keys, np.int64)
    cols = {"key": jax.device_put(keys),
            "v": jax.device_put(np.asarray(vals, np.float32))}
    b = BatchTPU(cols, np.asarray(panes, np.int64) * PANE + 5, len(keys),
                 S64, wm=0, host_keys=keys)
    b.wm = wm_pane * PANE
    return b


@pytest.mark.parametrize("x64", [False, True], ids=["int32_column",
                                                    "int64_column"])
def test_int64_keys_past_2_to_the_31(x64):
    """With 64-bit types on, an int64 key column rides as two words a key
    and comes back whole; without, the column is int32 on the device and
    the keys come back as the parent's table had them, wrapped."""
    import jax

    big = [2**40 + 1, 2**33 - 7, -(2**36), 5]
    with jax.enable_x64(x64):
        rep = make_replica(budget=None, keys=4)
        rows = []
        emit = rep.emitter.emit_device_batch
        rep.emitter.emit_device_batch = lambda b: (rows.append(
            np.asarray(b.fields["key"])[:b.size]), emit(b))
        for p in range(8):
            rep.handle_msg(0, batch64(big, [p] * 4, [1.0] * 4, p))
        rep.flush_on_termination()
    assert rep._key_words() == (2 if x64 else 1)
    want = np.asarray(big, np.int64)
    if not x64:
        want = want.astype(np.int32)
    got = np.concatenate(rows)
    assert got.dtype == want.dtype and set(got.tolist()) == set(
        want.tolist())
    assert got.size == rep.stats.windows_fired == 4 * 8


def test_keys_that_are_no_ints_are_built_on_the_host():
    """A key extractor that gives tuples: the plan carries no key, the
    column is the host's (None: no named field), and the plan is the
    same chunk rows."""
    from windflow_tpu.basic import WinType
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU
    from test_ffat_grouped_fire import Rows, add, lift_v

    op = Ffat_Windows_TPU(
        lift=lift_v, combine=add, key_extractor=lambda t: ("k", t["key"]),
        win_len=4 * PANE, slide_len=PANE, win_type=WinType.TB,
        key_capacity=4, name="win")
    op.build_replicas()
    rep = op.replicas[0]
    rep.emitter = Rows()
    seen = []
    rep.emitter.emit_device_batch = seen.append
    assert rep._key_words() == 0 and not rep._plan_keys()
    for p in range(6):
        b = batch([1, 2], [p, p], [1.0, 2.0], p)
        b._host_keys = [("k", 1), ("k", 2)]
        rep.handle_msg(0, b)
    rep.flush_on_termination()
    assert seen and all("key" not in b.fields for b in seen)
    keys = [k for b in seen for k in b.host_keys]
    assert set(keys) == {("k", 1), ("k", 2)} and len(keys) == sum(
        b.size for b in seen)
    assert rep._plan_len(16) == 1 + 70 + 5 * 4


def test_a_named_key_field_of_non_int_keys_builds_the_column_on_the_host():
    rep = make_replica(budget=None, keys=4)
    seen = []
    emit = rep.emitter.emit_device_batch
    rep.emitter.emit_device_batch = lambda b: (seen.append(b), emit(b))
    for p in range(6):
        b = batch([1, 2], [p, p], [1.0, 2.0], p)
        b._host_keys = [1.5, 2.5]                  # no ints: no table
        rep.handle_msg(0, b)
    rep.flush_on_termination()
    assert not rep._plan_keys() and rep._key_words() == 1
    for b in seen:
        col = np.asarray(b.fields["key"])[:b.size]
        assert col.tolist() == [int(k) for k in b.host_keys]
