"""The fire query by range (``tpu/ffat_tpu.py`` ``_query_fns``): one tree
walk per distinct ring range over every key slot at once, against the
lane walk (one walk a fired window). The replica is driven directly, on
the CPU backend; the lane walk is forced from the test's side by blanking
the group table in the plan that ``_pack_fire_arrays`` hands the programs,
which is exactly what the planner does for a program with more than
``G_CAP`` distinct ranges."""

import numpy as np
import pytest

from windflow_tpu.basic import WinType
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.ffat_tpu import (G_CAP, Ffat_Windows_TPU,
                                       fire_pack_views)
from windflow_tpu.tpu.schema import TupleSchema

PANE = 1000
SCHEMA = TupleSchema({"key": np.int32, "v": np.float32})


def add(a, b):
    return {"v": a["v"] + b["v"]}


def lift_v(f):
    return {"v": f["v"]}


def first_last(a, b):
    """Not commutative: the first and the last value of the range."""
    return {"first": a["first"], "last": b["last"]}


def mat2(a, b):
    """Not commutative: the product of 2x2 matrices, in stream order
    (of whole numbers: a compiler may contract a float ``a*b + c*d`` in
    one program and not in the other, which is no change of order)."""
    return {"m00": a["m00"] * b["m00"] + a["m01"] * b["m10"],
            "m01": a["m00"] * b["m01"] + a["m01"] * b["m11"],
            "m10": a["m10"] * b["m00"] + a["m11"] * b["m10"],
            "m11": a["m10"] * b["m01"] + a["m11"] * b["m11"]}


def lift_mat2(f):
    import jax.numpy as jnp
    v = f["v"].astype(jnp.int32)
    return {"m00": v % 3, "m01": v // 3 % 3 - 1, "m10": v // 9 % 2,
            "m11": v // 18 % 3}


class Rows:
    """The replica's emitter: every fired row, in the order emitted."""

    def __init__(self):
        self.rows = []

    def emit_device_batch(self, b):
        cols = {n: np.asarray(c)[:b.size] for n, c in b.fields.items()}
        names = sorted(n for n in cols if n not in ("key", "wid", "valid"))
        for i in range(b.size):
            ok = bool(cols["valid"][i])
            self.rows.append(
                (int(cols["key"][i]), int(cols["wid"][i]), ok)
                # where valid is False the values are whatever the walk
                # left: not part of the result
                + tuple(cols[n][i].tobytes() if ok else b""
                        for n in names))

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass


def make_replica(lane_only=False, win=4, slide=1, budget=8, keys=4,
                 win_type=WinType.TB, lift=lift_v, combine=add):
    unit = PANE if win_type is WinType.TB else 1
    op = Ffat_Windows_TPU(
        lift=lift, combine=combine, key_extractor="key",
        win_len=win * unit, slide_len=slide * unit, win_type=win_type,
        num_win_per_batch=budget, key_capacity=keys, name="win")
    op.build_replicas()
    rep = op.replicas[0]
    rep.emitter = Rows()
    if lane_only:
        pack = rep._pack_fire_arrays

        def by_lane(chunks, n_out, W):
            plan, _n_groups = pack(chunks, n_out, W)
            fire, groups, _evict = fire_pack_views(plan, rep.slide_units)
            fire[5] = 0
            groups[:] = 0
            return plan, 0

        rep._pack_fire_arrays = by_lane
    return rep


def batch(keys, panes, vals, wm_pane):
    import jax
    keys = np.asarray(keys, np.int64)
    ts = np.asarray(panes, np.int64) * PANE + 5
    cols = {"key": jax.device_put(keys.astype(np.int32)),
            "v": jax.device_put(np.asarray(vals, np.float32))}
    b = BatchTPU(cols, ts, len(keys), SCHEMA, wm=0, host_keys=keys)
    b.wm = wm_pane * PANE
    return b


def aligned_stream(n_keys, n_panes, per_batch, rng, skip=()):
    """Batches of ``per_batch`` panes, every key a reading in every pane
    (but ``skip``: (key, pane) pairs left out), the watermark at the end
    of each batch."""
    out = []
    for base in range(0, n_panes, per_batch):
        ks, ps = [], []
        for p in range(base, min(base + per_batch, n_panes)):
            for k in range(n_keys):
                if (k, p) not in skip:
                    ks.append(k)
                    ps.append(p)
        out.append(batch(ks, ps, rng.random(len(ks)) * 100,
                         min(base + per_batch, n_panes)))
    return out


def run(rep, batches, flush=True):
    for b in batches:
        rep.handle_msg(0, b)
    if flush:
        rep.flush_on_termination()
    return rep.emitter.rows


def both(batches_fn, **kw):
    """The same stream through the planner's choice and the lane walk."""
    reps = [make_replica(lane_only=lane, **kw) for lane in (False, True)]
    rows = [run(rep, batches_fn()) for rep in reps]
    return reps[0], reps[1], rows[0], rows[1]


# (a)-(f): grouped against lane walk, bit-equal values and ``valid``
CASES = {
    # every key the same slides: one range a program
    "aligned": dict(stream=dict(n_keys=4, n_panes=24, per_batch=3),
                    kw=dict(budget=4), groups_per_program=(1, 1)),
    # keys 1 and 3 stop early: at the flush the clip to max_leaf makes
    # their ranges shorter than the others'
    "max_leaf_differs": dict(
        stream=dict(n_keys=4, n_panes=24, per_batch=3,
                    skip={(k, p) for k in (1, 3) for p in range(21, 24)}),
        kw=dict(budget=4), groups_per_program=(1, 3)),
    # F = 32: 100 panes wrap the ring three times
    "wraps_the_ring": dict(stream=dict(n_keys=3, n_panes=100, per_batch=5),
                           kw=dict(budget=3, win=13, slide=2),
                           groups_per_program=(1, 2)),
    "first_last": dict(stream=dict(n_keys=4, n_panes=30, per_batch=4),
                       kw=dict(budget=4, win=7, slide=1, combine=first_last,
                               lift=lambda f: {"first": f["v"],
                                               "last": f["v"]}),
                       groups_per_program=(1, 2)),
    "matrix_product": dict(stream=dict(n_keys=4, n_panes=30, per_batch=4),
                           kw=dict(budget=4, win=7, slide=1, combine=mat2,
                                   lift=lift_mat2),
                           groups_per_program=(1, 2)),
    # key 2 is silent for 12 panes: its windows in between fire empty
    "empty_windows": dict(
        stream=dict(n_keys=3, n_panes=30, per_batch=3,
                    skip={(2, p) for p in range(6, 18)}),
        kw=dict(budget=3), groups_per_program=(1, 3)),
    # the watermark never moves: every window leaves in the flush
    "flush_of_partial_windows": dict(
        stream=dict(n_keys=4, n_panes=20, per_batch=20, parked=True),
        kw=dict(budget=4, win=6, slide=1), groups_per_program=(1, 1)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_equals_lane_walk(case):
    spec = CASES[case]

    def stream():
        s = dict(spec["stream"])
        parked = s.pop("parked", False)
        bs = aligned_stream(rng=np.random.default_rng(7), **s)
        if parked:
            for b in bs:
                b.wm = 0
        return bs

    grouped, lane, got, want = both(stream, **spec["kw"])
    assert got == want and len(got) > 20
    assert any(not r[2] for r in got) == (case == "empty_windows")
    st = grouped.stats
    assert st.fire_grouped_programs == st.fire_programs > 0
    lo, hi = spec["groups_per_program"]
    assert lo <= st.fire_groups / st.fire_grouped_programs <= hi
    assert lane.stats.fire_grouped_programs == 0 == lane.stats.fire_groups
    assert lane.stats.fire_programs == st.fire_programs
    assert lane.stats.windows_fired == st.windows_fired == len(got)


def test_more_ranges_than_the_table_holds_take_the_lane_walk():
    """(g) two keys and a budget of 64: the flush's rounds give a program
    32 slides of each key, 32 distinct ranges, more than ``G_CAP``: the
    planner blanks the table and the program walks by lane."""
    assert G_CAP < 32

    def stream():
        bs = aligned_stream(2, 80, 80, np.random.default_rng(3))
        bs[0].wm = 0
        return bs

    grouped, lane, got, want = both(stream, budget=64, keys=2, win=8)
    assert got == want and len(got) == 160
    st = grouped.stats
    # 64 + 64 by lane, then 16 slides of each key: 16 ranges, by range
    assert (st.fire_programs, st.fire_grouped_programs, st.fire_groups) \
        == (3, 1, 16)


def test_count_based_windows_never_take_the_grouped_query():
    """(h) a count-based window starts at a per-key arrival index."""
    def stream():
        rng = np.random.default_rng(5)
        return [batch(np.arange(12) % 3, np.zeros(12), rng.random(12), 0)
                for _ in range(10)]

    grouped, _lane, got, want = both(stream, win=5, slide=2, budget=4,
                                     win_type=WinType.CB)
    assert got == want and len(got) > 20
    assert grouped.stats.fire_programs > 0
    assert grouped.stats.fire_grouped_programs == 0


def test_snapshot_and_restore_between_two_programs_of_a_plan():
    """(i) a dataless fire of three rounds, cut after its first program:
    the restored replica fires the other rounds, every (key, wid) once
    and bit-equal to the uninterrupted run."""
    def stream():
        bs = aligned_stream(4, 12, 12, np.random.default_rng(11))
        bs[0].wm = 0        # parked: all of it is ingested, nothing fires
        return bs

    def punctuate(rep, pane):
        rep.cur_wm = pane * PANE
        rep.on_punctuation(rep.cur_wm)

    whole = make_replica(budget=4)
    run(whole, stream(), flush=False)
    punctuate(whole, 7)      # windows 0..3 of four keys: three programs
    want = list(whole.emitter.rows)
    assert len(want) == 16 and whole.stats.fire_programs == 4

    cut = make_replica(budget=4)
    run(cut, stream(), flush=False)
    fireable, calls = cut._fireable, []

    def once(frontier, partial, budget):
        calls.append(budget)
        if len(calls) > 1:
            return (np.zeros(0, np.int64),) * 5
        return fireable(frontier, partial, budget)

    cut._fireable = once
    punctuate(cut, 7)
    assert len(cut.emitter.rows) == 4    # one round of the plan is out
    state = cut.snapshot_state()

    rest = make_replica(budget=4)
    rest.restore_state(state)
    punctuate(rest, 7)
    assert cut.emitter.rows + rest.emitter.rows == want
    assert rest.stats.fire_grouped_programs == rest.stats.fire_programs == 3
    # and both go on alike to the end of the stream
    whole.emitter.rows.clear()
    rest.emitter.rows.clear()
    whole.flush_on_termination()
    rest.flush_on_termination()
    assert rest.emitter.rows == whole.emitter.rows != []


@pytest.mark.parametrize("budget", [1, 3, 5, 8, 13, 100])
def test_fireable_by_rounds(budget):
    """(j) the plan by rounds: a slot's windows leave in ``wid`` order,
    no program over its budget and none short while windows remain, a
    program holds at most two rounds, and once all rounds are out
    ``next_fire`` and ``fired`` stand where the slot-order plan leaves
    them."""
    eligible = np.array([3, 0, 7, 1, 4, 4, 0, 2])
    rep = make_replica(budget=budget, keys=8, win=2, slide=1)
    for k in range(8):
        rep._keymap.slot(k)
    fired0 = np.arange(8) * 10
    rep.fired[:8] = fired0
    rep.next_fire[:8] = fired0      # slide is one pane: start == wid
    # the flush's rule: a window for every slide up to max_leaf (a slot
    # with nothing eligible has no pane at or past next_fire)
    rep.max_leaf[:8] = np.where(eligible > 0, fired0 + eligible - 1, -1)
    seen = {k: [] for k in range(8)}
    left = eligible.copy()
    while left.sum():
        slots, start0, k, wid0, _ml = rep._fireable(None, True, budget)
        assert k.sum() == min(budget, left.sum()) and (k > 0).all()
        assert (np.diff(slots) > 0).all()
        rounds = k.max()
        # every firing slot gave min(left, rounds - 1) at least
        assert (k >= np.minimum(left[slots], rounds - 1)).all()
        short = slots[k < np.minimum(left[slots], rounds)]
        full = slots[k == rounds]
        assert short.size == 0 or full.size == 0 or full.max() < short.min()
        for s, w0, n, st in zip(slots, wid0, k, start0):
            assert st == w0
            seen[int(s)] += list(range(int(w0), int(w0 + n)))
        left[slots] -= k
    assert rep._fireable(None, True, budget)[0].size == 0
    for s in range(8):
        assert seen[s] == list(range(fired0[s], fired0[s] + eligible[s]))
    assert (rep.fired[:8] == fired0 + eligible).all()
    assert (rep.next_fire[:8] == fired0 + eligible).all()


def test_counters_in_get_stats_and_no_compile_when_the_query_switches():
    """(k) ``Fire_grouped_programs`` and ``Fire_groups`` beside
    ``Fire_programs``; a stream whose programs go by range, then by lane,
    then by range again compiles nothing after its first batch."""
    rep = make_replica(budget=64, keys=2, win=8)
    bs = aligned_stream(2, 80, 4, np.random.default_rng(2))
    rep.handle_msg(0, bs[0])
    compiled = rep.stats.compile_count
    assert compiled > 0
    for b in bs[1:10]:                    # four windows a key a batch
        rep.handle_msg(0, b)
    rep.dispatch.drain(forced=True)       # commits are deferred
    by_range = rep.stats.fire_grouped_programs
    assert by_range == rep.stats.fire_programs > 0
    for b in bs[10:15]:                   # parked: nothing fires
        b.wm = bs[9].wm
        rep.handle_msg(0, b)
    rep.handle_msg(0, bs[15])             # 24 slides a key in one program
    rep.dispatch.drain(forced=True)
    by_lane = rep.stats.fire_programs - by_range
    assert by_lane == 1 and rep.stats.fire_grouped_programs == by_range
    for b in bs[16:]:
        rep.handle_msg(0, b)
    rep.flush_on_termination()
    st = rep.stats.to_dict()
    assert st["Fire_programs"] - by_lane == st["Fire_grouped_programs"] \
        > by_range
    assert st["Fire_groups"] > st["Fire_grouped_programs"]
    assert st["Compile_count"] == compiled
