"""The fire query by range (``tpu/ffat_tpu.py`` ``_query_fns``): one tree
walk per distinct ring range over every key slot at once, against the
lane walk (one walk a fired window). The replica is driven directly, on
the CPU backend; the lane walk is forced from the test's side by blanking
the group table in the plan that ``_pack_fire_arrays`` hands the
programs, which is exactly what the planner does for a program whose
first round alone holds more than ``G_CAP`` distinct ranges. One planner
serves every operator (``_programs``): a budget given caps the width of
its programs, and from ``(l)`` on an operator with no budget given
(time-based windows) sizes that width by its plans (``_fit_width``).

Counters pinned "at the parent" were read on commit 20733da (PR 29),
the last before the width by the plan."""

import itertools

import numpy as np
import pytest

from windflow_tpu.basic import WinType
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.ffat_tpu import G_CAP, Ffat_Windows_TPU
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
        self.widths = []    # lanes of the program behind each batch

    def emit_device_batch(self, b):
        self.widths.append(b.capacity)
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
        rep._pack_fire_arrays = lambda chunks, W, keys, pairs: pack(
            chunks, W, keys, None)
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


def aligned_stream(n_keys, n_panes, per_batch, rng, skip=(), whole=False):
    """Batches of ``per_batch`` panes, every key a reading in every pane
    (but ``skip``: (key, pane) pairs left out), the watermark at the end
    of each batch. ``whole``: whole numbers, whose float32 sums are exact
    in any order of combination."""
    out = []
    for base in range(0, n_panes, per_batch):
        ks, ps = [], []
        for p in range(base, min(base + per_batch, n_panes)):
            for k in range(n_keys):
                if (k, p) not in skip:
                    ks.append(k)
                    ps.append(p)
        vals = rng.random(len(ks)) * 100
        out.append(batch(ks, ps, np.floor(vals) if whole else vals,
                         min(base + per_batch, n_panes)))
    return out


def run(rep, batches, flush=True):
    for b in batches:
        rep.handle_msg(0, b)
    if flush:
        rep.flush_on_termination()
    else:
        rep.dispatch.drain(forced=True)     # commits are deferred
    return rep.emitter.rows


def both(batches_fn, flush=True, **kw):
    """The same stream through the planner's choice and the lane walk."""
    reps = [make_replica(lane_only=lane, **kw) for lane in (False, True)]
    rows = [run(rep, batches_fn(), flush) for rep in reps]
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
    # key 2 is silent for 12 panes: its windows in between fire empty.
    # The watermark is parked (PR 34): with one that follows the data the
    # fires pass key 2's last reading before the next arrives, its slot
    # is given back, and it returns as a new key anchored past the
    # silence (tests/test_ffat_key_reclaim.py); an empty window fires
    # only between two windows of a key that both hold events when it is
    # their turn
    "empty_windows": dict(
        stream=dict(n_keys=3, n_panes=30, per_batch=3, parked=True,
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
    """(g) a budget GIVEN, more keys than ``G_CAP``, each silent from a
    pane of its own on, so that no two share a (clipped) range in any
    round: the first round alone holds more ranges than the table, no
    whole round can be cut, and every program walks by lane at the
    budget's width. (Where fewer keys share the ranges of a round, a plan
    over the table is cut at a whole round and stays by range: (n).)"""
    # two rounds of every key a program: all of its keys in each
    n_keys, win = G_CAP + 8, 100                        # F = 128
    budget = 2 * n_keys

    def stream():
        rng = np.random.default_rng(3)
        last = 40 + np.arange(n_keys)       # key k is silent after this
        ks = np.repeat(np.arange(n_keys), last + 1)
        ps = np.concatenate([np.arange(n + 1) for n in last])
        # the watermark closes windows 0..30 of every key: 31 rounds
        return [batch(ks, ps, rng.random(len(ks)) * 100, win + 30)]

    grouped, lane, got, want = both(stream, flush=False, budget=budget,
                                    keys=n_keys, win=win)
    assert got == want and len(got) == 31 * n_keys
    st = grouped.stats
    assert (st.fire_programs, st.fire_grouped_programs, st.fire_groups,
            st.fire_range_cuts) == (16, 0, 0, 0)
    assert set(grouped.emitter.widths) == {budget}


def test_count_based_windows_never_take_the_grouped_query(monkeypatch):
    """(h) a count-based window starts at a per-key arrival index. (Both
    replicas on the lane walk: since PR 33 a count-based program may
    answer by sliding scan instead, which reads its lanes' rounds from
    the row ``lane_only`` blanks; ``test_ffat_sliding_fire.py`` holds
    that query to this one.)"""
    from windflow_tpu.tpu import ffat_tpu
    monkeypatch.setattr(ffat_tpu, "SLIDE_X", 0)

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
    programs = cut._programs
    # the first program only: the rest of the plan is never taken
    cut._programs = lambda *a: itertools.islice(programs(*a), 1)
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
    """(j) the plan by rounds (``_programs`` of a replica with a budget
    given): a slot's windows leave in ``wid`` order,
    no program over its budget and none short while windows remain, a
    program holds at most two rounds, and once all rounds are out
    ``next_fire`` and ``fired`` stand where the slot-order plan leaves
    them."""
    eligible = np.array([3, 0, 7, 1, 4, 4, 0, 2])
    rep = make_replica(budget=budget, keys=8, win=2, slide=1)
    assert rep.W_wide == rep.W_cap == budget
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
    for prog in rep._programs(None, True, lambda: None):
        slots, start0, k, wid0, _ml = prog[0]
        assert prog[4] == budget
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
    assert left.sum() == 0
    assert not list(rep._programs(None, True, lambda: None))
    for s in range(8):
        assert seen[s] == list(range(fired0[s], fired0[s] + eligible[s]))
    assert (rep.fired[:8] == fired0 + eligible).all()
    assert (rep.next_fire[:8] == fired0 + eligible).all()


def padded(keys, panes, wm_pane, rng, cap=2048):
    """``batch`` at a capacity of ``cap`` rows, so that batches of any
    size are one compiled shape."""
    import jax
    n = len(keys)
    k = np.zeros(cap, np.int64)
    k[:n] = keys
    ts = np.zeros(cap, np.int64)
    ts[:n] = np.asarray(panes, np.int64) * PANE + 5
    v = np.zeros(cap, np.float32)
    v[:n] = rng.random(n) * 100
    b = BatchTPU({"key": jax.device_put(k.astype(np.int32)),
                  "v": jax.device_put(v)}, ts, n, SCHEMA, wm=0,
                 host_keys=k[:n])
    b.wm = wm_pane * PANE
    return b


def test_counters_in_get_stats_and_no_compile_when_the_query_switches():
    """(k) ``Fire_grouped_programs`` and ``Fire_groups`` beside
    ``Fire_programs``; a stream (budget given) whose programs go by
    range, then by lane, then by range again compiles nothing after its
    first batch. By range: eight keys in step. By lane: 32 more keys,
    each admitted at a window of its own, so that a round holds 33
    ranges. By range: all 40 in step once those have fired."""
    rng = np.random.default_rng(2)
    rep = make_replica(budget=2 * G_CAP, keys=G_CAP + 8, win=20)  # F = 64
    old = np.arange(8)

    def panes_of(keys, lo, hi):
        ks = np.repeat(keys, hi - lo)
        return ks, np.tile(np.arange(lo, hi), len(keys))

    bs = [padded(*panes_of(old, p, p + 4), p + 4, rng)
          for p in range(0, 24, 4)]
    # key k (8..39) from pane k + 17 on: its first window k - 2
    new = np.arange(8, G_CAP + 8)
    ks, ps = panes_of(old, 24, 64)
    ks = np.concatenate([ks] + [np.full(47 - k, k) for k in new])
    ps = np.concatenate([ps] + [np.arange(k + 17, 64) for k in new])
    bs.append(padded(ks, ps, 64, rng))
    every = np.arange(G_CAP + 8)
    bs += [padded(*panes_of(every, p, p + 4), p + 4, rng)
           for p in range(64, 80, 4)]
    rep.handle_msg(0, bs[0])
    compiled = rep.stats.compile_count
    assert compiled > 0
    for b in bs[1:6]:
        rep.handle_msg(0, b)
    rep.dispatch.drain(forced=True)       # commits are deferred
    by_range = rep.stats.fire_grouped_programs
    assert by_range == rep.stats.fire_programs > 0
    rep.handle_msg(0, bs[6])              # 33 ranges in the first round
    rep.dispatch.drain(forced=True)
    by_lane = rep.stats.fire_programs - rep.stats.fire_grouped_programs
    assert by_lane > 0
    for b in bs[7:]:
        rep.handle_msg(0, b)
    rep.flush_on_termination()
    st = rep.stats.to_dict()
    assert st["Fire_programs"] - by_lane == st["Fire_grouped_programs"] \
        > by_range
    assert st["Fire_groups"] > st["Fire_grouped_programs"]
    assert st["Windows_fired"] == 8 * 80 + sum(80 - (k - 2) for k in new)
    assert set(rep.emitter.widths) == {2 * G_CAP}
    assert st["Compile_count"] == compiled


# ----------------------------------------------------------------------
# the width by the plan: time-based windows, no budget given
# ----------------------------------------------------------------------
def ordered_fold(batches, n_keys, win, slide, lift, combine):
    """{(key, wid): value bytes}: every window that holds a reading, its
    panes combined in pane order, one after the other (what the CPU
    plane's FlatFAT answers; whole numbers, so any grouping of an
    associative combine gives these bits)."""
    import jax
    panes = {}
    for b in batches:
        cols = jax.device_get(lift({n: np.asarray(c)
                                    for n, c in b.fields.items()}))
        for i, (k, ts) in enumerate(zip(b.host_keys, b.ts_host)):
            val = {n: c[i] for n, c in cols.items()}
            cell = panes.setdefault((int(k), int(ts) // PANE), val)
            if cell is not val:
                panes[int(k), int(ts) // PANE] = combine(cell, val)
    last = max(p for _, p in panes)
    out = {}
    for k in range(n_keys):
        for w in range(last // slide + 1):
            acc = None
            for p in range(w * slide, w * slide + win):
                if (k, p) in panes:
                    acc = panes[k, p] if acc is None else combine(
                        acc, panes[k, p])
            if acc is not None:
                out[k, w] = tuple(np.asarray(acc[n]).tobytes()
                                  for n in sorted(acc))
    return out


def fed(rep, batches):
    """Programs each batch ran: the replica fed batch by batch, commits
    landed, ``Fire_programs`` read after each."""
    per_batch = []
    for b in batches:
        before = rep.stats.fire_programs
        rep.handle_msg(0, b)
        rep.dispatch.drain(forced=True)
        per_batch.append(rep.stats.fire_programs - before)
    return per_batch


BY_PLAN = {
    # (l) 40 keys, eight slides a batch: 320 windows a batch
    "dense_sum": dict(stream=dict(n_keys=40, n_panes=64, per_batch=8),
                      kw=dict(keys=40, win=12)),
    "dense_matrix_product": dict(
        stream=dict(n_keys=40, n_panes=64, per_batch=8),
        kw=dict(keys=40, win=12, combine=mat2, lift=lift_mat2)),
    # (m) F = 32 and 96 panes: the ring wraps three times, and every
    # program holds eight consecutive windows of every slot, each
    # evicting panes the next one reads
    "wrapped_ring_eight_rounds": dict(
        stream=dict(n_keys=5, n_panes=96, per_batch=8),
        kw=dict(keys=5, win=13, combine=mat2, lift=lift_mat2)),
}


@pytest.mark.parametrize("case", sorted(BY_PLAN))
def test_a_batchs_whole_plan_leaves_in_one_program(case):
    spec = BY_PLAN[case]
    n_keys, per_batch = spec["stream"]["n_keys"], spec["stream"]["per_batch"]

    def stream():
        return aligned_stream(rng=np.random.default_rng(7), whole=True,
                              **spec["stream"])

    reps = [make_replica(lane_only=lane, budget=None, **spec["kw"])
            for lane in (False, True)]
    rep, lane = reps
    assert rep.W_wide == rep.W_cap == max(16, n_keys)
    per_batch_programs = fed(rep, stream())
    rep.flush_on_termination()
    got = rep.emitter.rows
    assert got == run(lane, stream()) and len(got) > 20
    # ONE program a batch, the step itself, by range; only the flush
    # (more windows than the widest batch) takes a second one
    assert set(per_batch_programs) == {0, 1}
    assert per_batch_programs[2:] == [1] * (len(per_batch_programs) - 2)
    st = rep.stats
    assert st.fire_grouped_programs == st.fire_programs \
        == sum(per_batch_programs) + 2
    assert lane.stats.fire_grouped_programs == 0
    assert lane.stats.fire_programs == st.fire_programs
    # a steady batch: eight rounds of every slot in its one program
    assert max(rep.emitter.widths) == rep.W_wide == n_keys * per_batch
    steady = st.windows_fired / st.fire_programs
    assert steady > 0.75 * n_keys * per_batch
    # and the answers are the ordered fold's, bit for bit
    kw = spec["kw"]
    want = ordered_fold(stream(), n_keys, kw["win"], 1,
                        kw.get("lift", lift_v), kw.get("combine", add))
    assert {(k, w): tuple(vals) for k, w, ok, *vals in got if ok} == want
    assert len(got) == len(want)


def flushed_wide(batches, total, flush=True, **kw):
    """A replica of two keys, no budget, ``batches`` taken in and its
    width grown to a plan of ``total`` windows as a firing batch with
    such a plan would have grown it, then flushed: the end-of-stream
    flush itself keeps the width it has (PR 34: nothing follows it that
    could use a new compiled shape)."""
    rep = make_replica(budget=None, keys=2, win=8, **kw)
    run(rep, batches, flush=False)
    assert rep._fit_width(total)
    if flush:
        rep.flush_on_termination()
    return rep


def test_the_flush_keeps_the_width_it_has():
    n = 5 * G_CAP // 2
    bs = aligned_stream(2, n, n, np.random.default_rng(3))
    bs[0].wm = 0
    rep = make_replica(budget=None, keys=2, win=8)
    assert len(run(rep, bs)) == 2 * n
    assert rep.W_wide == rep.W_cap == 16
    assert set(rep.emitter.widths) == {16}
    assert rep.stats.fire_programs == 2 * n // 16


def test_a_plan_over_the_table_is_cut_at_a_whole_round_and_stays_by_range():
    """(n) two keys flush ``2.5 * G_CAP`` slides each, a range a round:
    programs of ``G_CAP`` whole rounds, none by lane."""
    n = 5 * G_CAP // 2

    def stream():
        bs = aligned_stream(2, n, n, np.random.default_rng(3))
        bs[0].wm = 0
        return bs

    grouped, lane = (flushed_wide(stream(), 2 * n, lane_only=lane)
                     for lane in (False, True))
    got, want = grouped.emitter.rows, lane.emitter.rows
    assert got == want and len(got) == 2 * n
    st = grouped.stats
    assert (st.fire_programs, st.fire_grouped_programs, st.fire_groups,
            st.fire_range_cuts) == (3, 3, n, 2)
    # the width: the bucket of the plan, held to the batch's capacity
    assert set(grouped.emitter.widths) == {2 * n} == {grouped.W_wide}
    assert grouped.stats.to_dict()["Fire_range_cuts"] == 2
    assert lane.stats.fire_range_cuts == 2    # cut alike, walked by lane


def test_a_ragged_plan_walks_by_lane_at_the_narrow_width():
    """(o) more keys than ``G_CAP``, each silent from a pane of its own
    on, so that no two share a (clipped) range in any round: the first
    round alone holds more ranges than the table, and the programs walk
    by lane, at the width they had at the parent (the key capacity's)."""
    n_keys, win = G_CAP + 8, 100            # F = 128

    def stream():
        rng = np.random.default_rng(5)
        last = 40 + np.arange(n_keys)       # key k is silent after this
        ks = np.repeat(np.arange(n_keys), last + 1)
        ps = np.concatenate([np.arange(n + 1) for n in last])
        # the watermark closes windows 0..30 of every key: 31 rounds,
        # window j of key k clipped to 41 + k - j panes
        return [batch(ks, ps, rng.random(len(ks)) * 100, win + 30)]

    grouped, lane, got, want = both(stream, flush=False, budget=None,
                                    keys=n_keys, win=win)
    assert got == want and len(got) == 31 * n_keys
    st = grouped.stats
    assert (st.fire_programs, st.fire_grouped_programs,
            st.fire_range_cuts) == (31, 0, 0)
    # the plan outgrew the width and the width grew: no program used it
    assert grouped.W_wide == 2048 > grouped.W_cap == n_keys
    assert set(grouped.emitter.widths) == {grouped.W_cap}


def test_the_width_grows_with_the_plan_and_compiles_nothing_after():
    """(p) the width follows the plans up to the capacity of the input
    batch, each growth warms its shapes once, and ``Compile_count`` is
    flat from then on, the flush included."""
    rep = make_replica(budget=None, keys=40, win=12)
    bs = aligned_stream(40, 96, 8, np.random.default_rng(9))
    widths, compiled = [], []
    for b in bs:
        rep.handle_msg(0, b)
        rep.dispatch.drain(forced=True)
        widths.append(rep.W_wide)
        compiled.append(rep.stats.compile_count)
    # batch 2 fires five slides of 40 keys (200 windows: the bucket of
    # 256), batch 3 eight (320: the batch's capacity, under the bucket of
    # 512); nothing later is wider
    assert widths == [40, 256] + [320] * 10
    assert compiled[2] > compiled[1] > compiled[0] > 0
    assert compiled[2:] == [compiled[2]] * 10
    rep.flush_on_termination()
    assert rep.stats.compile_count == compiled[2]
    assert rep.W_wide == 320 == bs[0].capacity
    # the step at W_cap and at every W_wide it grew to
    assert sorted(W for key, W in rep._warm_shapes
                  if key[0] == "step") == [40, 256, 320]


def test_snapshot_and_restore_between_two_programs_of_a_wide_plan():
    """(q) the flush of (n), cut after its first program: the restored
    replica (its width back at the start) fires the other rounds, every
    (key, wid) once and bit-equal to the uninterrupted run."""
    n = 5 * G_CAP // 2

    def stream():
        bs = aligned_stream(2, n, n, np.random.default_rng(11))
        bs[0].wm = 0
        return bs

    whole = flushed_wide(stream(), 2 * n)
    want = list(whole.emitter.rows)
    assert len(want) == 2 * n and whole.stats.fire_programs == 3

    class Cut(Exception):
        pass

    cut = flushed_wide(stream(), 2 * n, flush=False)
    plan_program, calls = cut._plan_program, []

    def once(slots, k):
        calls.append(int(k.sum()))
        if len(calls) > 1:
            raise Cut
        return plan_program(slots, k)

    cut._plan_program = once
    with pytest.raises(Cut):
        cut.flush_on_termination()
    assert len(cut.emitter.rows) == 2 * G_CAP   # G_CAP whole rounds are out
    state = cut.snapshot_state()

    rest = make_replica(budget=None, keys=2, win=8)
    rest.restore_state(state)
    # a width is a compiled shape, not state: it starts over, and with no
    # input batch seen it stays at the key capacity's
    assert rest.W_wide == rest.W_cap == 16
    rest.flush_on_termination()
    got = cut.emitter.rows + rest.emitter.rows
    assert sorted(got) == sorted(want) and len(set(got)) == len(want)
    for key in (0, 1):      # a key's windows in wid order, as in one run
        assert [r for r in got if r[0] == key] \
            == [r for r in want if r[0] == key]
    assert rest.stats.fire_grouped_programs == rest.stats.fire_programs \
        == (2 * n - 2 * G_CAP) // 16


# (r) a given budget and count-based windows on the one planner:
# (Fire_programs, Fire_grouped_programs, Fire_groups, Windows_fired,
# Device_programs_run, Compile_count) and the widths of the programs as
# commit 20733da reads them on these streams, where its budget given had
# one width
AS_AT_THE_PARENT = {
    "time_based_budget_given": dict(
        kw=dict(budget=8, keys=4, win=6), widths={8},
        counters=(13, 13, 24, 96, 19, 4)),
    "count_based_budget_given": dict(
        kw=dict(budget=8, keys=4, win=6, slide=2, win_type=WinType.CB),
        widths={8}, counters=(6, 0, 0, 48, 7, 4)),
    "count_based_no_budget": dict(
        kw=dict(budget=None, keys=4, win=6, slide=2, win_type=WinType.CB),
        widths={16}, counters=(6, 0, 0, 48, 7, 4)),
    # 96 windows a batch, a budget of 96: every program 96 lanes wide
    # (commit 20733da ran its first firing step 64 lanes wide and read
    # (8, 8, 27, 576, 14, 5): the same 576 rows)
    "time_based_two_tiers": dict(
        kw=dict(budget=96, keys=24, win=6), widths={96},
        counters=(7, 7, 24, 576, 13, 4)),
}


@pytest.mark.parametrize("case", sorted(AS_AT_THE_PARENT))
def test_a_given_budget_and_count_based_windows_plan_as_at_the_parent(case):
    spec = AS_AT_THE_PARENT[case]
    rep = make_replica(**spec["kw"])
    bs = aligned_stream(spec["kw"]["keys"], 24, 4, np.random.default_rng(13))
    for i, b in enumerate(bs):
        if i % 3:
            b.wm = bs[i - i % 3].wm       # the watermark moves in steps
    run(rep, bs)
    st = rep.stats
    assert rep.W_wide == rep.W_cap
    assert set(rep.emitter.widths) == spec["widths"]
    assert (st.fire_programs, st.fire_grouped_programs, st.fire_groups,
            st.windows_fired, st.device_programs_run,
            st.compile_count) == spec["counters"]
    assert st.fire_range_cuts == 0
