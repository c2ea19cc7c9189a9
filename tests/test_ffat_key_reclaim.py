"""Key turnover of the time-based window operator (``tpu/ffat_tpu.py``,
``tpu/keymap.py``; PR 34): a key's slot is given back once none of its
windows that hold an event is left, a new key takes it, a key that comes
back is a new key; what the operator hands downstream (a fired row's event
time, an emitted batch's watermark, whose keys its host keys are). The
replica is driven directly where a test reads its state, through
``PipeGraph`` where it reads results. CPU backend."""

import numpy as np
import pytest

from common import expected_windows
from test_ffat_grouped_fire import (PANE, add, aligned_stream, batch,
                                    lift_v, make_replica, run)
from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.basic import WindFlowError, WinType
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder, Map_TPU_Builder
from windflow_tpu.tpu.keymap import KeySlotMap

WIN = 4         # make_replica's window, in panes; it slides by one


def sums(rows):
    """{(key, wid): float sum} of the valid rows ``Rows`` recorded."""
    out = {}
    for key, wid, ok, *vals in rows:
        if ok:
            assert (key, wid) not in out, f"({key}, {wid}) delivered twice"
            out[(key, wid)] = float(np.frombuffer(vals[0], np.float32)[0])
    return out


def model(events):
    """``expected_windows`` over ``(key, pane, value)`` events."""
    seqs = {}
    for k, p, v in events:
        seqs.setdefault(k, []).append((v, p))
    want = expected_windows(seqs, WIN, 1, False, sum)
    return {kw: float(v) for kw, v in want.items() if v}


def feed(rep, events, wm_pane):
    ks, ps, vs = zip(*events)
    rep.handle_msg(0, batch(ks, ps, vs, wm_pane))
    rep.dispatch.drain(forced=True)


# ---------------------------------------------------------------------------
# the slot table
# ---------------------------------------------------------------------------
def test_a_dead_slot_is_given_back_and_its_row_holds_no_valid_leaf():
    rep = make_replica(budget=None, keys=4)
    feed(rep, [(10, 0, 1.0), (11, 0, 2.0), (10, 1, 3.0)], 0)
    s10, s11 = rep.slot_of_key[10], rep.slot_of_key[11]
    assert np.asarray(rep.tvalid)[rep.F:, [s10, s11]].any(axis=0).all()
    # the watermark passes every window that holds an event of either
    # key; key 12 arrives with it
    feed(rep, [(12, 9, 5.0)], 9)
    assert set(rep.slot_of_key) == {12}
    assert sorted(rep._keymap.free) == sorted([s10, s11])
    st = rep.stats.to_dict()
    assert (st["Keys_admitted"], st["Keys_reclaimed"],
            st["Key_slots_live"], st["Key_capacity_growths"]) == (3, 2, 1, 0)
    # every leaf of the rows given back was evicted by the fires that
    # consumed it: nothing on the device needed clearing
    assert not np.asarray(rep.tvalid)[rep.F:, [s10, s11]].any()
    # a new key takes a free slot before the table grows, and its windows
    # hold its own events alone
    feed(rep, [(13, 10, 7.0), (14, 10, 8.0), (12, 10, 1.0)], 10)
    assert {rep.slot_of_key[13], rep.slot_of_key[14]} == {s10, s11}
    assert rep.K_cap == 4 and rep._keymap.n_slots == 3
    rep.flush_on_termination()
    assert sums(rep.emitter.rows) == model(
        [(10, 0, 1.0), (11, 0, 2.0), (10, 1, 3.0), (12, 9, 5.0),
         (13, 10, 7.0), (14, 10, 8.0), (12, 10, 1.0)])
    assert rep.stats.key_slots_live == 0 == len(rep.slot_of_key)


def test_many_more_keys_than_slots_pass_through_a_table_that_never_grows():
    rep = make_replica(budget=None, keys=8)
    events, rng = [], np.random.default_rng(5)
    for p in range(60):          # three new keys a pane, each for 2 panes
        now = [(100 + 3 * q + j, p, float(rng.integers(1, 9)))
               for q in (p - 1, p) if q >= 0 for j in range(3)]
        events += now
        feed(rep, now, p)
        assert len(rep.slot_of_key) <= 8 * 3
    rep.flush_on_termination()
    assert sums(rep.emitter.rows) == model(events)
    assert rep.stats.keys_admitted == 180 and rep.stats.keys_reclaimed == 180
    # (2 + 4) panes of three keys live at once: 32 slots, never more
    assert rep.K_cap == 32 and rep._keymap.n_slots <= 32


def test_a_key_that_returns_is_a_new_key_with_its_absolute_wid():
    rep = make_replica(budget=None, keys=4)
    events = [(7, 0, 1.0), (7, 2, 2.0), (8, 2, 1.0)]
    feed(rep, events, 2)
    feed(rep, [(8, 40, 1.0)], 40)           # key 7 is long forgotten
    assert 7 not in rep.slot_of_key
    back = [(7, 50, 4.0), (7, 51, 5.0), (8, 51, 1.0)]
    feed(rep, back, 51)
    rep.flush_on_termination()
    got = sums(rep.emitter.rows)
    assert got == model(events + [(8, 40, 1.0)] + back)
    assert got[(7, 47)] == 4.0 and got[(7, 50)] == 9.0
    # nothing fired over the silence, valid or empty: the key held no slot
    assert {w for k, w, *_ in rep.emitter.rows if k == 7} == \
        {0, 1, 2} | set(range(47, 52))


def test_count_based_windows_keep_their_keys():
    rep = make_replica(budget=8, keys=4, win_type=WinType.CB, win=3, slide=1)
    for _ in range(4):
        rep.handle_msg(0, batch([1, 2, 1, 2], [0] * 4, [1.0] * 4, 0))
    rep.dispatch.drain(forced=True)
    assert rep.stats.windows_fired > 0       # complete windows fired
    assert set(rep.slot_of_key) == {1, 2} and not rep._keymap.free
    rep.flush_on_termination()
    assert set(rep.slot_of_key) == {1, 2}
    assert rep.stats.keys_reclaimed == 0 and rep.stats.keys_admitted == 2


# ---------------------------------------------------------------------------
# the rule for a late event of a forgotten key
# ---------------------------------------------------------------------------
def test_late_event_of_a_forgotten_key_is_counted_dropped_not_redelivered():
    """Key 7's windows 0..2 are delivered and its slot given back. Its
    late event in pane 1 must not make windows 0..1 fire again: a new
    key's first window is never below the furthest window a forgotten
    key had reached (``_reclaimed_wid``), so the event is behind its
    key's fired windows as it was before the key was forgotten: counted
    late and dropped. Every input is classified exactly once (PR 20's
    conservation)."""
    rep = make_replica(budget=None, keys=4)
    first = [(7, 0, 1.0), (7, 2, 2.0), (9, 2, 1.0)]
    feed(rep, first, 0)
    feed(rep, [(9, 10, 1.0)], 10)
    assert 7 not in rep.slot_of_key and rep._reclaimed_wid == 3
    delivered = sums(rep.emitter.rows)
    assert {(7, 0), (7, 1), (7, 2)} <= set(delivered)
    # the late event, and one in time in the same batch
    feed(rep, [(7, 1, 100.0), (9, 11, 1.0)], 11)
    st = rep.stats
    assert (st.late_records, st.late_dropped, rep.ignored) == (1, 1, 1)
    assert 7 not in rep.slot_of_key      # it holds no event: no slot
    # a late event past the floor is a new key's first: taken, as the
    # late first event of a key never seen is (windows 6..9; none of
    # them was delivered for key 7)
    feed(rep, [(7, 9, 50.0), (9, 12, 1.0)], 12)
    rep.flush_on_termination()
    got = sums(rep.emitter.rows)          # asserts no (key, wid) twice
    assert got == model(first + [(9, 10, 1.0), (9, 11, 1.0), (9, 12, 1.0),
                                 (7, 9, 50.0)])
    assert got[(7, 6)] == 50.0 and (7, 3) not in got
    n_in = 3 + 1 + 2 + 2
    on_time = n_in - st.late_records
    assert on_time + (st.late_records - st.late_dropped) \
        + st.late_dropped == n_in == st.inputs_received
    assert st.late_dropped == 1 and st.late_records == 2


def test_gap_windows_still_reanchor_a_key_whose_first_event_fell_in_a_gap():
    """slide > win: an event between two windows is late for every
    window. Its key holds no slot for it (PR 34); the key's next event
    registers it anew, at the window that holds it."""
    rep = make_replica(budget=None, keys=4, win=2, slide=10)
    feed(rep, [(5, 5, 1.0)], 0)              # pane 5: between 0 and 1
    assert 5 not in rep.slot_of_key and rep.stats.late_dropped == 1
    assert rep._reclaimed_wid == 0           # no window of it fired
    feed(rep, [(5, 21, 2.0), (6, 1, 3.0)], 0)
    rep.flush_on_termination()
    assert sums(rep.emitter.rows) == {(5, 2): 2.0, (6, 0): 3.0}


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------
def test_snapshot_between_a_reclaim_and_a_reuse_restores_to_the_same_rows():
    def upto(rep):
        feed(rep, [(1, 0, 1.0), (2, 0, 2.0), (3, 1, 3.0)], 1)
        feed(rep, [(3, 9, 1.0)], 9)          # 1 and 2 are given back
        return len(rep.emitter.rows)

    def after(rep):
        feed(rep, [(4, 10, 4.0), (5, 10, 5.0), (3, 10, 1.0)], 10)
        feed(rep, [(1, 11, 6.0)], 11)        # 1 returns, a new key
        rep.flush_on_termination()
        return rep.emitter.rows

    whole = make_replica(budget=None, keys=4)
    upto(whole)
    want = list(after(whole))

    cut = make_replica(budget=None, keys=4)
    n_before = upto(cut)
    assert len(cut._keymap.free) == 2
    state = cut.snapshot_state()
    assert sorted(state["ffat"]["free_slots"]) == sorted(cut._keymap.free)
    assert state["ffat"]["reclaimed_wid"] == cut._reclaimed_wid > 0
    rest = make_replica(budget=None, keys=4)
    rest.restore_state(state)
    assert rest._keymap.free == cut._keymap.free
    assert rest._reclaimed_wid == cut._reclaimed_wid
    # row for row, in order: the restored run hands out the same slots
    assert cut.emitter.rows[:n_before] + after(rest) == want
    assert rest.K_cap == 4


def test_a_state_without_a_free_list_gets_one_rebuilt():
    """``scaling/repartition.py`` writes states whose live keys are packed
    from slot 0, and older snapshots have no ``free_slots``."""
    rep = make_replica(budget=None, keys=4)
    feed(rep, [(1, 0, 1.0), (2, 0, 2.0), (3, 1, 3.0)], 1)
    feed(rep, [(3, 9, 1.0)], 9)
    state = rep.snapshot_state()
    live = state["ffat"]["slot_of_key"]
    del state["ffat"]["free_slots"], state["ffat"]["reclaimed_wid"]
    rest = make_replica(budget=None, keys=4)
    rest.restore_state(state)
    assert sorted(rest._keymap.free) == sorted(
        set(range(max(live.values()) + 1)) - set(live.values()))
    assert rest._keymap.n_slots == max(live.values()) + 1


def test_repartition_packs_live_keys_and_keeps_the_floor():
    from windflow_tpu.scaling.repartition import _split_ffat_tpu
    reps = [make_replica(budget=None, keys=4) for _ in range(2)]
    feed(reps[0], [(1, 0, 1.0), (2, 0, 2.0), (3, 1, 3.0)], 1)
    feed(reps[0], [(3, 9, 1.0)], 9)          # floor 4: 3's next is 6..
    feed(reps[1], [(4, 9, 1.0)], 9)
    states = [r.snapshot_state()["ffat"] for r in reps]
    floor = max(s["reclaimed_wid"] for s in states)
    assert floor > 0
    out = _split_ffat_tpu(states, 1, lambda key: 0, "win")[0]
    assert set(out["slot_of_key"]) == {3, 4}
    assert sorted(out["slot_of_key"].values()) == [0, 1]
    assert out["free_slots"] == [] and out["reclaimed_wid"] == floor
    merged = make_replica(budget=None, keys=4)
    merged.restore_state({"ffat": out})
    feed(merged, [(1, 0, 9.0), (3, 10, 1.0)], 10)     # 1: late, forgotten
    assert merged.stats.late_dropped == 1


# ---------------------------------------------------------------------------
# what a fired batch carries
# ---------------------------------------------------------------------------
class Batches:
    """An emitter that keeps what it is handed."""

    def __init__(self):
        self.out = []

    def emit_device_batch(self, b):
        self.out.append(b)

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass


@pytest.mark.parametrize("budget", [None, 4, 16])
def test_fired_rows_carry_window_ends_and_no_watermark_passes_an_owed_row(
        budget):
    """Four keys, eight windows each, closed by one watermark: with a
    budget of 4 the drain leaves in eight programs, with 16 in two, with
    none in one. Every row is stamped with the last instant of its
    window; every batch's watermark is below the window ends of its own
    rows and of every row a later batch of the drain brings."""
    rep = make_replica(budget=budget, keys=4)
    rep.emitter = Batches()
    bs = aligned_stream(4, 12, 12, np.random.default_rng(3))
    bs[0].wm = 0
    rep.handle_msg(0, bs[0])
    rep.cur_wm = 11 * PANE
    rep.on_punctuation(rep.cur_wm)       # windows 0..7 of four keys
    out = rep.emitter.out
    assert len(out) == {None: 1, 4: 8, 16: 2}[budget]
    ends = []
    for b in out:
        wid = np.asarray(b.fields["wid"])[:b.size].astype(np.int64)
        assert (b.ts_host[:b.size] == wid * PANE + WIN * PANE - 1).all()
        assert b.key_origin == "key"
        ends.append(wid * PANE + WIN * PANE)
    assert sum(len(e) for e in ends) == 32
    for i, b in enumerate(out):
        owed = np.concatenate(ends[i:])
        assert b.wm <= owed.min() - 1 < rep.cur_wm
        assert (b.ts_host[:b.size] >= b.wm).all()     # none late on arrival
    assert [b.wm for b in out] == sorted(b.wm for b in out)


def test_count_based_rows_carry_the_watermark_of_the_fire():
    rep = make_replica(budget=8, keys=4, win_type=WinType.CB, win=3, slide=1)
    rep.emitter = Batches()
    b = batch([1, 1, 1, 1], [0] * 4, [1.0] * 4, 0)
    b.wm = 777
    rep.handle_msg(0, b)
    rep.dispatch.drain(forced=True)
    (out,) = rep.emitter.out
    assert out.wm == 777 and (out.ts_host == 777).all()


# ---------------------------------------------------------------------------
# a keyed operator after a window operator
# ---------------------------------------------------------------------------
def two_stage_graph(blocks, rows_out, budget=None):
    """Filter-less Q5 in small: count per ``auction`` over 4 s sliding by
    1 s, a map that adds the constant column ``all``, then per second the
    largest count (ties to the lowest id) keyed by ``all``."""
    import jax.numpy as jnp

    def source(shipper):
        for cols, ts in blocks:
            shipper.set_next_watermark(max(0, int(ts[0]) - 1))
            shipper.push_columns(cols, ts=ts)
        shipper.set_next_watermark(int(ts[-1]))

    def sink(cols, ts):
        if cols is not None:
            rows_out.append({k: np.array(v) for k, v in cols.items()})

    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"count": jnp.ones(f["auction"].shape, jnp.int32)},
               lambda a, b: {"count": a["count"] + b["count"]})
           .with_key_by("auction").with_tb_windows(4_000_000, 1_000_000)
           .with_key_capacity(512).with_name("win"))
    if budget:
        win = win.with_num_win_per_batch(budget)
    one = Map_TPU_Builder(
        lambda f: {**f, "all": jnp.zeros(f["count"].shape, jnp.int32)}
    ).with_name("one").build()

    def larger(a, b):
        take = (a["count"] > b["count"]) | (
            (a["count"] == b["count"]) & (a["auction"] < b["auction"]))
        return {"count": jnp.where(take, a["count"], b["count"]),
                "auction": jnp.where(take, a["auction"], b["auction"])}

    hot = (Ffat_Windows_TPU_Builder(
               lambda f: {"count": jnp.where(f["valid"], f["count"], 0),
                          "auction": f["auction"]}, larger)
           .with_key_by("all").with_tb_windows(1_000_000, 1_000_000)
           .with_key_capacity(1).with_name("hot").build())
    g = PipeGraph("two_stage", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    g.add_source(Source_Builder(source).with_name("src")
                 .with_output_batch_size(len(blocks[0][1])).build()) \
     .add(win.build()).add(one).add(hot) \
     .add_sink(Sink_Builder(sink).with_name("snk").with_columns().build())
    return g


def churning_blocks(n_blocks=40, rows=512, seed=3):
    """Auction ids that move on by ~30 a block, 0.5 s of event time a
    block."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_blocks):
        ids = 1000 + 30 * b + rng.integers(0, 40, rows)
        ts = b * 500_000 + (np.arange(rows, dtype=np.int64) * 500_000) // rows
        out.append(({"auction": ids.astype(np.int32)}, ts))
    return out


def hot_items(blocks):
    """{stage-two window: (auction, count)} in plain Python."""
    from collections import Counter
    last = int(blocks[-1][1][-1])
    per_sec = {}
    for cols, ts in blocks:
        for a, t in zip(cols["auction"].tolist(), (ts // 1_000_000).tolist()):
            per_sec.setdefault(t, Counter())[a] += 1
    out = {}
    for w in range(last // 1_000_000 + 1):
        c = Counter()
        for p in range(w, w + 4):
            c.update(per_sec.get(p, {}))
        if c:
            top = max(c.values())
            out[w + 3] = (min(a for a, n in c.items() if n == top), top)
    return out


@pytest.fixture(scope="module")
def two_stages():
    blocks, rows = churning_blocks(), []
    g = two_stage_graph(blocks, rows)
    g.run()
    stats = {o["name"]: o["replicas"][0]
             for o in g.get_stats()["Operators"]}
    cols = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
    return {"cols": cols, "stats": stats, "want": hot_items(blocks)}


def test_the_second_window_stage_is_keyed_by_its_own_field(two_stages):
    """On the parent this graph ran to its end and delivered 2,892 rows
    keyed by the first stage's auction ids, where the reference has one a
    second: a fired batch carried the window operator's keys, and they
    won over ``with_key_by("all")``."""
    c, want = two_stages["cols"], two_stages["want"]
    keep = c["valid"].astype(bool)
    assert (c["all"] == 0).all() and len(want) == 20
    got = {int(w): (int(a), int(n)) for w, a, n in
           zip(c["wid"][keep], c["auction"][keep], c["count"][keep])}
    assert keep.sum() == len(got) == len(want)
    assert got == want


def test_both_stages_turn_their_keys_over_and_drop_nothing(two_stages):
    win, hot = two_stages["stats"]["win"], two_stages["stats"]["hot"]
    # ~340 auctions live at once (60 new a second, 4 s windows) in 512
    # slots, 1,200 in all
    assert win["Keys_admitted"] > 1000
    assert win["Keys_reclaimed"] == win["Keys_admitted"]
    assert win["Key_capacity_growths"] == 0
    assert win["Key_turnover_total_usec"] > 0
    assert hot["Late_dropped"] == 0 == hot["Late_records"]
    assert hot["Inputs_received"] == win["Windows_fired"]
    assert hot["Key_capacity_growths"] == 0


def test_a_small_fire_budget_splits_a_slide_and_the_second_stage_drops_none():
    blocks, rows = churning_blocks(n_blocks=16), []
    g = two_stage_graph(blocks, rows, budget=16)
    g.run()
    stats = {o["name"]: o["replicas"][0]
             for o in g.get_stats()["Operators"]}
    win, hot = stats["win"], stats["hot"]
    assert win["Fire_programs"] > 4 * win["Device_batches_in"]
    assert hot["Late_dropped"] == 0 == hot["Late_records"]
    cols = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
    keep = cols["valid"].astype(bool)
    got = {int(w): (int(a), int(n)) for w, a, n in
           zip(cols["wid"][keep], cols["auction"][keep], cols["count"][keep])}
    assert got == hot_items(blocks) and keep.sum() == len(got)


def test_key_turnover_is_a_span_inside_the_window_prep():
    from test_stage_spans import run_recorded
    blocks, rows = churning_blocks(n_blocks=12), []
    g = two_stage_graph(blocks, rows)
    log, stats, _ring = run_recorded(g)
    spans = log.named("wf:keys:win")
    # admission inside the prep, slots given back inside its fire plan;
    # the end-of-stream flush's are under no batch's span
    assert {s["parent"] for s in spans} == {"wf:prep:win",
                                            "wf:fireplan:win", None}
    assert stats["win"]["Key_turnover_total_usec"] > 0
    assert stats["one"]["Key_turnover_total_usec"] == 0


# ---------------------------------------------------------------------------
# the key map
# ---------------------------------------------------------------------------
def test_keymap_table_moves_with_a_churning_key_space():
    admitted = []
    m = KeySlotMap(on_new_many=lambda k, s: admitted.append(len(k)))
    base = 1000
    for step in range(300):
        keys = np.arange(base, base + 200, dtype=np.int64)
        slots = m.slots_of(keys, keys, len(keys))
        assert [m.slot_of_key[k] for k in keys.tolist()] == slots.tolist()
        base += 50 if step < 150 else 100_000       # then far jumps
        m.release(np.array([k for k in m.slot_of_key if k < base],
                           dtype=np.int64))
        assert len(m) == (150 if step < 150 else 0) and m.n_slots <= 400
    # ids past LUT_MAX, a table sized by the live keys
    assert base > KeySlotMap.LUT_MAX and len(m._lut) <= 1024
    assert m._base > 0 and sum(admitted) == 200 + 150 * 50 + 149 * 200


def test_keymap_small_ids_keep_the_table_from_zero():
    m = KeySlotMap()
    a = np.array([5, 9, 9, 2124])
    assert m.slots_of(a, a, 4).tolist() == [0, 1, 1, 2]
    assert m._base == 0 and len(m._lut) == 8192


def test_keymap_sparse_ids_are_looked_up_by_search():
    m = KeySlotMap(on_new_many=lambda k, s: None)
    rng = np.random.default_rng(1)
    for _ in range(30):
        keys = rng.integers(0, 2**62, 300)
        keys[:100] = keys[100:200]
        slots = m.slots_of(keys, keys, 300)
        assert [m.slot_of_key[k] for k in keys.tolist()] == slots.tolist()
        m.release(np.array(list(m.slot_of_key)[40:], dtype=np.int64))
        assert m._lut is None and len(m) == 40 and m.n_slots <= 300
    assert len(set(m.slot_of_key.values())) == len(m)


def test_keymap_a_refused_batch_registers_nothing():
    def refuse(keys, slots):
        if slots.max() >= 4:
            raise WindFlowError("over capacity")

    m = KeySlotMap(on_new_many=refuse)
    a = np.array([7, 8, 9])
    assert sorted(m.slots_of(a, a, 3).tolist()) == [0, 1, 2]
    m.release(np.array([8]))
    b = np.array([20, 21, 22])
    for _ in range(2):                  # the retry refuses alike
        with pytest.raises(WindFlowError, match="capacity"):
            m.slots_of(b, b, 3)
        assert set(m.slot_of_key) == {7, 9} and m.free == [1]
    c = np.array([30, 31])              # the free slot first, then the top
    assert sorted(m.slots_of(c, c, 2).tolist()) == [1, 3]
