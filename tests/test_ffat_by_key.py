"""Count-based windows by the KEY (``tpu/ffat_tpu.py``): the host hands
a step the rows' slots and two words a slot (``_prep_by_key``), and a
fire program chunk rows (``plan_views``); the step numbers its own
rows (``cb_number_rows``) and the program expands its own lanes
(``plan_lanes``; time-based plans too since PR 39, held in
``test_ffat_time_chunks.py``). Held here against what the parent commit
built on the host, by row and by lane, kept below as the plain
reference; a fired batch's keys leave by chunk (``ChunkedKeys``) and
reach a keyed consumer as they did. The replica is driven directly on
the CPU backend, as ``test_ffat_sliding_fire.py`` drives it."""

import inspect

import numpy as np
import pytest

from test_ffat_sliding_fire import (SCHEMA, Rows, batch, comb_sd, lift_sd,
                                    make_replica, run, stream)
from windflow_tpu.basic import WinType
from windflow_tpu.tpu.batch import BatchTPU, ChunkedKeys
from windflow_tpu.tpu.ffat_tpu import (Ffat_Windows_TPU, cb_number_rows,
                                       plan_lanes, plan_len, plan_views)
from windflow_tpu.tpu.keymap import group_positions


# ---------------------------------------------------------------------------
# the parent's host code, the plain reference
# ---------------------------------------------------------------------------
def parent_numbering(count, next_fire, max_leaf, slots, K_cap, F):
    """The parent's by-row prep of a count-based batch: ``(comp by row,
    count, max_leaf, n_late)`` after it; ``slots`` the surviving rows'."""
    _, within = group_positions(slots, K_cap)
    leaves = count[slots] + within
    count = count.copy()
    np.add.at(count, slots, 1)
    live = leaves >= next_fire[slots]
    max_leaf = max_leaf.copy()
    np.maximum.at(max_leaf, slots, np.where(live, leaves, -1))
    comp = np.where(live, slots * F + (leaves & (F - 1)), K_cap * F)
    return comp, count, max_leaf, int((~live).sum())


def parent_lanes(chunks, W, F, win_units, slide_units, K_cap):
    """The parent's ``_lanes`` + ``_pack_plan`` of a count-based plan:
    the six fire rows at width ``W`` and the set of evicted leaves, as
    flat indices of the node-major (2F, K_cap) forest."""
    c_slots, c_start0, c_k, c_wid0, c_ml = chunks
    tot = int(c_k.sum())
    rnd = np.arange(tot) - np.repeat(np.cumsum(c_k) - c_k, c_k)
    starts = np.repeat(c_start0, c_k) + rnd * slide_units
    lens = np.minimum(win_units, np.repeat(c_ml, c_k) + 1 - starts)
    fire = np.zeros((6, W), np.int64)
    fire[0, :tot] = np.repeat(c_slots, c_k)
    fire[1, :tot] = starts % F
    fire[2, :tot] = lens
    fire[3, :tot] = np.repeat(c_wid0, c_k) + rnd
    fire[4, :tot] = 1
    fire[5, :tot] = rnd
    ne = np.maximum(0, np.minimum(c_start0 + c_k * slide_units, c_ml + 1)
                    - c_start0)
    ep = (np.repeat(c_start0, ne) + np.arange(int(ne.sum()))
          - np.repeat(np.cumsum(ne) - ne, ne))
    evicted = set(((F + ep % F) * K_cap + np.repeat(c_slots, ne)).tolist())
    return fire, evicted


# ---------------------------------------------------------------------------
# 1. the step numbers its own rows
# ---------------------------------------------------------------------------
def numbered_by_program(slots_p, keyrows, K_cap, F):
    """The composite of every ROW as the program forms it."""
    import jax
    order, sc = jax.jit(cb_number_rows, static_argnums=(2, 3))(
        slots_p, keyrows, K_cap, F)
    comp = np.empty(len(slots_p), np.int64)
    comp[np.asarray(order)] = np.asarray(sc)
    return comp


def prep_once(rep, b, keep=None):
    """One batch through the replica's prep: what it would hand the step
    (the slots column, the per-slot words), nothing launched."""
    seen = {}

    def capture(fields, wm, cap, comp_p, frontier, bid=0, keyrows=None):
        seen.update(slots_p=comp_p, keyrows=keyrows, frontier=frontier)
        return None

    rep._prep_step = capture
    if keep is not None:
        rep._prefix_mask = lambda _b: keep
    rep.prep_device_batch(b)
    return seen


# every case: a state before the batch (count, next_fire by key slot),
# a batch's keys, and the rows a fused prefix filter keeps (None: all)
RNG = np.random.default_rng(11)
NUMBERING = {
    "plain_keyed_stream": dict(
        count=[40, 7, 0, 19], next_fire=[33, 0, 0, 12],
        keys=RNG.integers(0, 4, 50)),
    # slide > win: a key's first arrivals of the batch lie in the gap
    # behind its next window (skip > 0), some keys' whole batch does
    "gap_windows_skip": dict(
        win=3, slide=5, count=[10, 14, 3, 20], next_fire=[15, 15, 5, 20],
        keys=RNG.integers(0, 4, 40)),
    "whole_batch_of_a_key_late": dict(
        win=3, slide=5, count=[10, 0, 0, 0], next_fire=[40, 0, 0, 0],
        keys=[0, 0, 1, 0, 1, 0]),
    # keys 4..6 have no slot before this batch: they register in it
    "a_key_registers_mid_batch": dict(
        count=[9, 2, 5, 1], next_fire=[2, 0, 0, 0],
        keys=[0, 5, 1, 5, 4, 0, 6, 6, 5, 2, 4]),
    "a_fused_filter_drops_rows": dict(
        count=[40, 7, 0, 19], next_fire=[33, 0, 0, 12],
        keys=RNG.integers(0, 4, 50), keep=RNG.random(50) < 0.6),
    "filter_and_gaps": dict(
        win=3, slide=5, count=[10, 14, 3, 20], next_fire=[15, 15, 5, 20],
        keys=RNG.integers(0, 4, 40), keep=RNG.random(40) < 0.5),
    # 70 arrivals of key 0 against a ring of 32: the ring grows in prep
    "ring_grows": dict(
        count=[5, 0, 0, 0], next_fire=[0, 0, 0, 0],
        keys=[0] * 70 + [1] * 3, grows=True),
    # counts past the ring (and past int16): only the ring place ships
    "counts_far_past_the_ring": dict(
        count=[100_003, 65_536 + 31, 32, 7], next_fire=[99_990, 65_560, 25, 0],
        keys=RNG.integers(0, 4, 60)),
}


@pytest.mark.parametrize("case", NUMBERING)
def test_rows_numbered_in_the_program_equal_the_parents_host_numbering(case):
    c = NUMBERING[case]
    rep = make_replica(win=c.get("win", 8), slide=c.get("slide", 1),
                       keys=8)
    n0 = len(c["count"])
    for k in range(n0):
        assert rep._keymap.slot(k) == k
    rep.count[:n0] = c["count"]
    rep.next_fire[:n0] = c["next_fire"]
    rep.max_leaf[:n0] = np.asarray(c["count"]) - 1
    before = rep.count.copy(), rep.next_fire.copy(), rep.max_leaf.copy()
    keys = np.asarray(c["keys"], np.int64)
    b = batch(keys, np.arange(len(keys)))
    keep = c.get("keep")
    F0 = rep.F
    seen = prep_once(rep, b, keep)
    assert (rep.F > F0) is bool(c.get("grows"))
    rows = np.arange(len(keys)) if keep is None else np.nonzero(keep)[0]
    slots = np.asarray([rep.slot_of_key[int(k)] for k in keys[rows]])
    want_rows, count, max_leaf, n_late = parent_numbering(
        *before, slots, rep.K_cap, rep.F)
    want = np.full(b.capacity, rep.K_cap * rep.F)
    want[rows] = want_rows
    # the one batch-sized plane: slots, the sentinel on the other rows
    M, cdt = rep._comp_dtype()
    assert M == rep.K_cap and seen["slots_p"].dtype == cdt == np.int16
    assert (seen["slots_p"][rows] == slots).all()
    assert (np.delete(seen["slots_p"], rows) == M).all()
    assert seen["keyrows"].shape == (2, rep.K_cap)
    got = numbered_by_program(seen["slots_p"], seen["keyrows"], rep.K_cap,
                              rep.F)
    assert (got == want).all()
    # ... and the host's books by key are the parent's by row
    assert (rep.count == count).all() and (rep.max_leaf == max_leaf).all()
    assert rep.ignored == n_late == rep.stats.inputs_ignored - (
        0 if keep is None else int((~keep).sum()))
    assert rep.stats.prep_by_key_batches == 1


def oracle(batches, win, slide, keep=None):
    """Per key, arrival order: window ``w`` holds readings ``[w * slide,
    w * slide + win)``; fired complete, then every partial one that holds
    a reading at the flush. ``(key, wid) -> (sum, count, last)``."""
    per_key = {}
    for i, b in enumerate(batches):
        ks = np.asarray(b.host_keys)
        vs = np.asarray(b.fields["v"])[:b.size]
        for j, (k, v) in enumerate(zip(ks.tolist(), vs.tolist())):
            if keep is None or keep(v):
                per_key.setdefault(k, []).append(v)
    out = {}
    for k, vals in per_key.items():
        w = 0
        while w * slide < len(vals):
            seg = vals[w * slide:w * slide + win]
            if seg:
                out[(k, w)] = (np.float32(sum(seg)), len(seg),
                               np.float32(seg[-1]))
            w += 1
    return out


def fired(rep):
    out = {}
    for cols in rep.emitter.cols:
        for i in range(cols["wid"].size):
            if cols["valid"][i]:
                key = (int(cols["key"][i]), int(cols["wid"][i]))
                assert key not in out
                out[key] = (cols["sum"][i], int(cols["count"][i]),
                            cols["last"][i])
    return out


# (f) ``last`` is not commutative: a key's readings must combine in
# arrival order through the in-program numbering, whatever the batch
# boundaries; (d) a ring that grows mid-stream; gap windows end to end
@pytest.mark.parametrize("sizes,kw", [
    ([1, 2, 3, 50, 7, 64, 1, 33], dict(win=8, slide=1)),
    ([40] * 6, dict(win=10, slide=3)),
    ([17, 90, 5, 120, 20], dict(win=8, slide=1, keys=2)),     # ring grows
    ([40] * 8, dict(win=3, slide=5)),                          # gaps: skip
    ([64] * 5, dict(win=16, slide=1, budget=16)),              # fire-only
    # nine keys into four slots: the key table doubles twice mid-stream,
    # and with it the plan buffer and every program
    ([30, 60, 45], dict(win=4, slide=1, keys=9)),
], ids=["ragged_boundaries", "slide3", "ring_grows", "gaps", "drains",
        "key_table_grows"])
def test_a_non_commutative_combine_at_several_batch_boundaries(sizes, kw):
    n_keys = kw.pop("keys", 3)
    batches = stream(sizes, n_keys=n_keys, seed=23)
    rep = make_replica(keys=4, **kw)
    run(rep, batches)
    assert rep.K_cap == (16 if n_keys > 4 else 4)
    want = oracle(batches, kw["win"], kw["slide"])
    got = fired(rep)
    assert got.keys() == want.keys()
    for key, (s, c, last) in want.items():
        assert got[key] == (s, c, last), key
    assert rep.stats.prep_by_key_batches == len(sizes)


def test_a_key_restored_mid_stream_is_numbered_from_its_count():
    """(c) A restore mid-stream: the next batch's rows number from the
    restored ``count``, and a key whose ``next_fire`` the restored state
    holds ahead of its count (re-registered) drops its first arrivals."""
    batches = stream([40, 25, 60, 30], n_keys=3, seed=3)
    whole = make_replica(win=8, slide=2)
    run(whole, batches)
    first = make_replica(win=8, slide=2)
    run(first, batches[:2], flush=False)
    state = first.snapshot_state()
    second = make_replica(win=8, slide=2)
    second.restore_state(state)
    run(second, batches[2:])
    assert first.emitter.rows + second.emitter.rows == whole.emitter.rows
    # the re-registered key: its windows up to arrival 20 are behind it
    late = make_replica(win=8, slide=2)
    late.restore_state(state)
    s = late.slot_of_key[0]
    ahead = int(late.count[s]) + 6
    ahead += -ahead % 2
    late.next_fire[s] = ahead
    late.fired[s] = ahead // 2
    n0 = int(late.count[s])
    run(late, batches[2:])
    arrivals = sum(int((np.asarray(b.host_keys) == 0).sum())
                   for b in batches[2:])
    assert late.ignored == ahead - n0 <= arrivals
    assert min(w for k, w, *_ in late.emitter.rows if k == 0) == ahead // 2


def test_rows_a_fused_prefix_filter_drops_take_no_leaf_and_no_count():
    """(e) ``FusedFfatReplica``: a filter in front of a count-based
    window inside one program. A dropped row carries the sentinel slot:
    no rank, no leaf, no count."""
    from windflow_tpu.tpu.fused_ops import FusedFfatReplica
    from windflow_tpu.tpu.ops_tpu import Filter_TPU

    flt = Filter_TPU(lambda f: f["v"] >= 40.0, name="big", schema=SCHEMA)
    op = Ffat_Windows_TPU(
        lift=lift_sd, combine=comb_sd, key_extractor="key", win_len=8,
        slide_len=1, win_type=WinType.CB, num_win_per_batch=64,
        key_capacity=4, name="win")
    rep = FusedFfatReplica([flt, op], 0)
    rep.emitter = Rows()
    batches = stream([50, 3, 70, 40], n_keys=3, seed=9)
    run(rep, batches)
    want = oracle(batches, 8, 1, keep=lambda v: v >= 40.0)
    got = fired(rep)
    assert got.keys() == want.keys() and len(want) > 20
    for key, row in want.items():
        assert got[key] == row, key
    kept = sum(int((np.asarray(b.fields["v"])[:b.size] >= 40.0).sum())
               for b in batches)
    assert int(rep.count.sum()) == kept
    assert rep.stats.inputs_ignored == sum(b.size for b in batches) - kept


# ---------------------------------------------------------------------------
# 2. the fire program expands its own lanes
# ---------------------------------------------------------------------------
def expanded(rep, chunks, W):
    import jax
    pack, n_groups = rep._pack_fire_arrays(
        chunks, W, rep._chunk_keys(chunks[0]), None)
    assert n_groups == 0 and pack.dtype == np.int32
    assert pack.size == plan_len(W, rep.K_cap, False, 1) == rep._plan_len(W)
    out = jax.jit(plan_lanes, static_argnums=range(1, 8))(
        pack, W, rep.K_cap, rep.F, rep.win_units, rep.slide_units, False, 1)
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("win,slide", [(8, 1), (10, 3), (8, 8), (3, 5)])
@pytest.mark.parametrize("W", [64, 16])     # two widths of a program
@pytest.mark.parametrize("partial", [False, True],
                         ids=["complete", "flush_partial"])
def test_lanes_expanded_from_chunk_rows_equal_the_parents_lanes(
        win, slide, W, partial):
    rep = make_replica(win=win, slide=slide, budget=64, keys=8)
    rng = np.random.default_rng(win * 100 + slide * 10 + W + partial)
    for k in range(6):
        rep._keymap.slot(k)
    F = rep.F
    # six slots anywhere in their rings, counts past the ring's length
    nf = rng.integers(0, 5, 6) * slide + rng.integers(0, 4, 6) * F * slide
    span = rng.integers(1, F - 1, 6)
    rep.next_fire[:6], rep.fired[:6] = nf, nf // slide
    rep.max_leaf[:6] = nf + span - 1
    rep.count[:6] = nf + span
    rep.count[3] = nf[3]                # a slot that holds nothing
    rep.max_leaf[3] = nf[3] - 1
    slots, k = rep._eligible(None, partial)
    assert 3 not in slots.tolist() and slots.size
    chunks = rep._take(slots, rep._clip(k, W))
    assert 0 < int(chunks[2].sum()) <= W
    got = expanded(rep, chunks, W)
    fire, evicted = parent_lanes(chunks, W, F, win, slide, rep.K_cap)
    for name, g, want in zip(
            ("slot", "start", "len", "wid", "mask", "round"), got, fire):
        assert (g == want).all(), name
    eflat = got[6]
    assert eflat.size == W * slide
    live = eflat[eflat < rep.K_cap * 2 * F]
    assert len(set(live.tolist())) == live.size
    assert set(live.tolist()) == evicted
    if partial and slide <= win:
        assert (fire[2][fire[4] == 1] < win).any()    # clipped by the data


def test_a_plan_of_nothing_and_a_plan_of_one_full_program():
    rep = make_replica(win=8, slide=1, budget=64, keys=4)
    empty = (np.zeros(0, np.int64),) * 5
    for a in expanded(rep, empty, 64)[:6]:
        assert not a.any()
    # one slot, 16 windows: every lane live, chunk rows beyond it blank
    one = (np.array([2]), np.array([37]), np.array([16]), np.array([37]),
           np.array([37 + 23]))
    slot, start, ln, wid, mask, rnd, _e, _key = expanded(rep, one, 16)
    assert mask.all() and (slot == 2).all() and (ln == 8).all()
    assert (rnd == np.arange(16)).all() and (wid == 37 + rnd).all()
    assert (start == (37 + rnd) % rep.F).all()


# ---------------------------------------------------------------------------
# 3. a fired batch leaves with its keys by chunk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("keys", [
    np.array([7, 3, 11], np.int64), [("a", 1), ("b", 2), ("c", 3)]],
    ids=["int_array", "object_list"])
def test_chunked_keys_expand_to_a_key_a_row(keys):
    counts = np.array([2, 0, 3])
    b = BatchTPU({}, np.zeros(8, np.int64), 5, SCHEMA, 0,
                 ChunkedKeys(keys, counts))
    assert isinstance(b._host_keys, ChunkedKeys)        # not read yet
    assert isinstance(b.copy_for_dest()._host_keys, ChunkedKeys)
    got = b.host_keys
    if isinstance(keys, np.ndarray):
        assert isinstance(got, np.ndarray)
        assert (got == np.repeat(keys, counts)).all()
    else:
        assert got == [keys[0]] * 2 + [keys[2]] * 3
    assert b.host_keys is got                           # expanded once


def test_a_fired_batch_carries_its_keys_by_chunk():
    rep = make_replica(win=8, slide=1, budget=64)
    seen = []
    emit = rep.emitter.emit_device_batch
    rep.emitter.emit_device_batch = lambda b: (seen.append(b), emit(b))
    run(rep, stream([60, 60], n_keys=3, seed=2))
    assert seen and all(isinstance(b._host_keys, ChunkedKeys) for b in seen)
    for b, cols in zip(seen, rep.emitter.cols):
        assert (np.asarray(b.host_keys) == cols["key"]).all()
        assert b.host_keys.shape == (b.size,)


@pytest.mark.parametrize("keys", [
    np.array([7, 3, 11], np.int64), [("a", 1), ("b", 2), ("c", 3)], None],
    ids=["int_array", "object_list", "no_keys"])
def test_emit_compacted_hands_on_the_kept_rows_keys(keys):
    """A filter behind the window operator: the keyed consumer after it
    gets the kept rows' keys, in the kept rows' order."""
    import jax
    from windflow_tpu.tpu.ops_tpu import Filter_TPU

    flt = Filter_TPU(lambda f: f["v"] > 0, name="f", schema=SCHEMA)
    flt.build_replicas()
    rep = flt.replicas[0]
    out = []
    rep.emitter = type("E", (), {
        "emit_device_batch": lambda self, b: out.append(b)})()
    counts = np.array([2, 1, 3])
    hk = None if keys is None else ChunkedKeys(keys, counts)
    fields = {"v": jax.device_put(np.arange(8, dtype=np.float32))}
    b = BatchTPU(fields, np.arange(8, dtype=np.int64), 6, SCHEMA, 0, hk)
    order = np.array([4, 0, 2, 1, 3, 5, 6, 7], np.int32)   # keep 4, 0, 2
    rep.emit_compacted(b, fields, jax.device_put(order), 3)
    (nb,) = out
    assert nb.size == 3 and nb.ts_host[:3].tolist() == [4, 0, 2]
    if keys is None:
        assert nb.host_keys is None
    elif isinstance(keys, np.ndarray):
        assert isinstance(nb.host_keys, np.ndarray)
        assert nb.host_keys.tolist() == [11, 7, 3]
    else:
        assert nb.host_keys == [("c", 3), ("a", 1), ("b", 2)]


# ---------------------------------------------------------------------------
# who else runs the changed code: a time-based operator takes what it took
# ---------------------------------------------------------------------------
def test_both_window_types_take_the_same_arguments_and_plan_by_chunk():
    """Since PR 39 a time-based program takes the count-based layout:
    the same arguments, a chunk row a firing slot, a program a width;
    its head is the group table where a count-based one's is the
    ``keyrows``."""
    ops = {}
    for wt in (WinType.TB, WinType.CB):
        op = Ffat_Windows_TPU(
            lift=lift_sd, combine=comb_sd, key_extractor="key", win_len=8,
            slide_len=2, win_type=wt, num_win_per_batch=64, key_capacity=4,
            name="win")
        op.build_replicas()
        ops[wt] = op.replicas[0]
    tb, cb = ops[WinType.TB], ops[WinType.CB]
    for rep in ops.values():
        step = rep._make_step(16, W=64)
        assert list(inspect.signature(step._wrapped_jit).parameters) == [
            "fields", "comp", "trees", "tvalid", "fire_plan"]
        assert rep._key_words() == 1
    chunks = (np.arange(2), np.full(2, 8), np.full(2, 3), np.full(2, 4),
              np.full(2, 20))
    for k in (7, 9):
        assert tb._keymap.slot(k) == cb._keymap.slot(k)
    # time-based: the composite, the group table and the chunk rows
    assert tb._comp_dtype() == (tb.K_cap * tb.F, np.int16)
    assert tb._plan_len(64) == plan_len(64, tb.K_cap, True, 1) \
        == 1 + 70 + 6 * tb.K_cap
    pairs = np.unique(tb._range_words(chunks[1], chunks[2],
                                      chunks[4] + 1)[0])
    pack, n = tb._pack_fire_arrays(chunks, 64, tb._chunk_keys(chunks[0]),
                                   pairs)
    groups, rows, total = plan_views(pack, tb.K_cap, True, 1)
    assert pack.size == tb._plan_len(64) and total[0] == 6
    assert n == 3 == groups[32, 0]          # three rounds, three ranges
    assert rows[:, :2].tolist() == [[0, 1], [8, 8], [3, 3], [4, 4],
                                    [13, 13], [7, 9]]
    # count-based: the slots and a few words a key, no ranges
    assert cb._comp_dtype() == (cb.K_cap, np.int16)
    assert cb._plan_len(64) == plan_len(64, cb.K_cap, False, 1) \
        == 1 + 2 * cb.K_cap + 6 * cb.K_cap
    assert plan_len(2, cb.K_cap, False, 1) == 1 + 2 * cb.K_cap + 6 * 2
    pack, n = cb._pack_fire_arrays(chunks, 64, cb._chunk_keys(chunks[0]),
                                   None)
    keyrows, rows, total = plan_views(pack, cb.K_cap, False, 1)
    assert n == 0 and total[0] == 6 and not keyrows.any()
    assert rows[:, :2].tolist() == [[0, 1], [8, 8], [3, 3], [4, 4],
                                    [13, 13], [7, 9]]
    # one jitted function a width, for both
    for rep in ops.values():
        rep._ensure_forest(batch([0], [1.0]).fields)
        rep._full_step(rep._step_keys(16)[0], 16, 64)
        rep._fire_step(64)
        assert {k[-1] for k in rep._prog_cache} == {64}


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------
def test_the_counters_say_how_often_the_mechanism_engages():
    """``Fire_plan_rows / Fire_programs`` is at most the keys and
    ``Prep_by_key_batches`` every batch on a count-based operator; on a
    time-based one no batch is prepared by key and the plan's rows are
    its chunks too (its lanes before PR 39)."""
    import test_ffat_grouped_fire as tg

    rep = make_replica(win=8, slide=1, budget=64, keys=4)
    batches = stream([60] * 5, n_keys=3, seed=4)
    run(rep, batches)
    d = rep.stats.to_dict()
    assert d["Fire_programs"] >= 5
    assert 0 < d["Fire_plan_rows"] <= 3 * d["Fire_programs"]
    assert d["Fire_plan_rows"] < d["Windows_fired"] / 10
    assert d["Prep_by_key_batches"] == 5 == rep.stats.stage_count("prep")
    timed = tg.make_replica(win=4, slide=1, budget=8, keys=4)
    for i in range(6):
        timed.handle_msg(0, tg.batch([0, 1, 2], [i, i, i], [1., 2., 3.],
                                     wm_pane=i))
    timed.flush_on_termination()
    d = timed.stats.to_dict()
    assert d["Fire_programs"] > 0 and d["Prep_by_key_batches"] == 0
    assert d["Fire_plan_rows"] <= 3 * d["Fire_programs"] < d["Windows_fired"]
