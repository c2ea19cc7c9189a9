"""``Interval_Join_TPU`` (``windflow_tpu/tpu/join_tpu.py``) on the CPU
backend at small sizes, through ``PipeGraph`` and the public builders:
held to a brute-force numpy model (every pair of equal keys with ``ts_b``
in ``[ts_a - lower, ts_a + upper]``, exactly once) and to the per-tuple
``Interval_Join`` over the same seeded streams. Rows carry a number of
their own, so a delivered pair says which two rows it is."""

import threading

import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, Interval_Join_Builder, PipeGraph,
                          Sink_Builder, Source_Builder, TimePolicy,
                          WindFlowError)
from windflow_tpu.tpu import (Filter_TPU_Builder, Interval_Join_TPU_Builder,
                              Map_TPU_Builder)

BLK = 64


def stream(seed, n, keys, span, start=0):
    """``n`` rows in event-time order: key, the row's number, time."""
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, keys, n).astype(np.int32),
            "v": np.arange(n, dtype=np.int32),
            "ts": start + np.sort(rng.integers(0, span, n)).astype(np.int64)}


def brute(a, b, lower, upper):
    """Sorted ``(a row, b row)`` of every matching pair."""
    m = ((a["k"][:, None] == b["k"][None, :])
         & (b["ts"][None, :] >= a["ts"][:, None] - lower)
         & (b["ts"][None, :] <= a["ts"][:, None] + upper))
    return sorted(zip(*(x.tolist() for x in np.nonzero(m))))


def block_source(s, name, blk=BLK, after=None, done=None):
    """Pushes ``s`` a block of ``blk`` rows at a time, the watermark just
    below a block's first time before it and at its last after; waits
    for ``after`` first and sets ``done`` at the end (who arrives
    first)."""
    def src(shipper, ctx=None):
        if after is not None:
            after.wait(30)
        wm = 0
        for i in range(0, len(s["ts"]), blk):
            sl = slice(i, i + blk)
            wm = max(wm, int(s["ts"][sl][0]) - 1)
            shipper.set_next_watermark(wm)
            shipper.push_columns({"k": s["k"][sl], name: s["v"][sl]},
                                 ts=s["ts"][sl])
            wm = max(wm, int(s["ts"][sl][-1]))
            shipper.set_next_watermark(wm)
        if done is not None:
            done.set()
    return src


def row_source(s, name):
    def src(shipper, ctx=None):
        for k, v, ts in zip(s["k"].tolist(), s["v"].tolist(),
                            s["ts"].tolist()):
            shipper.set_next_watermark(max(0, ts - 1))
            shipper.push_with_timestamp({"k": k, name: v}, ts)
    return src


class Pairs:
    """Columnar sink: the delivered pairs and their stamps."""

    def __init__(self):
        self.va, self.vb, self.ts = [], [], []

    def __call__(self, cols, ts):
        if cols is not None:
            self.va += cols["va"].tolist()
            self.vb += cols["vb"].tolist()
            self.ts += ts.tolist()

    def sorted(self):
        return sorted(zip(self.va, self.vb))


def join_builder(lower, upper):
    return (Interval_Join_TPU_Builder(
                lambda a, b: {"k": a["k"], "va": a["va"], "vb": b["vb"]})
            .with_key_by("k").with_boundaries(lower, upper).with_kp_mode()
            .with_name("join"))


def run_device(a, b, lower, upper, *, blk=BLK, order=None, par=1,
               src_par=1, capacity=None):
    """Two block sources -> merge -> the device join -> columnar sink.
    ``order``: ``"a_first"`` / ``"b_first"`` holds one source back until
    the other has ended; ``capacity``: the archives' rows, A's and B's."""
    gate = threading.Event()
    first = {"a_first": "a", "b_first": "b"}.get(order)
    out = Pairs()
    g = PipeGraph("join_tpu", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pipes = {}
    for side, s in (("a", a), ("b", b)):
        parts = [{k: v[i::src_par] for k, v in s.items()}
                 for i in range(src_par)]

        def make(parts, side):
            def src(shipper, ctx):
                block_source(parts[ctx.get_replica_index()], "v" + side,
                             blk,
                             after=None if first in (None, side) else gate,
                             done=gate if first == side else None)(shipper)
            return src

        pipes[side] = g.add_source(
            Source_Builder(make(parts, side)).with_name("src_" + side)
            .with_parallelism(src_par).with_output_batch_size(blk).build())
    join = join_builder(lower, upper).with_parallelism(par)
    if capacity is not None:
        join = join.with_archive_capacity(*capacity)
    pipes["a"].merge(pipes["b"]).add(join.build()).add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    stats = {o["name"]: o["replicas"] for o in g.get_stats()["Operators"]}
    return out, stats


def run_per_tuple(a, b, lower, upper):
    got = []
    g = PipeGraph("join_cpu", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pa = g.add_source(Source_Builder(row_source(a, "va")).build())
    pb = g.add_source(Source_Builder(row_source(b, "vb")).build())
    j = (Interval_Join_Builder(lambda x, y: (x["va"], y["vb"]))
         .with_key_by(lambda t: t["k"]).with_boundaries(lower, upper)
         .with_kp_mode().build())
    pa.merge(pb).add(j).add_sink(Sink_Builder(
        lambda t: got.append(t) if t is not None else None).build())
    g.run()
    return sorted(got)


def total(stats, field, op="join"):
    return sum(r[field] for r in stats[op])


# ---------------------------------------------------------------------------
# the device join against the numpy model and the per-tuple operator
# ---------------------------------------------------------------------------
CASES = {
    # name: (a: seed rows keys span, b: likewise, lower, upper, options)
    "many_to_many": ((1, 400, 12, 40_000), (2, 900, 12, 40_000), 300, 700,
                     {}),
    "asymmetric_bounds": ((3, 300, 8, 30_000), (4, 500, 8, 30_000), 0, 2_000,
                          {}),
    "lower_only": ((5, 300, 8, 30_000), (6, 500, 8, 30_000), 1_500, 0, {}),
    "zero_bounds": ((7, 600, 4, 400), (8, 600, 4, 400), 0, 0, {}),
    "b_before_a": ((9, 300, 10, 30_000), (10, 600, 10, 30_000), 400, 400,
                   {"order": "b_first"}),
    "a_before_b": ((11, 300, 10, 30_000), (12, 600, 10, 30_000), 400, 400,
                   {"order": "a_first"}),
    # bounds as wide as the stream: B's archive holds all of it, 71
    # batches, past the 64 slots it starts with
    "archive_growth": ((13, 300, 6, 5_000), (14, 4_500, 6, 5_000), 5_000,
                       5_000, {}),
    "kp_parallelism_2": ((15, 500, 16, 40_000), (16, 900, 16, 40_000), 500,
                         500, {"par": 2, "src_par": 2}),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    sa, sb, lower, upper, opts = CASES[request.param]
    a, b = stream(*sa), stream(*sb)
    out, stats = run_device(a, b, lower, upper, **opts)
    return {"name": request.param, "a": a, "b": b, "lower": lower,
            "upper": upper, "out": out, "stats": stats}


def test_every_pair_of_the_model_is_delivered_exactly_once(case):
    want = brute(case["a"], case["b"], case["lower"], case["upper"])
    assert len(want) > 100
    assert case["out"].sorted() == want
    assert total(case["stats"], "Join_pairs") == len(want)


def test_the_per_tuple_join_delivers_the_same_pairs(case):
    assert case["out"].sorted() == run_per_tuple(
        case["a"], case["b"], case["lower"], case["upper"])


def test_a_pair_is_stamped_with_its_later_member(case):
    out, a, b = case["out"], case["a"], case["b"]
    assert (np.asarray(out.ts) == np.maximum(
        a["ts"][out.va], b["ts"][out.vb])).all()


def test_counters_account_for_every_row(case):
    st, a, b = case["stats"], case["a"], case["b"]
    assert total(st, "Join_probe_rows_a") == len(a["ts"])
    assert total(st, "Join_probe_rows_b") == len(b["ts"])
    assert total(st, "Join_archived_rows_a") == len(a["ts"])
    assert total(st, "Join_late_probes") == 0 == total(st, "Late_records")
    assert total(st, "Join_output_batches") >= 1
    assert total(st, "Join_host_total_usec") > 0
    # what was archived and is gone was purged, and it was most of it
    live = total(st, "Join_archive_rows")
    assert total(st, "Join_purged_rows") >= len(a["ts"]) + len(b["ts"]) \
        - live - 2 * BLK * len(st["join"])
    # an archive doubles where it has to hold more batches than the slots
    # it starts with (elsewhere only if one source runs far ahead)
    if case["name"] == "archive_growth":
        assert total(st, "Join_archive_growths") > 0


def test_fan_out_past_one_output_batch():
    """Three keys, 40 A rows and 120 B rows of each inside one interval:
    a batch's pairs outnumber its 64 lanes many times over, and the
    excess leaves through ``jit_join_more``."""
    a, b = stream(21, 120, 3, 500), stream(22, 360, 3, 500)
    out, stats = run_device(a, b, 1_000, 1_000)
    want = brute(a, b, 1_000, 1_000)
    assert len(want) == sum(
        int((a["k"] == k).sum()) * int((b["k"] == k).sum()) for k in range(3))
    assert out.sorted() == want
    assert total(stats, "Join_output_batches") >= len(want) // BLK
    assert total(stats, "Join_output_batches") \
        > 4 * total(stats, "Device_batches_in")


def test_a_key_on_one_side_only_delivers_nothing():
    a, b = stream(23, 200, 5, 10_000), stream(24, 300, 5, 10_000)
    b["k"] += 100
    out, stats = run_device(a, b, 5_000, 5_000)
    assert out.sorted() == [] == brute(a, b, 5_000, 5_000)
    assert total(stats, "Join_pairs") == 0 == total(
        stats, "Join_output_batches")


def run_one_source(a, b, lower, upper, blk=256, keep=None, channel=None,
                   after=False):
    """One source of both kinds in event-time order -> Map_TPU -> device
    split -> a branch a kind -> merge -> the join: the two inputs stay
    within a few blocks of each other. ``keep`` (a flag a row of A) puts
    a ``Filter_TPU`` ``some`` on A's branch; ``channel`` bounds every
    channel (how far one branch can run ahead of the other); ``after``
    puts a ``Map_TPU`` ``after`` behind the join (what the stage after
    it sees of its watermarks)."""
    ts = np.concatenate([a["ts"], b["ts"]])
    order = np.argsort(ts, kind="stable")
    flag = np.ones(len(a["ts"]), np.int32) if keep is None \
        else keep.astype(np.int32)
    mixed = {"k": np.concatenate([a["k"], b["k"]])[order],
             "v": np.concatenate([a["v"], b["v"]])[order],
             "kind": np.concatenate([np.zeros(len(a["ts"]), np.int32),
                                     np.ones(len(b["ts"]), np.int32)])[order],
             "keep": np.concatenate([flag, np.ones(len(b["ts"]),
                                                   np.int32)])[order],
             "ts": ts[order]}

    def src(shipper, ctx=None):
        for i in range(0, len(mixed["ts"]), blk):
            sl = slice(i, i + blk)
            shipper.set_next_watermark(max(0, int(mixed["ts"][sl][0]) - 1))
            shipper.push_columns({k: mixed[k][sl] for k in
                                  ("k", "v", "kind", "keep")},
                                 ts=mixed["ts"][sl])

    out = Pairs()
    g = PipeGraph("one_source", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  **({} if channel is None else {"channel_capacity": channel}))
    pipe = g.add_source(Source_Builder(src).with_output_batch_size(blk)
                        .build()).add(Map_TPU_Builder(dict).build())
    pipe.split("kind", 2)
    pa = pipe.select(0)
    if keep is not None:
        pa = pa.add(Filter_TPU_Builder(lambda f: f["keep"] == 1)
                    .with_name("some").build())
    pa = pa.add(Map_TPU_Builder(
        lambda f: {"k": f["k"], "va": f["v"]}).build())
    pb = pipe.select(1).add(Map_TPU_Builder(
        lambda f: {"k": f["k"], "vb": f["v"]}).build())
    joined = pa.merge(pb).add(join_builder(lower, upper).build())
    if after:
        joined = joined.add(Map_TPU_Builder(dict).with_name("after").build())
    joined.add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    return out, {o["name"]: o["replicas"]
                 for o in g.get_stats()["Operators"]}


def test_both_inputs_from_one_block_through_split_and_merge():
    """A pair's two rows reach the join in batches made from one block,
    either first."""
    a, b = stream(25, 500, 9, 50_000), stream(26, 1_500, 9, 50_000)
    out, _ = run_one_source(a, b, 600, 900)
    assert out.sorted() == brute(a, b, 600, 900)


@pytest.mark.parametrize("silenced_by", ["filter", "split"])
def test_a_silent_side_still_moves_the_watermark(silenced_by):
    """Input A sends nothing for eighty batches on end: a filter on its
    branch keeps nothing of them, or the split finds no row of its kind
    in them. Each batch still carries its watermark on, so the join's
    aligned watermark follows B and B's archive is purged meanwhile.
    Held back, B's ring (64 slots to start with) would have to take the
    stretch's eighty batches and double; as it is it holds what the
    branches' short channels let B run ahead by."""
    a, b = stream(27, 6_400, 6, 640_000), stream(28, 6_400, 6, 640_000)
    quiet = (a["ts"] >= 64_000) & (a["ts"] < 576_000)
    kept = {k: v[~quiet] for k, v in a.items()}
    want = [(int(kept["v"][i]), j) for i, j in brute(kept, b, 500, 500)]
    if silenced_by == "filter":
        out, stats = run_one_source(a, b, 500, 500, blk=128, keep=~quiet,
                                    channel=2)
        assert total(stats, "Inputs_ignored", "some") == int(
            quiet.sum()) > 5_000
        assert total(stats, "Punctuations_sent", "some") >= 76
    else:
        out, stats = run_one_source(kept, b, 500, 500, blk=128, channel=2)
    assert out.sorted() == sorted(want) and len(want) > 300
    assert total(stats, "Join_archive_growths") == 0
    assert total(stats, "Join_purged_rows") > 6_400


def run_in_turns(a, b, lower, upper, rounds):
    """Two sources that push a block each in turns, A's first: the join
    takes A(0), B(0), A(1), B(1), ... A ``Map_TPU`` ``after`` behind it
    (what the stage after the join sees of its watermarks)."""
    turn = {"a": threading.Semaphore(1), "b": threading.Semaphore(0)}

    def source(s, name, mine, other):
        def src(shipper, ctx=None):
            n = len(s["ts"]) // rounds
            wm = 0
            for i in range(rounds):
                sl = slice(i * n, (i + 1) * n if i < rounds - 1 else None)
                turn[mine].acquire(timeout=30)
                wm = max(wm, int(s["ts"][sl][0]) - 1)
                shipper.set_next_watermark(wm)
                shipper.push_columns({"k": s["k"][sl], name: s["v"][sl]},
                                     ts=s["ts"][sl])
                wm = max(wm, int(s["ts"][sl][-1]))
                shipper.set_next_watermark(wm)
                turn[other].release()
        return src

    out = Pairs()
    g = PipeGraph("in_turns", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pa = g.add_source(Source_Builder(source(a, "va", "a", "b"))
                      .with_name("src_a")
                      .with_output_batch_size(len(a["ts"]) // rounds).build())
    pb = g.add_source(Source_Builder(source(b, "vb", "b", "a"))
                      .with_name("src_b")
                      .with_output_batch_size(len(b["ts"]) // rounds).build())
    pa.merge(pb).add(join_builder(lower, upper).build()).add(
        Map_TPU_Builder(dict).with_name("after").build()).add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    return out, {o["name"]: o["replicas"]
                 for o in g.get_stats()["Operators"]}


@pytest.mark.parametrize("bounds", [(600, 900), (0, 2_000), (2_000, 0)])
def test_an_a_batch_ahead_of_input_b_waits_for_it(bounds):
    """The two inputs abreast, A's batch of every stretch first: it
    waits until B has passed its interval and then delivers its pairs in
    ONE output batch; taken at once, it would deliver those with the
    stretch before and B's batch after it the rest, in a batch of its
    own. Every pair once all the same, none late for the stage after
    the join: the watermark it is sent stays behind a waiting batch's
    oldest event."""
    rounds = 16
    a, b = stream(31, 1_024, 40, 160_000), stream(32, 4_096, 40, 160_000)
    # the same stretch of event time a round on both inputs
    for s in (a, b):
        n = len(s["ts"]) // rounds
        for i in range(rounds):
            s["ts"][i * n:(i + 1) * n] = np.sort(
                10_000 * i + s["ts"][i * n:(i + 1) * n] % 10_000)
    out, stats = run_in_turns(a, b, *bounds, rounds)
    assert out.sorted() == brute(a, b, *bounds)
    assert len(out.va) > 500
    assert total(stats, "Join_batches_held") >= rounds - 2
    # (the first A batch of all has seen nothing of B to wait for)
    assert total(stats, "Join_output_batches") <= rounds + 1
    for op in ("join", "after"):
        assert total(stats, "Late_records", op) == 0 == total(
            stats, "Late_dropped", op), op


def test_both_inputs_abreast_through_one_split_every_pair_once_none_late():
    """As the cell has them: both inputs from one block through a split,
    either first by the threads' turns; whichever waits or does not,
    every pair is delivered once and none is late downstream."""
    a, b = stream(35, 1_500, 9, 150_000), stream(36, 4_500, 9, 150_000)
    out, stats = run_one_source(a, b, 600, 900, after=True)
    assert out.sorted() == brute(a, b, 600, 900)
    for op in ("join", "after"):
        assert total(stats, "Late_records", op) == 0 == total(
            stats, "Late_dropped", op), op


def test_waiting_a_batches_are_taken_when_input_b_goes_quiet():
    """Input B sends its first block and no more until A has ended: of
    the A batches behind it at most ``HOLD_MAX`` wait at a time, the idle
    tick takes what waits, and B's later rows find every one archived."""
    from windflow_tpu.tpu.join_tpu import HOLD_MAX

    a, b = stream(33, 640, 5, 40_000), stream(34, 640, 5, 40_000)
    gate, first_b = threading.Event(), threading.Event()
    out = Pairs()
    g = PipeGraph("b_quiet", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)

    def src_b(shipper, ctx=None):
        head = {k: v[:BLK] for k, v in b.items()}
        block_source(head, "vb", done=first_b)(shipper)
        gate.wait(30)
        block_source({k: v[BLK:] for k, v in b.items()}, "vb")(shipper)

    pa = g.add_source(Source_Builder(
        block_source(a, "va", after=first_b, done=gate))
        .with_output_batch_size(BLK).build())
    pb = g.add_source(Source_Builder(src_b).with_output_batch_size(BLK)
                      .build())
    pa.merge(pb).add(join_builder(3_000, 3_000).build()).add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    stats = {o["name"]: o["replicas"] for o in g.get_stats()["Operators"]}
    assert out.sorted() == brute(a, b, 3_000, 3_000)
    assert 1 <= total(stats, "Join_batches_held") <= len(a["ts"]) // BLK
    assert HOLD_MAX == 2


def test_a_stream_longer_than_int32_microseconds():
    """Event time from 0 to past 2**33 us in steps the bounds span: the
    base the offsets count from moves with the purge. (Both inputs from
    one source over short channels: the device holds what lies within
    2**29 us of the watermark, 23 of these blocks, and refuses by name an
    input that runs further ahead of the other.)"""
    n = 6_000
    rng = np.random.default_rng(29)
    step = (1 << 33) // n
    a = {"k": rng.integers(0, 4, n).astype(np.int32),
         "v": np.arange(n, dtype=np.int32),
         "ts": np.arange(n, dtype=np.int64) * step}
    b = {"k": rng.integers(0, 4, n).astype(np.int32),
         "v": np.arange(n, dtype=np.int32),
         "ts": np.arange(n, dtype=np.int64) * step + step // 3}
    assert b["ts"][-1] > 1 << 32
    out, stats = run_one_source(a, b, 3 * step, 3 * step, blk=32, channel=2)
    want = brute(a, b, 3 * step, 3 * step)
    assert len(want) > 6_000 and out.sorted() == want
    assert (np.asarray(out.ts) == np.maximum(a["ts"][out.va],
                                             b["ts"][out.vb])).all()
    assert max(out.ts) > 1 << 32


def test_late_rows_probe_what_is_left_as_the_per_tuple_join():
    """The per-tuple plane's purge rule, with lateness 0 on an in-order
    stream: the same pairs and no late probe; and a row behind its own
    purge line is counted and still probes what is left."""
    a, b = stream(31, 400, 5, 20_000), stream(32, 400, 5, 20_000)
    out, stats = run_device(a, b, 300, 300)
    assert out.sorted() == run_per_tuple(a, b, 300, 300)
    assert total(stats, "Join_late_probes") == 0
    # B's first block comes again at the end, far behind the watermark
    late = {k: np.concatenate([v, v[:BLK]]) for k, v in b.items()}
    out2, stats2 = run_device(a, late, 300, 300, order="a_first")
    assert total(stats2, "Join_late_probes") == BLK
    assert total(stats2, "Late_records") >= BLK
    # nothing of A that old is left: the late rows find nothing, and the
    # rest is what it was
    assert out2.sorted() == out.sorted()


# ---------------------------------------------------------------------------
# an archive sized for the deployment, donated to its step
# ---------------------------------------------------------------------------
def test_a_given_capacity_gives_the_growing_rings_pairs_without_growth():
    """B's archive holds 71 batches: from 64 slots it doubles; allocated
    for 128 batches at its first batch, it never does."""
    sa, sb, lower, upper, _ = CASES["archive_growth"]
    a, b = stream(*sa), stream(*sb)
    grown, st_grown = run_device(a, b, lower, upper)
    sized, st_sized = run_device(a, b, lower, upper,
                                 capacity=(None, 128 * BLK))
    assert sized.sorted() == grown.sorted() == brute(a, b, lower, upper)
    assert total(st_grown, "Join_archive_growths") > 0
    assert total(st_sized, "Join_archive_growths") == 0
    # B's 128 slots of a batch, A's 64 (no capacity given)
    assert total(st_sized, "Join_archive_capacity_rows") == 192 * BLK
    assert total(st_grown, "Join_archive_capacity_rows") == (128 + 64) * BLK


def test_a_batch_past_the_capacity_still_grows_and_is_counted():
    sa, sb, lower, upper, _ = CASES["archive_growth"]
    a, b = stream(*sa), stream(*sb)
    out, st = run_device(a, b, lower, upper, capacity=(2 * BLK, 8 * BLK))
    assert out.sorted() == brute(a, b, lower, upper)
    # 8 slots doubled to 128 for B's 71 batches; A's two to 8 for its 5
    assert total(st, "Join_archive_growths") == 4 + 2
    assert total(st, "Join_archive_capacity_rows") == (128 + 8) * BLK


def test_a_capacity_is_a_number_of_rows():
    with pytest.raises(WindFlowError, match="capacity"):
        join_builder(1, 1).with_archive_capacity(None, 0).build()


def test_further_batches_are_gathered_before_the_next_step_takes_the_ring(
        monkeypatch):
    """Both inputs through one split: the join's launches alternate
    sides, so a step whose pairs outnumber its first output batch is
    followed by a step donated the archive its further batches gather
    from. Those are gathered before that launch (``_resolve``), its
    finish emits them after it, and every pair leaves once with its
    stamp."""
    from windflow_tpu.tpu.join_tpu import IntervalJoinTPUReplica

    gathered = []
    resolve = IntervalJoinTPUReplica._resolve

    def spy(self, hold, bid):
        resolve(self, hold, bid)
        gathered.append(len(hold["more"]))

    monkeypatch.setattr(IntervalJoinTPUReplica, "_resolve", spy)
    a, b = stream(43, 600, 3, 6_000), stream(44, 1_800, 3, 6_000)
    out, stats = run_one_source(a, b, 1_000, 1_000, blk=64)
    want = brute(a, b, 1_000, 1_000)
    assert out.sorted() == want and len(want) > 50_000
    assert (np.asarray(out.ts) == np.maximum(a["ts"][out.va],
                                             b["ts"][out.vb])).all()
    assert sum(gathered) > 10
    assert total(stats, "Join_output_batches") > len(want) // 64


def test_a_row_whose_valid_is_false_neither_probes_nor_is_archived():
    """A ``valid`` column of booleans marks the rows that are there (a
    window operator fires an empty window's row with it False): a row
    with False meets nothing, whichever input comes later."""
    a, b = stream(45, 400, 6, 40_000), stream(46, 900, 6, 40_000)
    valid = a["v"] % 3 != 0

    def src_a(shipper, ctx=None):
        for i in range(0, len(a["ts"]), BLK):
            sl = slice(i, i + BLK)
            shipper.set_next_watermark(max(0, int(a["ts"][sl][0]) - 1))
            shipper.push_columns({"k": a["k"][sl], "va": a["v"][sl],
                                  "valid": valid[sl]}, ts=a["ts"][sl])

    out = Pairs()
    g = PipeGraph("valid", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=2)
    pa = g.add_source(Source_Builder(src_a).with_output_batch_size(BLK)
                      .build())
    pb = g.add_source(Source_Builder(block_source(b, "vb"))
                      .with_output_batch_size(BLK).build())
    pa.merge(pb).add(join_builder(700, 300).build()).add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    kept = {k: v[valid] for k, v in a.items()}
    want = sorted((int(kept["v"][i]), j) for i, j in brute(kept, b, 700, 300))
    assert out.sorted() == want and len(want) > 100
    assert len(brute(a, b, 700, 300)) > len(want)


def test_a_window_feeding_input_a_delivers_the_per_tuple_joins_pairs():
    """``Ffat_Windows_TPU``'s fired rows as input A, as they stand: a
    keyed tumbling max over one stream joined with another on the key,
    each window's row against B over ``[T - win, T]`` (its row is
    stamped ``T - 1``), exactly the pairs of the per-tuple
    ``Interval_Join`` over the window's rows as a plain model fires them:
    every window of a key that holds a row."""
    import jax.numpy as jnp

    from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

    win = 4_000
    x, b = stream(47, 1_200, 4, 60_000), stream(48, 1_500, 4, 60_000)
    # the model's rows: (key, window, its max), stamped T - 1
    w = x["ts"] // win
    rows = sorted({(int(k), int(i)) for k, i in zip(x["k"], w)})
    top = {r: int(x["v"][(x["k"] == r[0]) & (w == r[1])].max())
           for r in rows}
    a = {"k": np.array([k for k, _ in rows], np.int32),
         "ts": np.array([(i + 1) * win - 1 for _, i in rows], np.int64)}
    order = np.argsort(a["ts"], kind="stable")
    a = {k: v[order] for k, v in a.items()}
    a["v"] = np.arange(len(rows), dtype=np.int32)     # its place, in time
    want = run_per_tuple(a, b, win - 1, 1)
    assert want == brute(a, b, win - 1, 1) and len(want) > 300

    got = []

    def sink(cols, ts):
        if cols is not None:
            got.extend(zip(cols["k"].tolist(), cols["wid"].tolist(),
                           cols["m"].tolist(), cols["vb"].tolist()))

    g = PipeGraph("win_join", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME,
                  channel_capacity=2)
    px = g.add_source(Source_Builder(block_source(x, "vx"))
                      .with_output_batch_size(BLK).build())
    px.add(Ffat_Windows_TPU_Builder(
               lambda f: {"m": f["vx"]},
               lambda p, q: {"m": jnp.maximum(p["m"], q["m"])})
           .with_key_by("k").with_tb_windows(win, win).with_key_capacity(8)
           .with_name("max").build())
    pb = g.add_source(Source_Builder(block_source(b, "vb"))
                      .with_output_batch_size(BLK).build())
    px.merge(pb).add(
        Interval_Join_TPU_Builder(
            lambda p, q: {"k": p["k"], "wid": p["wid"], "m": p["m"],
                          "vb": q["vb"]})
        .with_key_by("k").with_boundaries(win - 1, 1).with_kp_mode()
        .with_name("join").build()).add_sink(
        Sink_Builder(sink).with_columns().build())
    g.run()
    at = {(int(k), int(i)): j for j, (k, i) in enumerate(
        zip(a["k"].tolist(), (a["ts"] // win).tolist()))}
    assert sorted((at[(k, i)], vb) for k, i, _, vb in got) == want
    assert all(m == top[(k, i)] for k, i, m, _ in got)


# ---------------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------------
def test_dp_mode_is_refused_by_name():
    with pytest.raises(WindFlowError, match="DP mode"):
        (Interval_Join_TPU_Builder(lambda a, b: a).with_key_by("k")
         .with_boundaries(1, 1).with_dp_mode().build())


@pytest.mark.parametrize("build,match", [
    (lambda: Interval_Join_TPU_Builder(lambda a, b: a)
     .with_key_by(lambda t: t["k"]).with_boundaries(1, 1).build(),
     "ONE integer field"),
    (lambda: Interval_Join_TPU_Builder(lambda a, b: a).with_key_by("k")
     .with_boundaries(1, 1 << 28).build(), "boundaries"),
    (lambda: Interval_Join_TPU_Builder(lambda a, b: a).with_key_by("k")
     .build(), "withBoundaries"),
    (lambda: Interval_Join_TPU_Builder(lambda a, b: a)
     .with_boundaries(1, 1).build(), "withKeyBy"),
])
def test_a_key_or_bounds_the_device_cannot_hold_are_refused(build, match):
    with pytest.raises(WindFlowError, match=match):
        build()


def test_it_sits_after_a_merge_of_exactly_two_pipes():
    g = PipeGraph("one_pipe", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pipe = g.add_source(Source_Builder(lambda s: None)
                        .with_output_batch_size(8).build())
    with pytest.raises(WindFlowError, match="merging exactly two"):
        pipe.add(join_builder(1, 1).build())


def test_a_float_key_is_refused_at_the_first_batch():
    a = stream(33, 64, 3, 1_000)
    a["k"] = a["k"].astype(np.float32)
    with pytest.raises(WindFlowError, match="integer column"):
        run_device(a, stream(34, 64, 3, 1_000), 10, 10)


def test_a_branch_index_out_of_range_names_the_operator_it_follows():
    def src(shipper, ctx=None):
        shipper.set_next_watermark(0)
        shipper.push_columns({"k": np.arange(8, dtype=np.int32),
                              "kind": np.full(8, 2, np.int32)},
                             ts=np.arange(8, dtype=np.int64))

    g = PipeGraph("bad_branch", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pipe = g.add_source(Source_Builder(src).with_output_batch_size(8)
                        .build()).add(Map_TPU_Builder(dict)
                                      .with_name("route").build())
    pipe.split("kind", 2)
    for b in (0, 1):
        pipe.select(b).add(Map_TPU_Builder(dict).build()).add_sink(
            Sink_Builder(lambda c, t: None).with_columns().build())
    with pytest.raises(WindFlowError, match=r"'route'.*'kind'.*2\.\.2"):
        g.run()


def test_rescale_refuses_the_operator_by_name():
    from windflow_tpu.scaling.repartition import repartition_refusal
    assert "Interval_Join_TPU" in repartition_refusal(
        join_builder(1, 1).build())


# ---------------------------------------------------------------------------
# declared schemas: prewarm, and no program for one input alone
# ---------------------------------------------------------------------------
def test_declared_schemas_prewarm_both_directions():
    a, b = stream(35, 300, 6, 20_000), stream(36, 500, 6, 20_000)
    out = Pairs()
    g = PipeGraph("warm", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME).with_prewarm()
    pa = g.add_source(Source_Builder(block_source(a, "va"))
                      .with_output_batch_size(BLK).build())
    pb = g.add_source(Source_Builder(block_source(b, "vb"))
                      .with_output_batch_size(BLK).build())
    join = join_builder(400, 400).with_schemas(
        {"k": np.int32, "va": np.int32}, {"k": np.int32, "vb": np.int32}
    ).build()
    pa.merge(pb).add(join).add_sink(
        Sink_Builder(out).with_name("snk").with_columns().build())
    g.run()
    assert out.sorted() == brute(a, b, 400, 400)
    assert join.staged_sides == [True, True]
    rep = g.prewarm_report
    # the step and the program for a step's further output batches
    assert rep["signatures_compiled"] == 4 * len(rep["bucket_caps"])
    stats = {o["name"]: o["replicas"][0]
             for o in g.get_stats()["Operators"]}
    # every shape the stream used was compiled before the sources opened
    assert stats["join"]["Compile_count"] == 4 * len(rep["bucket_caps"])


# ---------------------------------------------------------------------------
# snapshot and restore mid-stream (tests/test_checkpoint_recovery.py's
# harness: a replayable source that asks for a checkpoint, then dies)
# ---------------------------------------------------------------------------
class InjectedCrash(Exception):
    pass


class ReplayBoth:
    """Rows of both inputs in event-time order from one replayable
    source (``kind`` 0 is A); checkpoint requested at ``ckpt_at``, crash
    injected at ``crash_at``."""

    def __init__(self, rows, ckpt_at=None, crash_at=None):
        self.rows, self.ckpt_at, self.crash_at = rows, ckpt_at, crash_at
        self.pos = 0

    def __call__(self, shipper):
        while self.pos < len(self.rows):
            if self.crash_at is not None and self.pos == self.crash_at:
                raise InjectedCrash(f"killed at row {self.pos}")
            kind, k, v, ts = self.rows[self.pos]
            shipper.set_next_watermark(max(0, ts - 1))
            shipper.push_with_timestamp({"kind": kind, "k": k, "v": v}, ts)
            self.pos += 1
            if self.ckpt_at is not None and self.pos == self.ckpt_at:
                assert shipper.request_checkpoint() is not None

    def snapshot_position(self):
        return self.pos

    def restore(self, pos):
        self.pos = pos


def _ckpt_graph(store, src, got):
    g = PipeGraph("ck_join", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    g.with_checkpointing(store_dir=store)
    pipe = g.add_source(Source_Builder(src).with_name("src")
                        .with_output_batch_size(32).build())
    pipe.split(lambda t: t["kind"], 2)
    pa = pipe.select(0).add(Map_TPU_Builder(
        lambda f: {"k": f["k"], "va": f["v"]}).with_name("a").build())
    pb = pipe.select(1).add(Map_TPU_Builder(
        lambda f: {"k": f["k"], "vb": f["v"]}).with_name("b").build())

    def sink(cols, ts):
        if cols is not None:
            got.extend(zip(cols["va"].tolist(), cols["vb"].tolist()))

    pa.merge(pb).add(join_builder(2_000, 2_000).build()).add_sink(
        Sink_Builder(sink).with_name("snk").with_columns().build())
    return g


def test_restore_mid_stream_loses_and_doubles_no_pair(tmp_path):
    a, b = stream(41, 600, 7, 60_000), stream(42, 900, 7, 60_000)
    rows = sorted([(0, int(k), int(v), int(t)) for k, v, t in
                   zip(a["k"], a["v"], a["ts"])]
                  + [(1, int(k), int(v), int(t)) for k, v, t in
                     zip(b["k"], b["v"], b["ts"])], key=lambda r: r[3])
    want = brute(a, b, 2_000, 2_000)
    golden = []
    _ckpt_graph(str(tmp_path / "gold"), ReplayBoth(rows), golden).run()
    assert sorted(golden) == want
    store, crashed = str(tmp_path / "store"), []
    g = _ckpt_graph(store, ReplayBoth(rows, ckpt_at=600, crash_at=1_100),
                    crashed)
    with pytest.raises(InjectedCrash):
        g.run()
    assert g._coordinator.completed == 1
    restored = []
    _ckpt_graph(store, ReplayBoth(rows), restored).run(restore_from=store)
    # the restored run continues from the checkpoint's archives: no pair
    # twice, none with both rows before the checkpoint (those left
    # before it), every pair that straddles it (one row archived in the
    # snapshot, the other replayed), and with the crashed run's all
    assert len(set(restored)) == len(restored) < len(want)
    cut = rows[600][3]
    old = {(i, j) for i, j in want
           if max(a["ts"][i], b["ts"][j]) < rows[599][3]}
    straddle = {(i, j) for i, j in want
                if min(a["ts"][i], b["ts"][j]) < rows[599][3]
                and max(a["ts"][i], b["ts"][j]) > cut}
    assert len(straddle) > 20
    assert not old & set(restored)
    assert straddle <= set(restored)
    assert sorted(set(crashed) | set(restored)) == want
