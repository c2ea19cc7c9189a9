"""Ffat_Windows_TPU tests (reference tests/win_tests_gpu equivalents):
device-plane sliding-window aggregation checked against the same window
model used for the CPU operators, TB and CB, multi-key, with lateness and
partial EOS flushes."""

import random

import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

from common import (DictWinCollector, TupleT, expected_windows,
                    rand_degree)

N_KEYS = 5
STREAM_LEN = 120
TS_STEP = 137
WIN_US, SLIDE_US = 1000, 400
WIN_CB, SLIDE_CB = 13, 5


def make_src(n_keys, stream_len):
    def src(shipper, ctx):
        for i in range(stream_len):
            ts = i * TS_STEP
            for k in range(ctx.get_replica_index(), n_keys,
                           ctx.get_parallelism()):
                shipper.push_with_timestamp(TupleT(k, i + 1 + k, ts), ts)
            shipper.set_next_watermark(ts)
    return src


def model_seqs(n_keys, stream_len):
    return {k: [(i + 1 + k, i * TS_STEP) for i in range(stream_len)]
            for k in range(n_keys)}


def sum_or_none(vals):
    return sum(vals) if vals else None


def run_ffat_tpu(win, slide, win_type_cb, n_keys=N_KEYS,
                 stream_len=STREAM_LEN, src_par=1, op_par=1, nwpb=8,
                 lateness=0, obs=32, key_capacity=None):
    coll = DictWinCollector()
    graph = PipeGraph("ffat_tpu", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    src = (Source_Builder(make_src(n_keys, stream_len))
           .with_parallelism(src_par).with_output_batch_size(obs).build())
    b = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b_: {"value": a["value"] + b_["value"]})
         .with_key_by("key").with_lateness(lateness)
         .with_num_win_per_batch(nwpb))
    b = (b.with_cb_windows(win, slide) if win_type_cb
         else b.with_tb_windows(win, slide))
    if key_capacity is not None:
        b = b.with_key_capacity(key_capacity)
    op = b.with_parallelism(op_par).build()
    graph.add_source(src).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    coll.op = op
    return coll


@pytest.mark.parametrize("win,slide", [(WIN_US, SLIDE_US), (800, 800),
                                       (300, 700)])
def test_ffat_tpu_tb(win, slide):
    expected = expected_windows(model_seqs(N_KEYS, STREAM_LEN), win, slide,
                                False, sum_or_none)
    coll = run_ffat_tpu(win, slide, win_type_cb=False)
    assert coll.dups == 0
    assert coll.results == expected


@pytest.mark.parametrize("win,slide", [(WIN_CB, SLIDE_CB), (8, 8), (3, 7)])
def test_ffat_tpu_cb(win, slide):
    expected = expected_windows(model_seqs(N_KEYS, STREAM_LEN), win, slide,
                                True, sum_or_none)
    coll = run_ffat_tpu(win, slide, win_type_cb=True)
    assert coll.dups == 0
    assert coll.results == expected


def test_ffat_tpu_parallel_replicas():
    """Keys partitioned across device replicas; randomized degrees."""
    rng = random.Random(7)
    expected = expected_windows(model_seqs(N_KEYS, STREAM_LEN), WIN_US,
                                SLIDE_US, False, sum_or_none)
    for _ in range(3):
        coll = run_ffat_tpu(WIN_US, SLIDE_US, False,
                            src_par=rand_degree(rng),
                            op_par=rand_degree(rng),
                            nwpb=rng.choice([1, 4, 16]),
                            obs=rng.choice([16, 64]))
        assert coll.results == expected


def test_ffat_tpu_many_keys_growth():
    """Key-capacity doubling: more keys than the initial 16-slot table."""
    n_keys = 50
    expected = expected_windows(model_seqs(n_keys, 40), 800, 800, False,
                                sum_or_none)
    coll = run_ffat_tpu(800, 800, False, n_keys=n_keys, stream_len=40)
    assert coll.results == expected


def test_ffat_tpu_lateness_disorder():
    disorder = 300
    rng = random.Random(9)
    rows = []
    for i in range(STREAM_LEN):
        ts = max(0, i * TS_STEP - rng.randint(0, disorder))
        rows.append((i + 1, ts))
    expected = expected_windows({0: rows}, WIN_US, SLIDE_US, False,
                                sum_or_none)

    coll = DictWinCollector()
    graph = PipeGraph("ffat_tpu_late", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for i, (v, ts) in enumerate(rows):
            shipper.push_with_timestamp(TupleT(0, v, ts), ts)
            shipper.set_next_watermark(max(0, i * TS_STEP - disorder))

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b_: {"value": a["value"] + b_["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_lateness(disorder).build())
    graph.add_source(Source_Builder(src).with_output_batch_size(16).build()) \
        .add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    assert coll.results == expected


def test_ffat_tpu_noncommutative_minmax():
    """combine keeps (min, max) pairs — associative, order-insensitive for
    values but exercises multi-field tree state."""
    expected = {}
    seqs = model_seqs(3, 60)
    raw = expected_windows(seqs, WIN_US, SLIDE_US, False,
                           lambda vs: (min(vs), max(vs)) if vs else None)
    coll = DictWinCollector()
    graph = PipeGraph("ffat_tpu_mm", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    src = (Source_Builder(make_src(3, 60))
           .with_output_batch_size(32).build())
    import jax.numpy as jnp
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"lo": f["value"], "hi": f["value"]},
            lambda a, b_: {"lo": jnp.minimum(a["lo"], b_["lo"]),
                           "hi": jnp.maximum(a["hi"], b_["hi"])})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US).build())

    res = {}
    import threading
    lock = threading.Lock()

    def sink(r):
        if r is not None and r["valid"]:
            with lock:
                res[(r["key"], r["wid"])] = (r["lo"], r["hi"])

    graph.add_source(src).add(op).add_sink(Sink_Builder(sink).build())
    graph.run()
    raw = {k: v for k, v in raw.items() if v is not None}
    assert res == raw


# K_cap * F against 2**15 - 1, the largest composite an int16 column holds
# beside its sentinel: 512 * 32 below it, 1024 * 32 = 2**15 the first
# product past it, and a key table that doubles from the one to the other
# in mid-stream (600 keys into 512 slots)
@pytest.mark.parametrize("key_capacity,n_keys,dtypes", [
    (512, 40, ["int16"]), (1024, 40, ["int32"]),
    (512, 600, ["int16", "int32"])])
def test_ffat_tpu_packed_composite_dtypes(key_capacity, n_keys, dtypes,
                                          monkeypatch):
    """The program sorts ONE packed composite column (slot * F + leaf)
    in the narrowest integer dtype that holds ``K_cap * F``
    (``_comp_dtype``): windows are exact at both dtypes, and across the
    growth that changes the dtype under a running stream."""
    import numpy as np
    from windflow_tpu.tpu.ffat_tpu import FfatTPUReplica
    seen = []
    orig = FfatTPUReplica._commit_step

    def spy(self, fields, wm, comp_p, *rest):
        assert comp_p.dtype == self._comp_dtype()[1]
        if not seen or seen[-1] != comp_p.dtype.name:
            seen.append(comp_p.dtype.name)
        return orig(self, fields, wm, comp_p, *rest)

    monkeypatch.setattr(FfatTPUReplica, "_commit_step", spy)
    stream_len = 24
    expected = expected_windows(model_seqs(n_keys, stream_len), WIN_US,
                                SLIDE_US, False, sum_or_none)
    coll = run_ffat_tpu(WIN_US, SLIDE_US, win_type_cb=False, n_keys=n_keys,
                        stream_len=stream_len, obs=256,
                        key_capacity=key_capacity)
    rep = coll.op.replicas[0]
    assert rep.F == 32 and seen == dtypes
    assert rep._comp_dtype() == (rep.K_cap * 32, np.dtype(dtypes[-1]))
    assert coll.dups == 0
    assert coll.results == expected


@pytest.mark.parametrize("count_based", [False, True])
def test_ffat_tpu_ring_alias_after_drain_iterations(count_based):
    """Regression: fire-only drain programs skip the level rebuild; window
    queries must clip to the data extent so ring slots aliasing panes
    evicted after the last rebuild never contribute (W_cap=2 forces long
    drain chains; 3x ring wraparound exercises aliasing). Time-based
    windows are answered by the walk by range (both keys share a ring
    range), count-based ones (a pane = one arrival of the key) by the
    lane walk."""
    import jax
    import numpy as np
    from windflow_tpu.basic import WinType
    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU
    from windflow_tpu.tpu.schema import TupleSchema

    PANE = 1 if count_based else 1000
    N_PANES = 100  # F is 32 -> wraps 3x
    op = Ffat_Windows_TPU(
        lift=lambda f: {"v": f["v"]},
        combine=lambda a, b: {"v": a["v"] + b["v"]},
        key_extractor="key", win_len=4 * PANE, slide_len=PANE,
        win_type=WinType.CB if count_based else WinType.TB,
        num_win_per_batch=2, key_capacity=2, name="alias")
    op.build_replicas()
    rep = op.replicas[0]
    assert rep.F == 32
    got = {}

    class Cap:
        def emit_device_batch(self, b):
            keys = np.asarray(b.fields["key"])[:b.size]
            wids = np.asarray(b.fields["wid"])[:b.size]
            vals = np.asarray(b.fields["v"])[:b.size]
            valid = np.asarray(b.fields["valid"])[:b.size]
            for k, w, v, ok in zip(keys, wids, vals, valid):
                if ok:
                    got[(int(k), int(w))] = int(v)

        def set_stats(self, s):
            pass

        def propagate_punctuation(self, wm):
            pass

    rep.emitter = Cap()
    schema = TupleSchema({"key": np.int32, "v": np.int32})
    # one batch per 4 panes, 2 keys, value = pane+1; watermark trails so
    # several windows become fireable at once and W_cap=2 forces drains
    for base in range(0, N_PANES, 4):
        rows_k = np.repeat(np.arange(2, dtype=np.int64), 4)
        panes = np.tile(np.arange(base, base + 4), 2)
        ts = panes * PANE + 5
        vals = (panes + 1).astype(np.int32)
        cols = {"key": jax.device_put(rows_k.astype(np.int32)),
                "v": jax.device_put(vals)}
        b = BatchTPU(cols, ts.astype(np.int64), 8, schema,
                     wm=max(0, (base - 1) * PANE), host_keys=rows_k)
        b.wm = (base + 4) * PANE  # frontier passes the batch's own panes
        rep.handle_msg(0, b)
    rep.flush_on_termination()

    st = rep.stats
    # two windows a program: three drain programs behind each of the 25 steps
    assert st.fire_programs >= N_PANES - 3
    assert st.fire_grouped_programs == (0 if count_based
                                        else st.fire_programs)
    for k in range(2):
        for w in range(N_PANES - 3):
            expect = sum(p + 1 for p in range(w, min(w + 4, N_PANES)))
            assert got.get((k, w)) == expect, (k, w, got.get((k, w)), expect)


def test_ffat_tpu_columnar_event_time_pipeline():
    """push_columns -> keyed FFAT_TPU -> sink through the public API under
    EVENT_TIME: every window sum checked, including the partial flush."""
    import threading
    import numpy as np
    from windflow_tpu import Source_Builder, Sink_Builder, TimePolicy

    K, N, WIN, SLIDE = 40, 30, 4000, 1000
    graph = PipeGraph("ffat_cols", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(N):
            shipper.set_next_watermark(p * 1000)
            shipper.push_columns(
                {"key": np.arange(K, dtype=np.int32),
                 "value": np.full(K, p + 1, dtype=np.int32)},
                ts=np.full(K, p * 1000 + 5, dtype=np.int64))
        shipper.set_next_watermark(N * 1000 + WIN)

    ffat = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"]},
                lambda a, b: {"value": a["value"] + b["value"]})
            .with_tb_windows(WIN, SLIDE)
            .with_key_by("key").with_key_capacity(K)
            .with_num_win_per_batch(64).build())
    res, lock = {}, threading.Lock()

    def sink(t):
        if t is not None and t["valid"]:
            with lock:
                res[(t["key"], t["wid"])] = t["value"]

    graph.add_source(Source_Builder(src).with_output_batch_size(K).build()) \
         .add(ffat).add_sink(Sink_Builder(sink).build())
    graph.run()
    for k in range(K):
        for w in range(N):
            panes = [p for p in range(w, w + 4) if p < N]
            if not panes:
                continue
            assert res.get((k, w)) == sum(p + 1 for p in panes), (k, w)


def test_ffat_tpu_tuple_keys():
    """Composite (tuple) keys from a callable extractor: slot mapping and
    window emission must take the object-key paths (regression: ragged
    zero-padded asarray crashed at first fire)."""
    import threading
    import numpy as np
    from windflow_tpu import Source_Builder, Sink_Builder, TimePolicy

    N, WIN, SLIDE = 20, 4000, 1000
    graph = PipeGraph("ffat_tuple_keys", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(N):
            shipper.set_next_watermark(p * 1000)
            for k in range(3):
                shipper.push_with_timestamp(
                    {"key": k, "value": p + 1}, p * 1000 + 5)
        shipper.set_next_watermark(N * 1000 + WIN)

    ffat = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"]},
                lambda a, b: {"value": a["value"] + b["value"]})
            .with_tb_windows(WIN, SLIDE)
            .with_key_by(lambda t: (t["key"], t["key"] % 2))
            .with_num_win_per_batch(4).build())
    res, lock = {}, threading.Lock()

    def sink(t):
        if t is not None and t["valid"]:
            with lock:
                res[(t["wid"],)] = res.get((t["wid"],), 0) + t["value"]

    graph.add_source(Source_Builder(src).with_output_batch_size(12).build()) \
         .add(ffat).add_sink(Sink_Builder(sink).build())
    graph.run()
    # 3 tuple-keys each contribute sum(p+1 for p in window) to window w
    for w in range(N - 3):
        expect = 3 * sum(p + 1 for p in range(w, w + 4))
        assert res.get((w,)) == expect, (w, res.get((w,)), expect)


def test_ffat_tpu_gap_windows_late_first_key_reanchor():
    """Regression (round-2 review): with GAP windows (slide > win) a key's
    FIRST tuple can land in a gap and stay late, leaving the slot
    unanchored (max_leaf < 0) past its registration batch. A much later
    timestamp must then RE-anchor the window origin instead of growing
    the pane ring toward epoch scale (which overflows the int32 index
    plane and raises)."""
    coll = DictWinCollector()
    graph = PipeGraph("gap", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        shipper.push_with_timestamp(TupleT(0, 7, 5000), 5000)  # in a gap
        shipper.set_next_watermark(5000)
        ts2 = 300_000_000_005  # ~epoch-scale jump, separate batch
        shipper.push_with_timestamp(TupleT(0, 9, ts2), ts2)
        shipper.set_next_watermark(ts2)

    src_op = Source_Builder(src).with_output_batch_size(1).build()
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b_: {"value": a["value"] + b_["value"]})
          .with_key_by("key").with_tb_windows(1000, 10000).build())
    graph.add_source(src_op).add(op).add_sink(
        Sink_Builder(coll.sink).build())
    graph.run()
    # window 30_000_000 covers panes [3e8, 3e8+1); the gap tuple is late
    assert coll.results.get((0, 30_000_000)) == 9


@pytest.mark.parametrize("win,slide,count_based", [
    (WIN_US, SLIDE_US, False), (WIN_CB, SLIDE_CB, True)])
def test_ffat_tpu_adaptive_fire_tiers(win, slide, count_based):
    """A GIVEN ``num_win_per_batch`` caps the width of every fire
    program, for time-based windows (which without one size their width
    by the plan, tests/test_ffat_grouped_fire.py) and count-based ones
    alike: a stream firing more windows a batch than the budget runs
    every program at exactly the budget's width, warms its one width
    eagerly (no compile after the first batch's), and keeps exact window
    results, for the walk by range (time-based) and the lane walk
    (count-based)."""
    n_keys, stream_len = 96, 60
    expected = expected_windows(model_seqs(n_keys, stream_len), win,
                                slide, count_based, sum_or_none)
    coll = run_ffat_tpu(win, slide, win_type_cb=count_based,
                        n_keys=n_keys, stream_len=stream_len,
                        nwpb=256, obs=512)
    rep = coll.op.replicas[0]
    st = rep.stats
    assert (rep.W_cap, rep.W_wide) == (256, 256)
    assert st.fire_programs > 1 and st.windows_fired > 256
    assert st.fire_lanes == 256 * st.fire_programs   # every width: 256
    # the step at its one width, ingest-only, fire-only, rebuild: all at
    # warm-up
    assert st.compile_count == 4
    assert coll.dups == 0
    assert coll.results == expected


def test_ffat_tpu_programs_chosen_by_nothing_but_shapes():
    """One step-program family on every backend: ``tpu/ffat_tpu.py``
    reads neither the backend nor an environment switch (its one look
    at the environment, ``checkpoint.delta.env_ckpt_delta``, marks rows
    for snapshots), so a test on the CPU backend runs the programs the
    chip runs; and the cache keys of a capacity bucket carry shapes, key
    dtype and chain tag, no mode."""
    import re
    import windflow_tpu.tpu.ffat_tpu as ft
    with open(ft.__file__) as fh:
        src = fh.read()
    found = re.findall(r"default_backend|jax\.devices|\.platform\b|environ"
                       r"|getenv|env_flag|env_int|WF_[A-Z_]+=", src)
    assert not found, found
    op = ft.Ffat_Windows_TPU(
        lift=lambda f: {"v": f["v"]},
        combine=lambda a, b: {"v": a["v"] + b["v"]},
        key_extractor="key", win_len=1000, slide_len=400, key_capacity=4)
    op.build_replicas()
    rep = op.replicas[0]
    assert rep._step_keys(64) == (
        ("step", 64, rep.K_cap, rep.F, "int32", None),
        ("ingest", 64, rep.K_cap, rep.F, None))


def test_ffat_tpu_scalar_constant_lift_field():
    """A lift may return per-tuple CONSTANT fields (count seeds: the
    reference's lift functor is per-tuple, wf/ffat_windows.hpp) — the
    columnar lift must broadcast them to the batch shape. Regression:
    round-3 verify found `{"n": 1.0}` raising TypeError."""
    coll = DictWinCollector()
    graph = PipeGraph("ffat_scalar_lift", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    src = (Source_Builder(make_src(3, 80))
           .with_output_batch_size(32).build())
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"s": f["value"], "n": 1.0},
            lambda a, b_: {"s": a["s"] + b_["s"], "n": a["n"] + b_["n"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_num_win_per_batch(8).build())

    def sink(r):
        if r is None:
            return
        coll.sink({"key": r["key"], "wid": r["wid"],
                   "value": (r["s"], r["n"]) if r["valid"] else None,
                   "valid": r["valid"]})

    graph.add_source(src).add(op).add_sink(Sink_Builder(sink).build())
    graph.run()
    seqs = model_seqs(3, 80)
    exp_sum = expected_windows(seqs, WIN_US, SLIDE_US, False, sum_or_none)
    exp_cnt = expected_windows(seqs, WIN_US, SLIDE_US, False,
                               lambda v: float(len(v)) if v else None)
    assert coll.dups == 0
    got_sum = {k: (v[0] if v else None) for k, v in coll.results.items()}
    got_cnt = {k: (v[1] if v else None) for k, v in coll.results.items()}
    assert got_sum == exp_sum
    assert got_cnt == exp_cnt


def test_ffat_tpu_deferred_rebuild_dataless_fire():
    """Deferred-rebuild soundness (round 4): batches whose watermark is
    PARKED run the ingest-only program (no level rebuild); the later
    watermark jump fires windows DATALESSLY through the fire-only
    program, which must see a settled forest (_ensure_rebuilt) — stale
    internal nodes would fire empty/wrong windows for data ingested
    during the parked phase."""
    coll = DictWinCollector()
    graph = PipeGraph("ffat_deferred", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        # watermark PARKED at 0 for the whole stream -> every staged
        # batch runs the ingest-only program (nothing ever fireable);
        # EOS then fires EVERY window datalessly through the fire-only
        # program, which would read stale internal nodes without the
        # _ensure_rebuilt settle (verified discriminating: neutering
        # _ensure_rebuilt makes this test fail)
        for i in range(100):
            shipper.push_with_timestamp(TupleT(i % 3, i + 1, i * TS_STEP),
                                        i * TS_STEP)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b_: {"value": a["value"] + b_["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_num_win_per_batch(8).build())
    graph.add_source(Source_Builder(src).with_output_batch_size(16).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    seqs = {k: [(i + 1, i * TS_STEP) for i in range(100) if i % 3 == k]
            for k in range(3)}
    expected = expected_windows(seqs, WIN_US, SLIDE_US, False, sum_or_none)
    assert coll.dups == 0
    assert coll.results == expected


def test_key_growth_overflow_raise_before_mutate():
    """A key-table growth that would overflow the int32 index plane must
    raise BEFORE any bookkeeping mutates: KeySlotMap rolls back the slot
    registration on refusal, so a caught-and-retried batch must find
    UNCHANGED replica state — not a double-appended _out_keys_by_slot
    shifting every later slot's original-key mapping."""
    from windflow_tpu.basic import WindFlowError, WinType
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU

    op = Ffat_Windows_TPU(
        lift=lambda f: {"v": f["v"]},
        combine=lambda a, b: {"v": a["v"] + b["v"]},
        key_extractor="key", win_len=4, slide_len=1,
        win_type=WinType.TB, key_capacity=2, name="grow_guard")
    op.build_replicas()
    rep = op.replicas[0]
    rep.F = 1 << 27          # forged: doubling K_cap 4 -> 8 overflows int32
    for k in range(rep.K_cap):
        rep._keymap.slot(1000 + k)
    before = list(rep._out_keys_by_slot)
    k_cap = rep.K_cap
    for _ in range(2):       # the retry must fail IDENTICALLY
        with pytest.raises(WindFlowError, match="int32 index plane"):
            rep._keymap.slot(9999)
        assert rep._out_keys_by_slot == before
        assert rep.K_cap == k_cap
        assert len(rep._keymap) == k_cap
    # ring growth must refuse BEFORE mutating F as well: a caught
    # refusal after mutation would leave a wrapped index plane that no
    # later per-batch guard re-checks
    op2 = Ffat_Windows_TPU(
        lift=lambda f: {"v": f["v"]},
        combine=lambda a, b: {"v": a["v"] + b["v"]},
        key_extractor="key", win_len=4, slide_len=1,
        win_type=WinType.TB, key_capacity=2, name="ring_guard")
    op2.build_replicas()
    rep2 = op2.replicas[0]
    rep2.K_cap = 1 << 26     # forged: F 32 -> 128 would give 2^34 indices
    f_before = rep2.F
    for _ in range(2):
        with pytest.raises(WindFlowError, match="int32 index plane"):
            rep2._grow_ring(1 << 6)
        assert rep2.F == f_before


def test_growth_build_then_commit(monkeypatch):
    """Growth must BUILD-THEN-COMMIT: an allocation failure mid-growth
    (injected here in place of a device OOM) leaves the replica in its
    exact pre-growth state, and the retry succeeds cleanly — no
    half-grown K_cap/F against old-shaped trees, no double-appended
    key bookkeeping."""
    import jax
    import numpy as np

    from windflow_tpu.basic import WinType
    from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU

    def mkop(name):
        op = Ffat_Windows_TPU(
            lift=lambda f: {"v": f["v"]},
            combine=lambda a, b: {"v": a["v"] + b["v"]},
            key_extractor="key", win_len=4, slide_len=1,
            win_type=WinType.TB, key_capacity=2, name=name)
        op.build_replicas()
        return op.replicas[0]

    def boom(*a, **k):
        raise RuntimeError("injected alloc failure")

    # ---- ring growth ----
    rep = mkop("rg_commit")
    rep._ensure_forest({"v": np.zeros(1)})
    trees_before, F_before = rep.trees, rep.F
    monkeypatch.setattr(jax.tree_util, "tree_map", boom)
    with pytest.raises(RuntimeError, match="injected"):
        rep._grow_ring(1 << 6)
    assert rep.F == F_before and rep.trees is trees_before
    monkeypatch.undo()
    rep._grow_ring(1 << 6)
    assert rep.F == 128 and rep.trees is not trees_before

    # ---- key growth via _on_new_key ----
    rep2 = mkop("kg_commit")
    rep2._ensure_forest({"v": np.zeros(1)})
    for k in range(rep2.K_cap):
        rep2._keymap.slot(100 + k)
    cap_before = rep2.K_cap
    keys_before = list(rep2._out_keys_by_slot)
    monkeypatch.setattr(jax.tree_util, "tree_map", boom)
    with pytest.raises(RuntimeError, match="injected"):
        rep2._keymap.slot(999)
    assert rep2.K_cap == cap_before
    assert rep2._out_keys_by_slot == keys_before
    assert len(rep2._keymap) == cap_before
    assert rep2.trees["v"].shape[1] == cap_before
    monkeypatch.undo()
    s = rep2._keymap.slot(999)            # retry succeeds from scratch
    assert s == cap_before
    assert rep2.K_cap == 2 * cap_before
    assert rep2._out_keys_by_slot[-1] == 999
    assert rep2.trees["v"].shape[1] == 2 * cap_before


def test_ffat_tpu_composite_key_columnar_pipeline():
    """push_columns with a COMPOSITE field-tuple key (the YSB join-key
    shape, with_key_by(("c", "a"))) -> keyed FFAT_TPU -> sink: routing
    rides the stacked-column FNV (no per-row hash), the structured key
    metadata feeds the KeySlotMap as tuples, and every (c, a, wid) sum
    matches the oracle. The key rides the lift output (composite keys
    are host metadata, not a device column)."""
    import threading
    import numpy as np
    from windflow_tpu import Source_Builder, Sink_Builder, TimePolicy

    C, A, N, WIN, SLIDE = 5, 4, 24, 4000, 1000
    K = C * A
    graph = PipeGraph("ffat_comp", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        cs = np.repeat(np.arange(C, dtype=np.int64), A)
        ads = np.tile(np.arange(A, dtype=np.int64), C)
        for p in range(N):
            shipper.set_next_watermark(p * 1000)
            shipper.push_columns(
                {"c": cs, "a": ads,
                 "value": np.full(K, p + 1, dtype=np.int64)},
                ts=np.full(K, p * 1000 + 5, dtype=np.int64))
        shipper.set_next_watermark(N * 1000 + WIN)

    ffat = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"], "c": f["c"], "a": f["a"]},
                lambda x, y: {"value": x["value"] + y["value"],
                              "c": x["c"], "a": x["a"]})
            .with_tb_windows(WIN, SLIDE)
            .with_key_by(("c", "a")).with_key_capacity(K)
            .with_num_win_per_batch(64).build())
    res, lock = {}, threading.Lock()

    def sink(t):
        if t is not None and t["valid"]:
            with lock:
                key = (t["c"], t["a"], t["wid"])
                assert key not in res, f"duplicate window {key}"
                res[key] = t["value"]

    graph.add_source(Source_Builder(src).with_output_batch_size(K).build()) \
         .add(ffat).add_sink(Sink_Builder(sink).build())
    graph.run()
    for c in range(C):
        for a in range(A):
            for w in range(N):
                panes = [p for p in range(w, w + 4) if p < N]
                if not panes:
                    continue
                expect = sum(p + 1 for p in panes)
                got = res.get((c, a, w))
                assert got == expect, ((c, a, w), got, expect)


@pytest.mark.parametrize("win_par", [1, 2])
def test_ffat_tpu_composite_key_device_reshard(win_par):
    """Composite keys past the FIRST staging hop: an UNKEYED device map
    feeds a composite-keyed windows op, so the key must be built from
    the device columns at the keyed re-shard (par>1) or by the replica
    itself (par=1) — no host key metadata exists on that edge."""
    import threading
    import numpy as np
    from windflow_tpu import Source_Builder, Sink_Builder, TimePolicy
    from windflow_tpu.tpu import Map_TPU_Builder

    C, A, N = 4, 3, 20
    K = C * A
    graph = PipeGraph(f"ffat_comp_reshard{win_par}", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        cs = np.repeat(np.arange(C, dtype=np.int64), A)
        ads = np.tile(np.arange(A, dtype=np.int64), C)
        for p in range(N):
            shipper.set_next_watermark(p * 1000)
            shipper.push_columns(
                {"c": cs, "a": ads,
                 "value": np.full(K, p + 1, dtype=np.int64)},
                ts=np.full(K, p * 1000 + 5, dtype=np.int64))
        shipper.set_next_watermark(N * 1000 + 4000)

    premap = Map_TPU_Builder(
        lambda f: {"c": f["c"], "a": f["a"], "value": f["value"] * 2}
    ).build()
    ffat = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"], "c": f["c"], "a": f["a"]},
                lambda x, y: {"value": x["value"] + y["value"],
                              "c": x["c"], "a": x["a"]})
            .with_tb_windows(4000, 1000)
            .with_key_by(("c", "a")).with_key_capacity(K)
            .with_parallelism(win_par).build())
    res, lock = {}, threading.Lock()

    def sink(t):
        if t is not None and t["valid"]:
            with lock:
                key = (t["c"], t["a"], t["wid"])
                assert key not in res, f"duplicate window {key}"
                res[key] = t["value"]

    graph.add_source(Source_Builder(src).with_output_batch_size(K).build()) \
         .add(premap).add(ffat) \
         .add_sink(Sink_Builder(sink).build())
    graph.run()
    for c in range(C):
        for a in range(A):
            for w in range(N):
                panes = [p for p in range(w, w + 4) if p < N]
                if not panes:
                    continue
                expect = 2 * sum(p + 1 for p in panes)
                got = res.get((c, a, w))
                assert got == expect, ((c, a, w), got, expect)
