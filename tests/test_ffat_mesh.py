"""Ffat_Windows_Mesh through the TOPOLOGY layer (round-3 verdict item 3):
a real pipeline — CPU source -> keyed staging -> sharded FlatFAT forest
over the virtual 8-device mesh -> CPU sink — built with the public
builders, checked against an origin-anchored window oracle, and invariant
under mesh reshape (8x1 / 4x2 / 2x4)."""

import threading

import numpy as np
import pytest

import jax

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy, WindFlowError)
from windflow_tpu.tpu import Ffat_Windows_TPU_Builder

pytestmark = pytest.mark.mesh  # shared conftest skip when devices short

needs_multi = pytest.mark.skipif(len(jax.devices()) < 8,
                                 reason="needs 8 virtual devices")

N_KEYS = 11
STREAM_LEN = 400
TS_STEP = 37          # µs between tuples of one key
WIN_US, SLIDE_US = 800, 200


def _make_src(n_keys, stream_len):
    def src(shipper, ctx):
        for i in range(stream_len):
            ts = i * TS_STEP
            for k in range(n_keys):
                shipper.push_with_timestamp(
                    {"key": k, "value": float(i + 1 + k)}, ts)
            if i % 16 == 15:
                shipper.set_next_watermark(ts)
    return src


def _oracle(n_keys, stream_len, win_us, slide_us):
    """Origin-anchored windows: window w of key k sums tuples with
    ts in [w*slide, w*slide + win). Keys emit at every ts here, so a
    window exists for every w whose span holds >= 1 tuple."""
    pane = np.gcd(win_us, slide_us)
    win_p, slide_p = win_us // pane, slide_us // pane
    exp = {}
    max_pane = ((stream_len - 1) * TS_STEP) // pane
    w = 0
    while w * slide_p <= max_pane:
        lo_p, hi_p = w * slide_p, w * slide_p + win_p
        for k in range(n_keys):
            s = 0.0
            any_t = False
            for i in range(stream_len):
                p = (i * TS_STEP) // pane
                if lo_p <= p < hi_p:
                    s += i + 1 + k
                    any_t = True
            if any_t:
                exp[(k, w)] = s
        w += 1
    return exp


class Collector:
    def __init__(self):
        self._lock = threading.Lock()
        self.rows = {}
        self.dups = 0

    def sink(self, r):
        if r is None:
            return
        with self._lock:
            key = (r["key"], r["wid"])
            if key in self.rows:
                self.dups += 1
            self.rows[key] = r["value"] if r["valid"] else None


def _run_mesh_pipeline(mesh_shape=None, obs=64, key_capacity=N_KEYS):
    coll = Collector()
    graph = PipeGraph("ffat_mesh", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    src = (Source_Builder(_make_src(N_KEYS, STREAM_LEN))
           .with_output_batch_size(obs).build())
    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key")
          .with_tb_windows(WIN_US, SLIDE_US)
          .with_key_capacity(key_capacity)
          .with_mesh(mesh_shape=mesh_shape)
          .build())
    graph.add_source(src).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    return coll


@needs_multi
def test_mesh_pipeline_matches_oracle():
    coll = _run_mesh_pipeline()
    exp = _oracle(N_KEYS, STREAM_LEN, WIN_US, SLIDE_US)
    got = {k: v for k, v in coll.rows.items() if v is not None}
    assert coll.dups == 0
    assert got == exp, (
        f"missing={sorted(set(exp) - set(got))[:5]} "
        f"extra={sorted(set(got) - set(exp))[:5]}")


@needs_multi
@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4)])
def test_mesh_reshape_invariance(shape):
    """The same stream through 8x1 / 4x2 / 2x4 meshes must produce the
    identical window results — resharding is a layout choice, not a
    semantics choice."""
    coll = _run_mesh_pipeline(mesh_shape=shape)
    exp = _oracle(N_KEYS, STREAM_LEN, WIN_US, SLIDE_US)
    got = {k: v for k, v in coll.rows.items() if v is not None}
    assert got == exp


@needs_multi
def test_mesh_pipeline_key_capacity_guard():
    with pytest.raises(WindFlowError, match="key_capacity"):
        _run_mesh_pipeline(key_capacity=4)  # keys go up to N_KEYS-1


# sparse int64 ids, negative included — the host KeySlotMap densifies
# them into the block-owner mapping (round-4 verdict item 4)
SPARSE_IDS = [(k * 2_654_435_761 - 5_000_000_000) * (11 + k)
              for k in range(N_KEYS)]


@needs_multi
def test_mesh_sparse_int_keys_match_oracle():
    """Arbitrary (sparse, negative) int64 keys through the mesh plane:
    results must equal the dense-key oracle, re-keyed by the original
    ids — the KeySlotMap densification is invisible to the user."""
    coll = Collector()
    graph = PipeGraph("mesh_sparse", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for i in range(STREAM_LEN):
            ts = i * TS_STEP
            for k in range(N_KEYS):
                shipper.push_with_timestamp(
                    {"key": SPARSE_IDS[k], "value": float(i + 1 + k)}, ts)
            if i % 16 == 15:
                shipper.set_next_watermark(ts)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_key_capacity(N_KEYS).with_mesh().build())
    graph.add_source(Source_Builder(src).with_output_batch_size(64).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    exp = {(SPARSE_IDS[k], w): v
           for (k, w), v in _oracle(N_KEYS, STREAM_LEN, WIN_US,
                                    SLIDE_US).items()}
    got = {k: v for k, v in coll.rows.items() if v is not None}
    assert coll.dups == 0
    assert got == exp, (
        f"missing={sorted(set(exp) - set(got))[:5]} "
        f"extra={sorted(set(got) - set(exp))[:5]}")


def test_mesh_builder_validation():
    b = (Ffat_Windows_TPU_Builder(lambda f: f, lambda a, b: a)
         .with_key_by("key").with_cb_windows(8, 4).with_mesh())
    with pytest.raises(WindFlowError, match="TB"):
        b.build()
    b2 = (Ffat_Windows_TPU_Builder(lambda f: f, lambda a, b: a)
          .with_key_by("key").with_tb_windows(800, 200)
          .with_parallelism(2).with_mesh())
    with pytest.raises(WindFlowError, match="exclusive"):
        b2.build()


@needs_multi
def test_mesh_epoch_timestamps_rebase():
    """Epoch-µs timestamps (~1.7e15) would overflow the device's int32
    pane domain without the host-side pane rebase; window ids stay
    origin-anchored (wid counts slides from the epoch)."""
    EPOCH = 1_700_000_000_000_000
    coll = Collector()
    graph = PipeGraph("mesh_epoch", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for i in range(200):
            ts = EPOCH + i * TS_STEP
            for k in range(3):
                shipper.push_with_timestamp(
                    {"key": k, "value": float(i + 1)}, ts)
            if i % 16 == 15:
                shipper.set_next_watermark(ts)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(WIN_US, SLIDE_US)
          .with_key_capacity(3).with_mesh().build())
    graph.add_source(Source_Builder(src).with_output_batch_size(64).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    got = {k: v for k, v in coll.rows.items() if v is not None}
    assert got, "no windows fired"
    pane = np.gcd(WIN_US, SLIDE_US)
    slide_p = SLIDE_US // pane
    win_p = WIN_US // pane
    # wids are epoch-anchored (huge); every fired window matches the oracle
    for (k, w), v in got.items():
        assert w >= EPOCH // SLIDE_US - 1, f"wid {w} not epoch-anchored"
        lo_p, hi_p = w * slide_p, w * slide_p + win_p
        exp = sum(i + 1 for i in range(200)
                  if lo_p <= (EPOCH + i * TS_STEP) // pane < hi_p)
        assert v == exp, (k, w, v, exp)


@needs_multi
def test_mesh_watermark_jump_no_ring_aliasing():
    """A watermark jump makes firing lag eviction (each step fires at
    most fire_rounds windows, so next_fire trails the frontier); tuples
    whose pane wraps the circular ring onto not-yet-evicted old leaves
    must trigger catch-up steps, NOT silently combine into them."""
    coll = Collector()
    graph = PipeGraph("mesh_jump", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)
    # win=4/slide=1 panes (pane_len = 1 µs) -> ring F = 32. Phase-2 panes
    # 30..34: pane 33 wraps to leaf 1, which still holds live pane-1 data
    # unless the catch-up fired + evicted windows 0..4 first.
    def src(shipper, ctx):
        for p in range(8):  # panes 0..7, exactly one staged batch
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(7)  # next batch carries wm=7
        for p in range(30, 35):
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(34)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(4, 1)
          .with_key_capacity(1).with_mesh(fire_rounds=2).build())
    graph.add_source(Source_Builder(src).with_output_batch_size(8).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    got = {k: v for k, v in coll.rows.items() if v is not None}
    # every fired window must match the oracle: window w covers [w, w+4)
    tuples = set(range(8)) | set(range(30, 35))
    for (k, w), v in got.items():
        exp = sum(1.0 for p in range(w, w + 4) if p in tuples)
        assert v == exp, (w, v, exp)
    # windows over both data phases actually fired
    assert any(w < 8 for (_, w) in got)
    assert any(w >= 30 for (_, w) in got)


@needs_multi
def test_mesh_idle_key_resume_no_ring_aliasing():
    """A key that drains (all windows fired, max_leaf < next_fire) and
    then sits idle while the frontier advances must fast-forward on
    resume: pre-fix, a resume pane p >= next_fire + F aliased the ring
    slots of its stalled (empty) windows, firing them valid=True with
    the NEW tuple's value and evicting the new leaf before its real
    window fired (empty). win=4/slide=1 panes -> F=32; idle gap 8..61
    spans > F panes."""
    coll = Collector()
    graph = PipeGraph("mesh_idle", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(8):          # panes 0..7
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(60)   # frontier jumps during the idle gap
        for p in range(62, 66):     # resume: panes 62..65 (> next_fire + F)
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(70)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(4, 1)
          .with_key_capacity(1).with_mesh().build())
    graph.add_source(Source_Builder(src).with_output_batch_size(8).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    got = {k: v for k, v in coll.rows.items() if v is not None}
    tuples = set(range(8)) | set(range(62, 66))
    exp = {}
    for w in range(0, 66):
        s = sum(1.0 for p in range(w, w + 4) if p in tuples)
        if s:
            exp[(0, w)] = s
    # the stalled range (8..58) must produce NO valid windows, and the
    # resume windows must carry the correct (non-aliased) values
    assert not any(8 <= w < 59 for (_, w) in got), sorted(got)[:8]
    assert got == exp, (
        f"missing={sorted(set(exp) - set(got))[:6]} "
        f"extra={sorted(set(got) - set(exp))[:6]}")


@needs_multi
def test_mesh_outrun_grows_ring():
    """A source briefly outrunning its watermarks (pane far past the
    ring's headroom) triggers host-driven ring GROWTH with leaf
    migration — the single-chip plane's _grow_ring analog (round-4
    parity; previously fatal) — and the results stay exact."""
    coll = Collector()
    graph = PipeGraph("mesh_grow", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(8):  # panes 0..7 live (no watermark yet)
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        # pane 400 >> F(32)-win with frontier still 0: must GROW (to 512
        # panes), migrating the live leaves — then fire correctly
        for p in range(400, 404):
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(410)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(4, 1)
          .with_key_capacity(1).with_mesh().build())
    graph.add_source(Source_Builder(src).with_output_batch_size(4).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    got = {k: v for k, v in coll.rows.items() if v is not None}
    tuples = set(range(8)) | set(range(400, 404))
    exp = {}
    for w in range(0, 404):
        s = sum(1.0 for p in range(w, w + 4) if p in tuples)
        if s:
            exp[(0, w)] = s
    assert got == exp, (
        f"missing={sorted(set(exp) - set(got))[:6]} "
        f"extra={sorted(set(got) - set(exp))[:6]}")


def test_mesh_outrunning_watermark_beyond_cap_raises():
    """Growth is refused past RING_CAP_PANES (an outrun of a million
    panes is a watermark bug, not a burst): the loud error remains."""
    graph = PipeGraph("mesh_outrun", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(8):
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        # no watermark: frontier stays 0; pane 2^21 >> RING_CAP_PANES
        shipper.push_with_timestamp({"key": 0, "value": 1.0}, 1 << 21)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(4, 1)
          .with_key_capacity(1).with_mesh().build())
    graph.add_source(Source_Builder(src).with_output_batch_size(4).build()
                     ).add(op).add_sink(
        Sink_Builder(lambda r, c: None).build())
    with pytest.raises(WindFlowError, match="ring"):
        graph.run()


def _run_late_policy_pipeline(late_policy):
    """Fire w0/w1 first (nf -> 2 panes), then deliver a LATE tuple at
    pane 2 — inside the last fired window (w1 spans panes 1..4) but also
    inside open windows (w2 spans 2..5). The two policies must diverge
    exactly there (advisor r4 finding #1): "keep_open" folds it into w2,
    "ref_fired" drops it like ``wf/window_replica.hpp:257-258``."""
    coll = Collector()
    graph = PipeGraph(f"mesh_late_{late_policy}", ExecutionMode.DEFAULT,
                      TimePolicy.EVENT_TIME)

    def src(shipper, ctx):
        for p in range(8):          # panes 0..7 (pane_len = 1 µs)
            shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
        shipper.set_next_watermark(5)
        # carries wm=5: the step fires w0 (end 4) and w1 (end 5) -> nf=2
        shipper.push_with_timestamp({"key": 0, "value": 0.0}, 7)
        # LATE: pane 2 in [nf, nf + win - slide) = [2, 5)
        shipper.push_with_timestamp({"key": 0, "value": 100.0}, 2)

    op = (Ffat_Windows_TPU_Builder(
            lambda f: {"value": f["value"]},
            lambda a, b: {"value": a["value"] + b["value"]})
          .with_key_by("key").with_tb_windows(4, 1)
          .with_key_capacity(1)
          .with_mesh(late_policy=late_policy).build())
    graph.add_source(Source_Builder(src).with_output_batch_size(1).build()
                     ).add(op).add_sink(Sink_Builder(coll.sink).build())
    graph.run()
    return {k: v for k, v in coll.rows.items() if v is not None}


@needs_multi
@pytest.mark.parametrize("late_policy,w2", [("keep_open", 104.0),
                                            ("ref_fired", 4.0)])
def test_mesh_late_policy(late_policy, w2):
    got = _run_late_policy_pipeline(late_policy)
    # w0/w1 fired BEFORE the late tuple arrived: identical either way
    assert got[(0, 0)] == 4.0 and got[(0, 1)] == 4.0
    # the discriminating window: open at arrival, spans the late pane
    assert got[(0, 2)] == w2, got
    # downstream windows never contain pane 2: identical either way
    assert got[(0, 3)] == 4.0 and got[(0, 7)] == 1.0


def test_mesh_late_policy_validation():
    with pytest.raises(WindFlowError, match="late_policy"):
        (Ffat_Windows_TPU_Builder(lambda f: f, lambda a, b: a)
         .with_key_by("key").with_tb_windows(4, 1)
         .with_mesh(late_policy="nope").build())


def test_keymap_capacity_overflow_rolls_back():
    """Advisor r4 finding #2: a key refused by on_new (capacity) must NOT
    stay registered — a caught-and-retried batch would silently get an
    out-of-range slot feeding device routing."""
    from windflow_tpu.tpu.keymap import KeySlotMap
    cap = 2

    def on_new(key, slot):
        if slot >= cap:
            raise WindFlowError("over capacity")

    m = KeySlotMap(on_new=on_new)
    assert m.slot("a") == 0 and m.slot("b") == 1
    for _ in range(2):          # the retry must raise AGAIN, not return 2
        with pytest.raises(WindFlowError, match="capacity"):
            m.slot("c")
        assert len(m) == 2
    # same contract through the vectorized int path (LUT miss loop)
    m2 = KeySlotMap(on_new=on_new)
    a = np.array([5, 9, 9])
    assert list(m2.slots_of(a, a, 3)) == [0, 1, 1]
    b = np.array([11])
    for _ in range(2):
        with pytest.raises(WindFlowError, match="capacity"):
            m2.slots_of(b, b, 1)
        assert len(m2) == 2


@needs_multi
def test_forest_int32_index_plane_guard():
    """Advisor r4 finding #3: k_local * 2 * ring_panes must refuse loudly
    when it would overflow the int32 flat-index plane (ring growth doubles
    F through the same construction path)."""
    from windflow_tpu.parallel import make_key_mesh, sharded_ffat_forest
    mesh = make_key_mesh(8, shape=(8, 1))
    with pytest.raises(ValueError, match="int32 index plane"):
        sharded_ffat_forest(
            mesh, lambda f: f, lambda a, b: a, n_keys=1 << 28,
            win_panes=4, slide_panes=1, local_batch=8, fire_rounds=2,
            ring_panes=64)


@needs_multi
def test_mesh_late_policy_hopping_windows_coincide():
    """Hopping windows (slide > win): the ref_fired offset must clamp at
    0, never below next_fire (an under-drop would fold tuples into
    EVICTED ring leaves). Gap panes belong to no window, so the two
    policies must produce identical results."""
    def run(late_policy):
        coll = Collector()
        graph = PipeGraph(f"mesh_hop_{late_policy}", ExecutionMode.DEFAULT,
                          TimePolicy.EVENT_TIME)

        def src(shipper, ctx):
            for p in range(12):       # win=1/slide=3 panes: gaps 1,2 etc.
                shipper.push_with_timestamp({"key": 0, "value": 1.0}, p)
            shipper.set_next_watermark(7)
            shipper.push_with_timestamp({"key": 0, "value": 0.0}, 11)
            # gap pane 4 (window starts: 0,3,6,9 with win=1): in no window,
            # and below next_fire once w0/w1 fired
            shipper.push_with_timestamp({"key": 0, "value": 100.0}, 4)

        op = (Ffat_Windows_TPU_Builder(
                lambda f: {"value": f["value"]},
                lambda a, b: {"value": a["value"] + b["value"]})
              .with_key_by("key").with_tb_windows(1, 3)
              .with_key_capacity(1)
              .with_mesh(late_policy=late_policy).build())
        graph.add_source(
            Source_Builder(src).with_output_batch_size(1).build()
        ).add(op).add_sink(Sink_Builder(coll.sink).build())
        graph.run()
        return {k: v for k, v in coll.rows.items() if v is not None}

    keep, ref = run("keep_open"), run("ref_fired")
    assert keep == ref, (keep, ref)
    # windows hold exactly their single start pane's value (no 100 leak)
    assert all(v == 1.0 for v in keep.values()), keep


@needs_multi
def test_mesh_catch_up_drain_count_pins_device_rule():
    """Verdict r4 weak #8: `_catch_up` sizes the WHOLE drain from ONE
    control fetch (a D2H per step would serialize the drain), so its
    count formula must exactly cover the device's eligibility rule
    (fire iff next_fire + win <= frontier AND max_leaf >= next_fire).
    Construct a device state mixing idle keys (ml < nf), deep backlogs,
    boundary keys and ahead-of-frontier keys; assert the drain fires
    EXACTLY the brute-force-eligible window count (a probe step after it
    fires nothing), then sabotage the step count by one and assert the
    probe CATCHES the under-fire — the formula is tight, not padded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from windflow_tpu.tpu.batch import BatchTPU
    from windflow_tpu.tpu.ffat_mesh import Ffat_Windows_Mesh
    from windflow_tpu.tpu.schema import TupleSchema

    WIN_P, SLIDE_P, ROUNDS = 4, 1, 2
    op = Ffat_Windows_Mesh(
        lift=lambda f: {"value": f["value"]},
        combine=lambda a, b: {"value": a["value"] + b["value"]},
        key_extractor="key", win_len=WIN_P, slide_len=SLIDE_P,
        key_capacity=8, fire_rounds=ROUNDS, mesh_shape=(8, 1),
        name="drain_pin")
    op.build_replicas()
    rep = op.replicas[0]
    emitted = []
    rep._emit_batch = lambda b: emitted.append(b)

    # one real batch (key 0, pane 0) builds the step + state and anchors
    # the pane rebase at 0; frontier 0 so nothing fires
    schema = TupleSchema({"value": np.dtype(np.float64)})
    seed = BatchTPU({"value": np.ones(1)}, np.zeros(1, np.int64), 1,
                    schema, wm=0, host_keys=np.array([0], np.int64))
    rep.process_device_batch(seed)
    assert not emitted

    def craft(nf_vals, ml_vals):
        sh1 = NamedSharding(rep._mesh, P("key"))
        st = rep._state
        rep._state = (
            st[0], st[1],
            jax.device_put(np.array(nf_vals, np.int32), sh1),
            jax.device_put(np.array(ml_vals, np.int32), sh1),
            jax.device_put((np.array(nf_vals, np.int32)
                            // SLIDE_P).astype(np.int32), sh1))

    def brute(nf, ml, frontier):
        """Literal simulation of the device fire rule."""
        fires = 0
        while nf + WIN_P <= frontier and ml >= nf:
            fires += 1
            nf += SLIDE_P
        return fires

    def probe_fires():
        before = sum(b.size for b in emitted)
        rep._run_steps(np.zeros(0, np.int32), np.zeros(0, np.int32),
                       rep._empty_vals())
        return sum(b.size for b in emitted) - before

    #        k0 deep  k1 mid  k2 ahead  k3 idle  k4 edge  k5 deep  k6/7 empty
    NF = [0,      5,      28,       10,      26,      0,       0, 0]
    ML = [19,     7,      40,        4,      26,      25,     -1, -1]
    FRONTIER = 30
    craft(NF, ML)
    rep._frontier = FRONTIER
    rep._backlog_bound = 1
    emitted.clear()
    rep._catch_up()
    expected = sum(brute(nf, ml, FRONTIER) for nf, ml in zip(NF, ML))
    assert expected > 0
    got = sum(b.size for b in emitted)
    assert got == expected, (got, expected)
    assert probe_fires() == 0  # no under-fire left, no over-fire possible

    # ---- EOS flush: same one-fetch sizing, frontier past every pane ----
    craft(NF, ML)
    rep._frontier = FRONTIER
    rep._max_pane_seen = 40
    emitted.clear()
    rep.flush_on_termination()
    eos_frontier = 40 + WIN_P + 1
    expected = sum(brute(nf, ml, eos_frontier) for nf, ml in zip(NF, ML))
    got = sum(b.size for b in emitted)
    assert got == expected, (got, expected)
    assert probe_fires() == 0

    # ---- sabotage: one fewer drain step must leave eligible windows ----
    craft(NF, ML)
    rep._frontier = FRONTIER
    nf = np.array(NF, np.int64)
    ml = np.array(ML, np.int64)
    per_key = np.minimum((FRONTIER - WIN_P - nf) // SLIDE_P,
                         (ml - nf) // SLIDE_P) + 1
    n_win = int(np.maximum(per_key, 0).max(initial=0))
    n_steps = -(-n_win // ROUNDS)
    emitted.clear()
    for _ in range(n_steps - 1):          # the off-by-one drain
        rep._run_steps(np.zeros(0, np.int32), np.zeros(0, np.int32),
                       rep._empty_vals())
    assert probe_fires() > 0, "formula is padded: off-by-one went unnoticed"
