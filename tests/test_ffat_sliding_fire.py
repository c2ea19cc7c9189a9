"""The fire query by sliding scan (``tpu/ffat_tpu.py`` ``_query_fns``):
count-based windows of a key that leave in one program are consecutive
ranges of its ring, answered from two block scans over the leaves,
against the lane walk (one tree walk a fired window). The replica is
driven directly, on the CPU backend, as ``test_ffat_grouped_fire.py``
drives it; which query a program holds is one comparison of static
shapes with ``SLIDE_X``, so the test sets that constant from its side
(0: nothing slides) and changes nothing else."""

import itertools

import numpy as np
import pytest

import windflow_tpu.tpu.ffat_tpu as ft
from windflow_tpu.basic import WinType
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.ffat_tpu import Ffat_Windows_TPU, fire_slides
from windflow_tpu.tpu.schema import TupleSchema

SCHEMA = TupleSchema({"key": np.int32, "v": np.float32})
P = 8191        # affine maps mod a prime: products stay inside int32


def lift_sd(f):
    import jax.numpy as jnp
    return {"sum": f["v"], "count": jnp.ones(f["v"].shape, jnp.int32),
            "last": f["v"]}


def comb_sd(a, b):
    """``sd``'s aggregate: not commutative in ``last``."""
    return {"sum": a["sum"] + b["sum"], "count": a["count"] + b["count"],
            "last": b["last"]}


def lift_affine(f):
    import jax.numpy as jnp
    v = f["v"].astype(jnp.int32)
    return {"a": v % 97 + 2, "b": v % 89}


def comb_affine(f, g):
    """``x -> a x + b`` mod ``P``, composed in stream order: associative,
    not commutative."""
    return {"a": f["a"] * g["a"] % P, "b": (f["b"] * g["a"] + g["b"]) % P}


class Rows:
    """The replica's emitter: every fired row, in the order emitted."""

    def __init__(self):
        self.rows = []
        self.cols = []

    def emit_device_batch(self, b):
        cols = {n: np.asarray(c)[:b.size] for n, c in b.fields.items()}
        self.cols.append(cols)
        names = sorted(n for n in cols if n not in ("key", "wid", "valid"))
        for i in range(b.size):
            ok = bool(cols["valid"][i])
            # where valid is False the values are whatever the query
            # left: not part of the result
            self.rows.append(
                (int(cols["key"][i]), int(cols["wid"][i]), ok)
                + tuple(cols[n][i].tobytes() if ok else b"" for n in names))

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass


def make_replicas(n, win=8, slide=1, budget=64, keys=4, lift=lift_sd,
                  combine=comb_sd):
    """``n`` replicas of one operator (they share its compiled programs)."""
    op = Ffat_Windows_TPU(
        lift=lift, combine=combine, key_extractor="key", win_len=win,
        slide_len=slide, win_type=WinType.CB, num_win_per_batch=budget,
        key_capacity=keys, name="win", parallelism=n)
    op.build_replicas()
    for rep in op.replicas:
        rep.emitter = Rows()
    return op.replicas


def make_replica(**kw):
    return make_replicas(1, **kw)[0]


def batch(keys, vals):
    import jax
    keys = np.asarray(keys, np.int64)
    cols = {"key": jax.device_put(keys.astype(np.int32)),
            "v": jax.device_put(np.asarray(vals, np.float32))}
    return BatchTPU(cols, np.zeros(len(keys), np.int64), len(keys), SCHEMA,
                    wm=0, host_keys=keys)


def stream(sizes, n_keys=3, seed=5, whole=True, keys_of=None):
    """A batch of each of ``sizes`` readings, keys drawn uniformly (or
    ``keys_of(i, n)``), whole-number values unless told otherwise."""
    rng = np.random.default_rng(seed)
    out = []
    for i, n in enumerate(sizes):
        ks = (rng.integers(0, n_keys, n) if keys_of is None
              else np.asarray(keys_of(i, n)))
        vals = rng.random(n) * 100
        out.append(batch(ks, np.floor(vals) if whole else vals))
    return out


def run(rep, batches, flush=True):
    for b in batches:
        rep.handle_msg(0, b)
    if flush:
        rep.flush_on_termination()
    else:
        rep.dispatch.drain(forced=True)     # commits are deferred
    return rep.emitter.rows


def both(monkeypatch, batches_fn, flush=True, make=make_replica, **kw):
    """The same stream through the sliding scan (every program) and
    through the lane walk (every program)."""
    reps = []
    for x in (1 << 40, 0):
        monkeypatch.setattr(ft, "SLIDE_X", x)
        rep = make(**kw)
        run(rep, batches_fn(), flush)
        reps.append(rep)
    scan, lane = reps
    assert scan.stats.fire_sliding_programs == scan.stats.fire_programs > 0
    assert lane.stats.fire_sliding_programs == 0
    assert lane.stats.fire_programs == scan.stats.fire_programs
    assert (lane.stats.windows_fired == scan.stats.windows_fired
            == len(scan.emitter.rows))
    assert scan.stats.fire_grouped_programs == 0
    return scan, lane


def lone_keys(i, n):
    """Key 0 takes the stream; key 1 gets ONE reading, key 2 exactly one
    tumbling window's worth (nothing left of it at the flush)."""
    ks = np.zeros(n, np.int64)
    if i == 0:
        ks[3] = 1
        ks[4:12] = 2
    return ks


# every case: equal rows (key, wid, valid, the values' bytes where valid)
# in equal order. F = 32 for win 8..16, 128 for win 64
CASES = {
    # sd's shape made small: slide 1, a window a reading
    "win64_slide1": dict(sizes=[90] * 8, kw=dict(win=64, slide=1, budget=256)),
    "win10_slide3": dict(sizes=[40] * 12, kw=dict(win=10, slide=3)),
    "win17_slide5": dict(sizes=[40] * 12, kw=dict(win=17, slide=5)),
    "win8_slide8_tumbling": dict(sizes=[40] * 12, kw=dict(win=8, slide=8)),
    "win12_slide4": dict(sizes=[40] * 12, kw=dict(win=12, slide=4)),
    "win3_slide5_gaps": dict(sizes=[40] * 12, kw=dict(win=3, slide=5)),
    # 60 readings of one key in a batch: 53 windows of it in ONE program,
    # more than win / slide = 8 (every block of the scan is crossed)
    "many_windows_of_a_key_in_a_program": dict(
        sizes=[60] * 4, n_keys=1, kw=dict(win=8, slide=1, budget=64),
        check=lambda rep: max(c["wid"].size for c in rep.emitter.cols) > 50),
    # 1,500 readings a key over a ring of 32 leaves
    "ring_wraps": dict(
        sizes=[30] * 150, kw=dict(win=13, slide=2),
        check=lambda rep: rep.F == 32 and rep.max_leaf[:3].min() > 40 * 32),
    # a batch brings a key more readings than its ring holds
    "ring_grows_mid_stream": dict(
        sizes=[20, 90, 20], n_keys=2, kw=dict(win=8, slide=1),
        check=lambda rep: rep.F > 32),
    # a budget of 16 under ~40 windows a batch: the cumulative clip cuts
    # a key's chunk in two programs, the second a fire-only one
    "budget_cuts_a_chunk_in_two_programs": dict(
        sizes=[40] * 8, kw=dict(win=8, slide=1, budget=16),
        check=lambda rep: rep.stats.fire_programs > 2 * 8),
    # three readings a batch fire nothing for a while (ingest-only
    # steps, the rebuild deferred), then a burst leaves through the
    # step and fire-only programs behind it
    "fire_only_drains_after_ingest_only_batches": dict(
        sizes=[3, 3, 3, 3, 90, 3, 3, 60], kw=dict(win=16, slide=1, budget=16),
        check=lambda rep: (rep.stats.device_programs_run
                           > rep.stats.fire_programs > 8)),
    # nothing completes before the end: every window is a partial one of
    # the flush
    "flush_of_partial_windows": dict(
        sizes=[5, 5, 5], kw=dict(win=16, slide=2),
        check=lambda rep: rep.stats.fire_programs == 1),
    "one_reading_and_emptied_keys": dict(
        sizes=[40] * 3, keys_of=lone_keys, kw=dict(win=8, slide=8),
        check=lambda rep: (
            {(1, 0), (2, 0)} <= {r[:2] for r in rep.emitter.rows}
            and (2, 1) not in {r[:2] for r in rep.emitter.rows})),
    "affine_maps_mod_a_prime": dict(
        sizes=[40] * 12, kw=dict(win=10, slide=3, lift=lift_affine,
                                 combine=comb_affine)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sliding_scan_equals_lane_walk(case, monkeypatch):
    spec = dict(CASES[case])
    kw, check = spec.pop("kw"), spec.pop("check", None)
    scan, lane = both(monkeypatch, lambda: stream(**spec), **kw)
    assert scan.emitter.rows == lane.emitter.rows
    assert len(scan.emitter.rows) > 5
    assert any(r[2] for r in scan.emitter.rows)
    if check is not None:
        assert check(scan) and check(lane)


def test_sd_last_is_the_windows_last_reading(monkeypatch):
    """Against plain Python: ``sum``, ``count`` and ``last`` of every
    window of a key, complete ones and the flush's partial ones."""
    monkeypatch.setattr(ft, "SLIDE_X", 1 << 40)
    rng = np.random.default_rng(2)
    vals = np.floor(rng.random(100) * 50)
    rep = make_replica(win=10, slide=3, keys=1)
    run(rep, [batch(np.zeros(25), vals[i:i + 25]) for i in range(0, 100, 25)])
    got = {}
    for cols in rep.emitter.cols:
        for i in range(cols["wid"].size):
            assert cols["valid"][i]
            got[int(cols["wid"][i])] = (
                float(cols["sum"][i]), int(cols["count"][i]),
                float(cols["last"][i]))
    want = {w: (float(vals[w * 3:w * 3 + 10].sum()),
                len(vals[w * 3:w * 3 + 10]), float(vals[w * 3:w * 3 + 10][-1]))
            for w in range(34)}
    assert got == want


def test_float_sums_within_rounding_and_whole_numbers_exact(monkeypatch):
    """Another parenthesisation of the same ordered leaves: float32 sums
    of fractions agree within rounding, ``count`` and ``last`` exactly
    (whole numbers, bit for bit, are the parametrised cases above)."""
    scan, lane = both(monkeypatch,
                      lambda: stream([50] * 10, whole=False, seed=9),
                      win=64, slide=1, budget=256)
    a, b = scan.emitter.cols, lane.emitter.cols
    assert len(a) == len(b)
    for ca, cb in zip(a, b):
        ok = cb["valid"]
        assert (ca["valid"] == ok).all() and (ca["wid"] == cb["wid"]).all()
        assert (ca["key"] == cb["key"]).all()
        assert (ca["count"][ok] == cb["count"][ok]).all()
        assert (ca["last"][ok] == cb["last"][ok]).all()
        np.testing.assert_allclose(ca["sum"][ok], cb["sum"][ok], rtol=1e-5)


def test_fused_replica_with_a_prefix_filter(monkeypatch):
    """``FusedFfatReplica`` inherits the query: a filter in front of the
    window, resolved at prep time, then the same windows by scan and by
    lane."""
    from windflow_tpu.tpu.fused_ops import FusedFfatReplica
    from windflow_tpu.tpu.ops_tpu import Filter_TPU

    def fused(**kw):
        keep = Filter_TPU(lambda f: f["v"] % 3 != 0, name="keep")
        op = Ffat_Windows_TPU(
            lift=lift_sd, combine=comb_sd, key_extractor="key", win_len=8,
            slide_len=2, win_type=WinType.CB, num_win_per_batch=64,
            key_capacity=4, name="win")
        rep = FusedFfatReplica([keep, op], 0)
        rep.emitter = Rows()
        return rep

    scan, lane = both(monkeypatch, lambda: stream([40] * 10), make=fused)
    assert scan.emitter.rows == lane.emitter.rows
    assert scan.stats.inputs_ignored > 100


def test_snapshot_and_restore_between_two_programs(monkeypatch):
    """A batch's windows leave in four programs; the run is cut after
    the first, snapshotted, and a restored replica fires the rest and
    goes on to the end of the stream: every (key, wid) once, bit-equal
    to the uninterrupted run."""
    monkeypatch.setattr(ft, "SLIDE_X", 1 << 40)

    def batches():
        return stream([6, 60], n_keys=2, seed=4)

    whole, cut, rest = make_replicas(3, win=8, slide=1, budget=16)
    want = list(run(whole, batches(), flush=False))
    assert whole.stats.fire_programs == 4 < len(want)

    run(cut, batches()[:1], flush=False)
    programs = cut._programs
    # the first program only: the rest of the plan is never taken
    cut._programs = lambda *a: itertools.islice(programs(*a), 1)
    run(cut, batches()[1:], flush=False)
    assert len(cut.emitter.rows) == 16       # the step's own program
    state = cut.snapshot_state()

    rest.restore_state(state)
    for rep in (rest, whole):
        run(rep, stream([30], n_keys=2, seed=8), flush=True)
    # the restored replica fires what the cut left with the next batch's
    # windows (keys interleave otherwise: the plan is in slot order)
    want = whole.emitter.rows
    assert sorted(cut.emitter.rows + rest.emitter.rows) == sorted(want)
    assert len({r[:2] for r in want}) == len(want) > 60
    assert rest.stats.fire_sliding_programs == rest.stats.fire_programs > 0


@pytest.mark.parametrize("W,K_cap,F,slides", [
    (16384, 64, 2048, True),      # sd's budget: 131,072 leaves
    (64, 4096, 2048, False),      # many keys, few windows a program
    (64, 16384, 1024, False),
    (16384, 4096, 2048, True),
])
def test_the_rule_on_shapes(W, K_cap, F, slides):
    assert fire_slides(W, K_cap, F) is slides
    # ... and it is the lanes against the leaves, nothing else
    assert fire_slides(2 * W, 2 * K_cap, F) is slides


def test_a_program_holds_one_query(monkeypatch):
    """The choice is made where the program is traced: a count-based
    program by scan holds no tree walk (no ``while`` at all: the walks
    are the program's only loops), one by lane no scan."""
    import jax

    def text(x):
        monkeypatch.setattr(ft, "SLIDE_X", x)
        rep = make_replica(win=8, slide=1, budget=64)
        rep._ensure_forest(batch([0], [1.0]).fields)
        fire = rep._query_fns()
        pack = np.zeros(rep._plan_len(64), np.int32)
        return jax.jit(fire).lower(rep.trees, rep.tvalid, pack).as_text()

    scan, lane = text(1 << 40), text(0)
    assert "while" not in scan and "while" in lane
