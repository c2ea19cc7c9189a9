"""The window step's partial level rebuild, end to end on the CPU backend:
a time-based ``Ffat_Windows_TPU`` whose step recomputes only the internal
nodes over the panes written and evicted since the last rebuild (the dirty
ring ranges its plan carries, kept by the replica in prep order), and the
whole forest where they are wide. The replica is driven directly, so the
batch boundaries are the test's; its ring wraps several times, and the
stream holds every path that moves the ranges: out-of-order and late rows,
a ring growth, runs of ingest-only batches wider than the window of the
partial rebuild (each released into fire-only drain programs), dataless
fires at punctuations, and a snapshot restored into a new replica
mid-stream. Every window is the CPU ``Ffat_Windows``' for the same events
and watermarks."""

import jax
import numpy as np

from windflow_tpu import (ExecutionMode, Ffat_Windows_Builder, PipeGraph,
                          Sink_Builder, Source_Builder, TimePolicy)
from windflow_tpu.basic import WinType
from windflow_tpu.tpu.batch import BatchTPU
from windflow_tpu.tpu.ffat_tpu import (REBUILD_W, Ffat_Windows_TPU,
                                       rebuilds_by_ranges)
from windflow_tpu.tpu.schema import TupleSchema

PANE = 1000
WIN = 500             # panes, sliding by one: a ring of F = 1,024 leaves
PANES = 20            # panes a batch
GAP = 300             # panes no key writes, fewer than a window
N_KEYS = 4
SCHEMA = TupleSchema({"key": np.int32, "v": np.float32})


class Rows:
    """The replica's emitter: ``{(key, wid): value or None}``."""

    def __init__(self):
        self.rows, self.dups = {}, 0

    def emit_device_batch(self, b):
        cols = {n: np.asarray(c)[:b.size] for n, c in b.fields.items()}
        for k, w, ok, v in zip(cols["key"], cols["wid"], cols["valid"],
                               cols["v"]):
            self.dups += (int(k), int(w)) in self.rows
            self.rows[int(k), int(w)] = float(v) if ok else None

    def set_stats(self, s):
        pass

    def propagate_punctuation(self, wm):
        pass


def script(seed):
    """``("batch", rows, wm_pane)``, ``("punct", wm_pane)`` and
    ``("snapshot",)`` steps. Rows ``(key, pane, value)``, whole values
    (float32 sums exact in any order), every key a row in every pane (no
    key falls silent for a window), shuffled; past the start a straggler
    just behind the batch's panes and ahead of the watermark (live, out
    of order), and
    a row a hundred panes behind the oldest open window (late: every
    window that holds it has fired). ``gap``: panes before the batch that
    no key writes, whose leaves the last lap of the ring evicted and this
    one leaves as they are, too far ahead of the panes written for a
    window around those to reach them: only the evicted range rebuilds
    the nodes over them."""
    rng = np.random.default_rng(seed)
    steps, p, wm = [], 0, 0

    def batch(panes=PANES, park=False, gap=0):
        nonlocal p, wm
        p += gap
        rows = [(k, q, int(rng.integers(0, 100)))
                for q in range(p, p + panes) for k in range(N_KEYS)]
        if p - 1 > wm:
            rows.append((int(rng.integers(N_KEYS)),
                         int(rng.integers(max(wm + 1, p - 2), p)), 7))
        if wm > 100 + WIN:
            rows.append((int(rng.integers(N_KEYS)), wm - WIN - 100, 5))
        rng.shuffle(rows)
        p += panes
        if not park:
            wm = p - 2
        steps.append(("batch", rows, wm))

    def run(n, gap_every=0, **kw):
        for i in range(n):
            batch(gap=GAP if gap_every and i % gap_every == 1 else 0, **kw)

    run(60)                                # the ring of 1,024 wraps
    steps.append(("punct", p))             # dataless: the fire-only program
    wm = p
    run(30, park=True)                     # outgrows it: F 1,024 -> 2,048
    run(20)                                # the release drains (G_CAP cut)
    steps.append(("snapshot",))
    run(20, park=True)                     # ingest-only, 400 panes > 2W - 1
    run(150, gap_every=10)                 # the ring of 2,048 wraps 3 times
    steps.append(("punct", p))
    wm = p
    run(10)
    return steps


def tpu_windows(steps):
    op = Ffat_Windows_TPU(
        lift=lambda f: {"v": f["v"]},
        combine=lambda a, b: {"v": a["v"] + b["v"]}, key_extractor="key",
        win_len=WIN * PANE, slide_len=PANE, win_type=WinType.TB,
        key_capacity=N_KEYS, name="win")
    op.build_replicas()
    rep, reps, out = op.replicas[0], [], Rows()
    rep.emitter = out
    reps.append(rep)
    assert rep.F == 1024 and rebuilds_by_ranges(rep.F)
    for step in steps:
        if step[0] == "batch":
            _, rows, wm = step
            ks, ps, vs = (np.asarray(c) for c in zip(*rows))
            b = BatchTPU({"key": jax.device_put(ks.astype(np.int32)),
                          "v": jax.device_put(vs.astype(np.float32))},
                         ps.astype(np.int64) * PANE + 5, len(rows), SCHEMA,
                         wm=0, host_keys=ks.astype(np.int64))
            b.wm = wm * PANE
            rep.handle_msg(0, b)
        elif step[0] == "punct":
            rep.dispatch.drain(forced=True)
            rep.cur_wm = step[1] * PANE
            rep.on_punctuation(rep.cur_wm)
        else:
            state = rep.snapshot_state()
            op.build_replicas()
            rep = op.replicas[0]
            rep.emitter = out
            rep.restore_state(state)
            reps.append(rep)
    rep.flush_on_termination()
    return out, reps


def cpu_windows(steps):
    """The same events and watermarks through the CPU ``Ffat_Windows``."""
    got = {}

    def src(shipper, ctx):
        for step in steps:
            if step[0] == "batch":
                for k, q, v in step[1]:
                    shipper.push_with_timestamp({"key": k, "v": float(v)},
                                                q * PANE + 5)
                shipper.set_next_watermark(step[2] * PANE)
            elif step[0] == "punct":
                shipper.set_next_watermark(step[1] * PANE)

    def sink(r):
        if r is not None:
            got[r.key, r.wid] = r.value

    g = PipeGraph("ffat_cpu_partial_rebuild", ExecutionMode.DEFAULT,
                  TimePolicy.EVENT_TIME)
    op = (Ffat_Windows_Builder(lambda t: t["v"], lambda a, b: a + b)
          .with_key_by(lambda t: t["key"])
          .with_tb_windows(WIN * PANE, PANE).build())
    g.add_source(Source_Builder(src).build()).add(op) \
        .add_sink(Sink_Builder(sink).build())
    g.run()
    return got


def test_partial_rebuild_windows_equal_the_cpu_operators():
    steps = script(seed=43)
    assert sum(s[0] == "batch" for s in steps) > 250
    tpu, reps = tpu_windows(steps)
    want = cpu_windows(steps)
    assert tpu.dups == 0
    assert len(want) > 30000 and tpu.rows == want
    first, last = reps
    # the ring wrapped, grew, and wrapped three times more
    assert (first.F, last.F) == (2048, 2048)
    assert last._leaf_frontier > 3 * last.F
    st = [r.stats for r in reps]
    rebuilds = sum(s.rebuild_programs for s in st)
    partial = sum(s.rebuild_partial_programs for s in st)
    # most rebuilds went by the dirty ranges; the first ones, the growth,
    # the restore, the wide ingest-only runs and the ring's wraps did not
    assert 0 < partial < rebuilds
    assert partial > rebuilds // 2
    # fire-only drains ran (a released run fires more ranges than G_CAP)
    assert sum(s.fire_programs for s in st) > rebuilds
    # late rows were dropped by the device plane too
    assert sum(r.ignored for r in reps) > 0
    # the parked run was over the window; a gap stays under a window
    assert 2 * REBUILD_W < 20 * PANES and GAP + PANES < WIN


def test_every_rebuild_is_full_where_the_partial_one_is_off(monkeypatch):
    """The choice goes by the input alone: with the partial rebuild off
    (a ring no wider than two windows), the same stream gives the same
    windows through full rebuilds only."""
    from windflow_tpu.tpu import ffat_tpu
    monkeypatch.setattr(ffat_tpu, "rebuilds_by_ranges", lambda F: False)
    steps = script(seed=43)[:130]
    tpu, reps = tpu_windows(steps)
    want = cpu_windows(steps)
    assert tpu.dups == 0 and tpu.rows == want
    assert sum(r.stats.rebuild_partial_programs for r in reps) == 0
    assert sum(r.stats.rebuild_programs for r in reps) > 0
