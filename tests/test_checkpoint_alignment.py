"""Barrier-alignment property: random multi-input DAGs with skewed
channel rates must never process a post-barrier tuple into a pre-barrier
snapshot.

The invariant under test is exact-prefix consistency: for every source
``i``, the per-source tuple count inside the checkpointed downstream
state equals the replay position recorded in source ``i``'s own snapshot
(or the source's full length when it finished before the barrier — a
closed channel contributes its whole stream). Any post-barrier leak
inflates the count; any pre-barrier tuple buffered past the snapshot
deflates it. Randomization (seeded, no hypothesis dependency) covers
source counts, rate skew, consumer parallelism, merge fan-in, batching,
and both DEFAULT and DETERMINISTIC execution modes.
"""

from __future__ import annotations

import random
import time

import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Reduce, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.checkpoint import CheckpointStore


class SkewedSource:
    def __init__(self, n, src_id, ckpt_at=None, sleep_every=0,
                 sleep_s=0.0):
        self.n = n
        self.src_id = src_id
        self.ckpt_at = ckpt_at
        self.sleep_every = sleep_every
        self.sleep_s = sleep_s
        self.pos = 0

    def __call__(self, shipper):
        while self.pos < self.n:
            shipper.push({"src": self.src_id, "v": self.pos})
            self.pos += 1
            if self.sleep_every and self.pos % self.sleep_every == 0:
                time.sleep(self.sleep_s)
            if self.ckpt_at is not None and self.pos == self.ckpt_at:
                shipper.request_checkpoint()

    def snapshot_position(self):
        return self.pos

    def restore(self, pos):
        self.pos = pos


@pytest.mark.parametrize("seed", range(8))
def test_no_post_barrier_tuple_in_snapshot(seed, tmp_path):
    rng = random.Random(0xA11C + seed)
    n_sources = rng.randint(2, 4)
    mode = rng.choice([ExecutionMode.DEFAULT, ExecutionMode.DETERMINISTIC])
    counts = [rng.randint(150, 2500) for _ in range(n_sources)]
    # one source triggers mid-stream; the others notice (or finish first —
    # the closed-channel path is part of the property)
    trig = rng.randrange(n_sources)
    ckpt_at = rng.randint(50, counts[trig])
    batching = rng.choice([0, 0, 8, 32])
    consumer_par = rng.randint(1, 3)

    store = str(tmp_path / "store")
    g = PipeGraph(f"align{seed}", mode, TimePolicy.INGRESS_TIME)
    g.with_checkpointing(store_dir=store)
    sources = []
    pipes = []
    for i in range(n_sources):
        slow = rng.random() < 0.5
        s = SkewedSource(
            counts[i], i, ckpt_at=ckpt_at if i == trig else None,
            sleep_every=rng.choice([50, 100, 200]) if slow else 0,
            sleep_s=rng.choice([0.0005, 0.001]) if slow else 0.0)
        sources.append(s)
        pipes.append(g.add_source(
            Source_Builder(s).with_name(f"s{i}")
            .with_output_batch_size(batching).build()))
    red = Reduce(lambda t, s: (0 if s is None else s) + 1,
                 key_extractor=lambda t: t["src"], name="red",
                 parallelism=consumer_par)
    pipes[0].merge(*pipes[1:]).add(red) \
        .add_sink(Sink_Builder(lambda t: None).with_name("snk").build())
    g.run()

    assert g._coordinator.completed == 1
    st = CheckpointStore(store)
    cid = st.latest()
    d = st.checkpoint_dir(cid)
    states = st.load_states(d, st.load_manifest(d))
    counts_in_snapshot: dict = {}

    def count_msg(m):
        from windflow_tpu.message import Batch
        if getattr(m, "is_punct", False):
            return
        if isinstance(m, Batch):
            for payload, _ts in m.rows:
                k = payload["src"]
                counts_in_snapshot[k] = counts_in_snapshot.get(k, 0) + 1
        else:
            k = m.payload["src"]
            counts_in_snapshot[k] = counts_in_snapshot.get(k, 0) + 1

    for idx in range(consumer_par):
        rep = states[("red", idx)]
        for k, v in rep.get("key_state", {}).items():
            counts_in_snapshot[k] = counts_in_snapshot.get(k, 0) + v
        # DETERMINISTIC mode: pre-barrier tuples can legitimately sit in
        # the ordering collector's buffers at snapshot time — they are
        # part of the worker's snapshot, not a leak
        coll = rep.get("__collector__", {})
        for buf in coll.get("bufs", []):
            for m in buf:
                count_msg(m)
        for _ts, _seq, m in coll.get("heap", []):
            count_msg(m)
    for i in range(n_sources):
        position = states[(f"s{i}", 0)]["position"]
        assert counts_in_snapshot.get(i, 0) == position, (
            f"seed={seed} source {i}: snapshot saw "
            f"{counts_in_snapshot.get(i, 0)} tuples but the source's "
            f"barrier position was {position} (mode={mode.name}, "
            f"batching={batching}, par={consumer_par})")


def _keyed_stage(kind: str, n_keys: int, par: int):
    """One randomized keyed operator + a canonical result encoder (the
    encoder makes results order-insensitively comparable across runs)."""
    if kind == "reduce":
        op = Reduce(lambda t, s: (0 if s is None else s) + t["v"],
                    key_extractor=lambda t: t["src_key"], name="keyed",
                    parallelism=par)
        enc = (lambda r: ("red", r))
    elif kind == "windows":
        from windflow_tpu import Keyed_Windows, WinType
        op = Keyed_Windows(lambda rows: sum(x["v"] for x in rows),
                           key_extractor=lambda t: t["src_key"],
                           win_len=6, slide_len=2, win_type=WinType.CB,
                           name="keyed", parallelism=par)
        enc = (lambda r: (r.key, r.wid, r.value))
    else:  # ffat
        from windflow_tpu import Ffat_Windows, WinType
        op = Ffat_Windows(lambda t: t["v"], lambda a, b: a + b,
                          key_extractor=lambda t: t["src_key"],
                          win_len=8, slide_len=4, win_type=WinType.CB,
                          name="keyed", parallelism=par)
        enc = (lambda r: (r.key, r.wid, r.value))
    return op, enc


@pytest.mark.parametrize("seed", range(6))
def test_randomized_repartition_differential(seed, tmp_path):
    """The elastic-rescaling twin of the exact-prefix property: for a
    random keyed topology and a random live rescale N -> M (up and down,
    including M=1 and prime M), checkpoint -> repartition -> restore must
    produce results IDENTICAL to an uninterrupted run. Any key whose
    state lands on a replica the KEYBY emitters do not route it to, any
    buffered message lost in the collector remap, or any double-replayed
    source suffix breaks the multiset equality."""
    import threading

    from windflow_tpu import Sink_Builder

    rng = random.Random(0x5CA1E + seed)
    kind = rng.choice(["reduce", "windows", "ffat"])
    n_keys = rng.choice([5, 13, 32])
    old_n = rng.randint(1, 4)
    new_n = rng.choice([m for m in (1, 2, 3, 5, 7) if m != old_n])
    n_sources = rng.randint(1, 2)
    batching = rng.choice([0, 0, 8])
    counts = [rng.randint(1200, 3000) for _ in range(n_sources)]
    gate_at = rng.randint(300, min(counts) - 200)
    sink_par = rng.randint(1, 2)

    def run(par, rescale_to=None):
        results, lock = [], threading.Lock()
        gate = threading.Event() if rescale_to is not None else None
        g = PipeGraph(f"repart{seed}_{par}_{rescale_to}",
                      ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
        g.with_checkpointing(
            store_dir=str(tmp_path / f"st{par}_{rescale_to}"))
        srcs, pipes = [], []
        for i in range(n_sources):
            s = SkewedSource(counts[i], i)
            if rescale_to is not None and i == 0:
                # replica 0 pauses at the gate; the rescale happens there
                orig = s.__call__

                def gated(shipper, _s=s, _orig=orig):
                    while _s.pos < gate_at:
                        shipper.push({"src": _s.src_id, "v": _s.pos,
                                      "src_key": _s.src_id * n_keys
                                      + _s.pos % n_keys})
                        _s.pos += 1
                    gate.wait(30)
                    while _s.pos < _s.n:
                        shipper.push({"src": _s.src_id, "v": _s.pos,
                                      "src_key": _s.src_id * n_keys
                                      + _s.pos % n_keys})
                        _s.pos += 1
                srcs.append((s, gated))
            else:
                def plain(shipper, _s=s):
                    while _s.pos < _s.n:
                        shipper.push({"src": _s.src_id, "v": _s.pos,
                                      "src_key": _s.src_id * n_keys
                                      + _s.pos % n_keys})
                        _s.pos += 1
                srcs.append((s, plain))
        for i, (s, fn) in enumerate(srcs):
            fn.snapshot_position = s.snapshot_position
            fn.restore = s.restore
            pipes.append(g.add_source(
                Source_Builder(fn).with_name(f"s{i}")
                .with_output_batch_size(batching).build()))
        op, enc = _keyed_stage(kind, n_keys, par)
        tail = pipes[0].merge(*pipes[1:]) if len(pipes) > 1 else pipes[0]

        def sink(r):
            if r is not None:
                with lock:
                    results.append(enc(r))
        tail.add(op).add_sink(
            Sink_Builder(sink).with_name("snk")
            .with_parallelism(sink_par).build())
        if rescale_to is None:
            g.run()
            return sorted(results)
        g.start()
        deadline = time.monotonic() + 30
        while srcs[0][0].pos < gate_at and time.monotonic() < deadline:
            time.sleep(0.01)
        import threading as _t
        _t.Timer(0.2, gate.set).start()
        rep = g.rescale("keyed", rescale_to, timeout_s=30)
        assert rep.changed
        g.wait_end()
        return sorted(results)

    base = run(old_n)
    got = run(old_n, rescale_to=new_n)
    assert got == base, (
        f"seed={seed} kind={kind} {old_n}->{new_n} keys={n_keys} "
        f"batching={batching}: rescaled run diverged "
        f"({len(got)} vs {len(base)} results)")


def test_two_stage_alignment_stall_recorded(tmp_path):
    """A multi-input worker that aligns a skewed barrier records the
    stall; the checkpoint still commits exactly once."""
    store = str(tmp_path / "store")
    g = PipeGraph("align_stats", ExecutionMode.DEFAULT,
                  TimePolicy.INGRESS_TIME)
    g.with_checkpointing(store_dir=store)
    fast = SkewedSource(3000, 0, ckpt_at=500)
    slow = SkewedSource(1200, 1, sleep_every=50, sleep_s=0.002)
    p0 = g.add_source(Source_Builder(fast).with_name("s0").build())
    p1 = g.add_source(Source_Builder(slow).with_name("s1").build())
    red = Reduce(lambda t, s: (0 if s is None else s) + 1,
                 key_extractor=lambda t: t["src"], name="red")
    p0.merge(p1).add(red) \
        .add_sink(Sink_Builder(lambda t: None).with_name("snk").build())
    g.run()
    assert g._coordinator.completed == 1
    stats = g.get_stats()
    red_reps = [op for op in stats["Operators"]
                if op["name"] == "red"][0]["replicas"]
    assert sum(r["Checkpoint_snapshots"] for r in red_reps) == 1
    # the fast channel's barrier waited on the slow channel
    assert sum(r["Checkpoint_align_stall_usec_total"]
               for r in red_reps) > 0


def test_wait_committed_waits_through_the_commit_write(tmp_path):
    """``_finalize`` takes the epoch out of the pending table, writes the
    commit outside the lock, and only then publishes it as completed. A
    ``wait_committed`` that looks in between (a live rescale on a loaded
    machine) must keep waiting, not report the epoch as dropped."""
    import threading

    from windflow_tpu.checkpoint import CheckpointStore
    from windflow_tpu.checkpoint.coordinator import CheckpointCoordinator

    store = CheckpointStore(str(tmp_path / "store"))
    in_commit, release = threading.Event(), threading.Event()
    commit = store.commit

    def slow_commit(cid, meta):
        in_commit.set()
        assert release.wait(30)
        return commit(cid, meta)

    store.commit = slow_commit
    coord = CheckpointCoordinator(store, "race")
    coord.expected_acks = 1
    cid = coord.trigger(force=True)
    acker = threading.Thread(
        target=coord.ack, args=(cid, "w0", {("op", 0): {"x": 1}}))
    acker.start()
    assert in_commit.wait(30)
    waited = []
    waiter = threading.Thread(
        target=lambda: waited.append(coord.wait_committed(cid, 30)))
    waiter.start()
    waiter.join(0.3)
    assert waiter.is_alive() and not waited     # still waiting, no raise
    release.set()
    acker.join(30)
    waiter.join(30)
    assert waited == [None] and coord.last_completed_id == cid
    # an epoch nobody knows is still reported as dropped
    with pytest.raises(Exception, match="dropped without committing"):
        coord.wait_committed(cid + 7, 1)
