"""The edge ``Ffat_Windows_TPU`` -> ``Map_TPU`` -> sink: a device operator
downstream of the window operator takes its fired batches (the rows that
hold a result, the ``valid`` false rows of empty windows, the
end-of-stream flush), through a columnar and a row sink, and a checkpoint
cut between the two restores to the same rows. CPU backend."""

import dataclasses

import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, PipeGraph, Sink_Builder,
                          Source_Builder, TimePolicy)
from windflow_tpu.tpu import (Ffat_Windows_TPU_Builder, Filter_TPU_Builder,
                              Map_TPU_Builder)

K, ROWS, BLOCKS, PANE_US = 4, 32, 16, 1000
WIN, SLIDE = 4, 2                       # panes
QUIET = (3, range(4, 12))               # key 3 sends nothing in these blocks


class InjectedCrash(Exception):
    pass


def make_blocks():
    """Block ``p`` lies in pane ``p``; key 3 is silent for eight panes, so
    its windows over them are empty and fire with ``valid`` false. One
    reading of key 3 in the block before the silence is stamped past it
    (pane 12): the key still holds an event when the fires reach the
    silent panes. Without it the key's slot would be given back once its
    last reading had fired (PR 34) and it would return as a new key, with
    no window over the silence at all (tests/test_ffat_key_reclaim.py)."""
    rng = np.random.default_rng(11)
    out = []
    for p in range(BLOCKS):
        k = rng.integers(0, K, ROWS).astype(np.int32)
        if p in QUIET[1]:
            k[k == QUIET[0]] = 0
        v = rng.integers(1, 50, ROWS).astype(np.int32)
        ts = p * PANE_US + np.arange(ROWS, dtype=np.int64)
        if p == QUIET[1][0] - 1:
            k[1], ts[1] = QUIET[0], QUIET[1][-1] * PANE_US + PANE_US
        out.append(({"k": k, "v": v}, ts))
    return out


class BlockSource:
    """Replayable column-block source; optionally asks for a checkpoint
    after ``ckpt_at`` blocks and dies before block ``crash_at``."""

    def __init__(self, blocks, ckpt_at=None, crash_at=None):
        self.blocks, self.pos = blocks, 0
        self.ckpt_at, self.crash_at = ckpt_at, crash_at

    def __call__(self, shipper):
        while self.pos < len(self.blocks):
            if self.pos == self.crash_at:
                raise InjectedCrash(f"killed before block {self.pos}")
            cols, ts = self.blocks[self.pos]
            shipper.set_next_watermark(max(0, int(ts[0]) - 1))
            shipper.push_columns(cols, ts=ts)
            shipper.set_next_watermark(int(ts[-1]))
            self.pos += 1
            if self.pos == self.ckpt_at:
                assert shipper.request_checkpoint() is not None

    def snapshot_position(self):
        return self.pos

    def restore(self, pos):
        self.pos = pos


def expected_rows(blocks):
    """{(key, wid): twice the sum} of every window that holds an event
    (window ``w`` is panes ``[w*SLIDE, w*SLIDE + WIN)`` from time 0)."""
    panes = {}
    for cols, ts in blocks:
        for k, v, t in zip(cols["k"].tolist(), cols["v"].tolist(),
                           (ts // PANE_US).tolist()):
            panes[(k, t)] = panes.get((k, t), 0) + v
    out = {}
    for (k, p), s in panes.items():
        for w in range(max(0, (p - WIN) // SLIDE + 1), p // SLIDE + 1):
            out[(k, w)] = out.get((k, w), 0) + 2 * s
    return out


def graph(source, results, columnar, store=None):
    g = PipeGraph("edge", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    if store is not None:
        g.with_checkpointing(store_dir=store)
    win = (Ffat_Windows_TPU_Builder(
               lambda f: {"s": f["v"]}, lambda a, b: {"s": a["s"] + b["s"]})
           .with_key_by("k").with_tb_windows(WIN * PANE_US, SLIDE * PANE_US)
           .with_key_capacity(K).with_name("win").build())
    twice = (Map_TPU_Builder(lambda f: {**f, "twice": f["s"] * 2})
             .with_name("twice").build())

    def col_sink(cols, ts):
        if cols is not None:
            for k, w, t, ok in zip(*(np.asarray(cols[c]).tolist()
                                     for c in ("k", "wid", "twice",
                                               "valid"))):
                results.append((k, w, t, bool(ok)))

    def row_sink(t):
        if t is not None:
            results.append((int(t["k"]), int(t["wid"]), int(t["twice"]),
                            bool(t["valid"])))

    sink = (Sink_Builder(col_sink).with_columns() if columnar
            else Sink_Builder(row_sink)).with_name("snk").build()
    g.add_source(Source_Builder(source).with_name("src")
                 .with_output_batch_size(ROWS).build()) \
     .add(win).add(twice).add_sink(sink)
    return g


@pytest.fixture(scope="module", params=["columnar", "rows"])
def delivered(request):
    blocks, results = make_blocks(), []
    g = graph(BlockSource(blocks), results, request.param == "columnar")
    g.run()
    stats = {o["name"]: o["replicas"][0]
             for o in g.get_stats()["Operators"]}
    return {"rows": results, "want": expected_rows(blocks), "stats": stats}


def test_fired_rows_pass_through_the_device_operator(delivered):
    got = {(k, w): t for k, w, t, ok in delivered["rows"] if ok}
    assert got == delivered["want"]
    assert sum(ok for *_, ok in delivered["rows"]) == len(got)   # once each


def test_empty_windows_arrive_with_valid_false(delivered):
    empty = [(k, w) for k, w, _, ok in delivered["rows"] if not ok]
    assert empty and all(k == QUIET[0] for k, _ in empty)
    assert not set(empty) & set(delivered["want"])
    # the silent panes 4..11 hold windows 2..4 whole
    assert {w for _, w in empty} >= {2, 3, 4}


def test_end_of_stream_flush_reaches_the_sink(delivered):
    """The last blocks' windows are closed by no watermark: they fire in
    the flush at end of stream, partial, and still cross the map."""
    last = (BLOCKS - 1) // SLIDE
    got = {(k, w) for k, w, _, ok in delivered["rows"] if ok}
    assert {w for _, w in got} >= {last - 1, last}
    st = delivered["stats"]
    assert st["twice"]["Device_batches_in"] == \
        st["win"]["Device_batches_out"] == st["win"]["Fire_programs"] > 0
    assert st["win"]["Windows_fired"] == len(delivered["rows"])


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "rows"])
def test_checkpoint_cut_between_window_and_map_restores(tmp_path, columnar):
    blocks = make_blocks()
    store = str(tmp_path / "store")
    crashed, restored = [], []
    g = graph(BlockSource(blocks, ckpt_at=6, crash_at=11), crashed, columnar,
              store)
    with pytest.raises(InjectedCrash):
        g.run()
    assert g._coordinator.completed == 1
    g2 = graph(BlockSource(blocks), restored, columnar, store)
    g2.run(restore_from=store)
    # a plain sink is at-least-once: the replayed stretch delivers the
    # same rows again, so the union is compared
    merged = {(k, w): t for k, w, t, ok in crashed + restored if ok}
    assert merged == expected_rows(blocks)
    assert restored and len(restored) < len(merged) + 8


@pytest.mark.parametrize("change", ["adds", "drops", "keeps",
                                    "adds_in_a_filtered_chain"])
def test_rows_leave_the_device_with_the_columns_the_map_made(change):
    """A ``Map_TPU`` that adds or drops a column changes what a row is:
    the row exit hands on the operator's columns, not the input's (also
    out of a fused chain that ends in a filter's compaction)."""
    funcs = {"adds": lambda f: {**f, "w": f["v"] + 1},
             "drops": lambda f: {"v": f["v"]},
             "keeps": lambda f: {"k": f["k"], "v": f["v"] * 2}}
    funcs["adds_in_a_filtered_chain"] = funcs["adds"]
    got = []

    def src(shipper):
        for i in range(40):
            shipper.push({"k": i % 4, "v": i})

    g = PipeGraph("cols", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    pipe = g.add_source(Source_Builder(src).with_name("src")
                        .with_output_batch_size(16).build()) \
     .add(Map_TPU_Builder(funcs[change]).with_name("m").build())
    if change == "adds_in_a_filtered_chain":
        pipe = pipe.chain(Filter_TPU_Builder(lambda f: f["v"] >= 0)
                          .with_name("all").build())
    pipe.add_sink(Sink_Builder(lambda t: got.append(t) if t is not None
                               else None).with_name("snk").build())
    g.run()
    adds = [{"k": i % 4, "v": i, "w": i + 1} for i in range(40)]
    want = {"adds": adds, "adds_in_a_filtered_chain": adds,
            "drops": [{"v": i} for i in range(40)],
            "keeps": [{"k": i % 4, "v": 2 * i} for i in range(40)]}[change]
    assert sorted(got, key=lambda t: t["v"]) == want


@dataclasses.dataclass
class Reading:
    k: int
    v: int


def test_typed_rows_stay_typed_beside_a_helper_column():
    """Rows of a user's type keep it while the columns hold its fields:
    a column the map adds beside them is a helper of the device plane."""
    got = []

    def src(shipper):
        for i in range(40):
            shipper.push(Reading(i % 4, i))

    g = PipeGraph("typed", ExecutionMode.DEFAULT, TimePolicy.INGRESS_TIME)
    g.add_source(Source_Builder(src).with_name("src")
                 .with_output_batch_size(16).build()) \
     .add(Map_TPU_Builder(lambda f: {**f, "v": f["v"] * 3,
                                     "odd": f["v"] % 2})
          .with_name("m").build()) \
     .add_sink(Sink_Builder(lambda t: got.append(t) if t is not None
                            else None).with_name("snk").build())
    g.run()
    assert sorted(got, key=lambda t: t.v) == \
        [Reading(i % 4, 3 * i) for i in range(40)]
