"""The multicast split (``MultiPipe.split(field, n, mask=True)``): a column
holds a bitmask of branches a row, bit ``b`` sending the row to branch
``b`` and 0 dropping it, the columnar sibling of a splitting function that
returns a vector of indices. After a device operator it reads that one
column back and gathers nothing for a branch every row selects
(``Split_whole_batches``, ``Split_gathered_batches``); on the host plane
it routes tuple by tuple to the same branches."""

import numpy as np
import pytest

from windflow_tpu import (ExecutionMode, Map_Builder, PipeGraph,
                          Sink_Builder, Source_Builder, TimePolicy,
                          WindFlowError)
from windflow_tpu.tpu import Map_TPU_Builder

BLK = 64


class Rows:
    """Columnar sink: the values a branch received."""

    def __init__(self):
        self.v = []

    def __call__(self, cols, ts):
        if cols is not None:
            self.v += cols["v"].tolist()


def masked_source(masks):
    """Blocks of ``BLK`` rows: ``v`` the row's number, ``m`` its mask."""
    def src(shipper, ctx=None):
        for i in range(0, len(masks), BLK):
            n = len(masks[i:i + BLK])
            ts = np.arange(i, i + n, dtype=np.int64)
            shipper.set_next_watermark(max(0, i - 1))
            shipper.push_columns({"v": np.arange(i, i + n, dtype=np.int32),
                                  "m": masks[i:i + BLK]}, ts=ts)
    return src


def run_split(masks, branches, device=True, mask=True, field="m"):
    """source -> Map_TPU ``route`` (or a host map) -> split by ``field``
    -> a Map_TPU and a columnar sink a branch (a sink alone on the host);
    the values each branch got and the stats of the operator the split
    follows."""
    sinks = [Rows() for _ in range(branches)]
    g = PipeGraph("split_mask", ExecutionMode.DEFAULT, TimePolicy.EVENT_TIME)
    pipe = g.add_source(Source_Builder(masked_source(masks))
                        .with_output_batch_size(BLK).build())
    if device:
        pipe.add(Map_TPU_Builder(dict).with_name("route").build())
    else:
        pipe.add(Map_Builder(dict).with_name("route").build())
    pipe.split(field, branches, mask=mask)
    for b in range(branches):
        if device:
            # a device stage a branch: the split's own edges stay on the
            # device, and what it reads back is its routing column alone
            pipe.select(b).add(Map_TPU_Builder(dict).build()).add_sink(
                Sink_Builder(sinks[b]).with_columns().build())
        else:
            pipe.select(b).add_sink(Sink_Builder(
                lambda t, s=sinks[b]: s.v.append(t["v"]) if t is not None
                else None).build())
    g.run()
    route = {o["name"]: o["replicas"][0]
             for o in g.get_stats()["Operators"]}["route"]
    return [sorted(s.v) for s in sinks], route


def want(masks, branches):
    return [sorted(np.nonzero(masks >> b & 1)[0].tolist())
            for b in range(branches)]


def test_a_row_goes_to_each_branch_its_bits_name():
    masks = np.random.default_rng(3).integers(0, 8, 8 * BLK).astype(np.int32)
    got, st = run_split(masks, 3)
    assert got == want(masks, 3)
    # one column read back, four bytes a row; a gather a branch a batch
    assert st["Device_bytes_D2H"] == 4 * len(masks)
    assert st["Split_gathered_batches"] == 3 * 8
    assert st["Split_whole_batches"] == 0


def test_a_batch_every_row_selects_is_delivered_whole():
    """Every row to both branches (as q7 sends every bid): each branch
    gets the batch itself, nothing is gathered."""
    masks = np.full(6 * BLK, 3, np.int32)
    masks[-BLK:] = 1                   # the last batch to branch 0 alone
    got, st = run_split(masks, 2)
    assert got == want(masks, 2)
    assert st["Split_whole_batches"] == 2 * 5 + 1
    assert st["Split_gathered_batches"] == 0


def test_mask_zero_drops_a_row():
    masks = np.where(np.arange(4 * BLK) % 5 == 0, 0, 2).astype(np.int32)
    got, st = run_split(masks, 2)
    assert got[0] == [] and got[1] == want(masks, 2)[1]
    assert len(got[1]) == 4 * BLK - len(range(0, 4 * BLK, 5))
    assert st["Split_gathered_batches"] == 4


def test_the_index_form_routes_as_it_did():
    idx = np.random.default_rng(4).integers(0, 3, 8 * BLK).astype(np.int32)
    got, st = run_split(idx, 3, mask=False)
    assert got == [sorted(np.nonzero(idx == b)[0].tolist())
                   for b in range(3)]
    assert st["Device_bytes_D2H"] == 4 * len(idx)
    assert st["Split_gathered_batches"] == 3 * 8


def test_the_host_plane_routes_by_the_same_bits():
    masks = np.random.default_rng(5).integers(0, 4, 4 * BLK).astype(np.int32)
    got, _ = run_split(masks, 2, device=False)
    assert got == want(masks, 2) == run_split(masks, 2)[0]


def test_a_bit_past_the_branches_is_refused_by_name():
    masks = np.full(2 * BLK, 4, np.int32)
    with pytest.raises(WindFlowError, match="branch mask"):
        run_split(masks, 2)


def test_the_mask_form_takes_a_field_name():
    g = PipeGraph("split_mask_fn")
    pipe = g.add_source(Source_Builder(lambda s: None).build())
    with pytest.raises(WindFlowError, match="mask=True"):
        pipe.split(lambda t: 3, 2, mask=True)
