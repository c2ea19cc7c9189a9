"""Peaks of the chip and the least bytes a keyed window step needs.

``window_step_bytes`` is a function of shapes and counts alone — which
program implements the step does not enter. Assumptions, all on the side
of *fewer* bytes (so the share cannot be flattered by over-counting):

- rows in: each row's key composite and one word per aggregated field are
  read once (timestamps are resolved to panes on the host and never reach
  the device);
- leaves: a batch touches at most ``min(rows, keys_touched * panes_per_
  batch)`` leaves; each is read and written once, a word per field plus a
  validity byte;
- ancestors: the union of root paths of ``p`` adjacent leaves of one key
  holds about ``log2(F) + p - 1`` internal nodes; each is written once and
  its two children are read once;
- fire: a window over ``win_units`` leaves is answered from its canonical
  cover, at most ``max(1, 2*ceil(log2(win_units)))`` nodes read;
- results out: per fired window one word per field, a validity byte, an
  8-byte window id and a 4-byte key.

The step does a handful of integer operations per byte, far under the
chip's ridge (197e12 / 819e9 = 240 operations per byte), so the bound is
the bytes one: the metric says "bytes-bound".
"""

from __future__ import annotations

import json
import math
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json; "
                       "add it with its source, there is no default")
    return table[device_kind]


def window_step_bytes(rows: float, keys_touched: float,
                      panes_per_batch: float, fired: float, ring: int,
                      win_units: int, fields: int = 1,
                      word: int = 4) -> float:
    """Least bytes one batch of a keyed window step moves (see above).
    Counts may be means over the window's batches."""
    node = fields * word + 1
    leaves = min(rows, keys_touched * panes_per_batch)
    ancestors = keys_touched * (math.log2(ring) + panes_per_batch - 1)
    cover = max(1, 2 * math.ceil(math.log2(win_units))) if win_units > 1 \
        else 1
    return (rows * (word + fields * word)
            + leaves * 2 * node
            + ancestors * 3 * node
            + fired * cover * node
            + fired * (fields * word + 1 + 8 + 4))


def ring_size(win_units: int, slide_units: int) -> int:
    """Leaves per key the window needs to keep: the window plus slack for
    panes ahead of the watermark, rounded to a power of two (the geometry
    any ring-buffered aggregation tree of this window has)."""
    return 1 << max(3, math.ceil(math.log2(
        win_units + max(2 * slide_units, 16))))
