"""Share of the HBM roofline the keyed grid scan's programs reach: the
least bytes the window's grid scans need over the chip's peak, against
the device time of the stateful operator's XLA modules in the traced
window (the scans flow evenly over the window, so the traced span holds
its share of the bytes).

The count, all on the side of FEWER bytes, from the operator's own
counters over the window (role ``window``):

- rows in: every scanned row is read once at its input's width
  (``Scan_rows`` x ``in_words``);
- state: every touched key's state row is read and written once
  (``Scan_keys`` x ``state_bytes`` x 2);
- rows out: every scanned row leaves once at the output's width
  (``Scan_rows`` x ``out_words``: a stateful map answers every row).

The grid itself (the rows scattered to KB x M cells, the walk over every
cell, the outputs gathered back) is the implementation's and is not
counted: no implementation needs fewer bytes, so none reads over 100%.
The walk goes by its depth, not its bytes, so the share reads low.
params: {"modules": <regex over XLA module names>, "in_words",
"out_words": 4-byte columns of a row in and out, "state_bytes": bytes of
one key's state}. Nothing without a trace, where no such module ran in
it, or from a program without the counters."""

from harness import roofline, trace

WORD = 4


def grid_scan_bytes(rows, keys, in_words, out_words, state_bytes) -> float:
    """Least bytes of grid scans with these counts (see above)."""
    return rows * (in_words + out_words) * WORD + keys * state_bytes * 2


def read(ctx, params):
    t, st = ctx.trace, ctx.stats
    if t is None or ctx.offered_s <= 0:
        return None
    if not any("Scan_keys" in tot for tot in st.end.values()):
        return None
    dev_s = trace.modules_seconds(t, params["modules"])
    if dev_s <= 0:
        return None
    need = grid_scan_bytes(
        st.delta("window", "Scan_rows"), st.delta("window", "Scan_keys"),
        params["in_words"], params["out_words"], params["state_bytes"])
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return need * (t["window_s"] / ctx.offered_s) / peak / dev_s * 100.0
