"""Share of the HBM roofline the interval join's programs reach: the
least bytes the window's join steps need over the chip's peak, against
the device time of the operator's XLA modules in the traced window (the
steps flow evenly over the window, so the traced span holds its share of
the bytes).

The count, all on the side of FEWER bytes, from the operator's own
counters over the window (role ``window``):

- rows in: every row that probed is read once at its input's width, the
  columns and the time word (``Join_probe_rows_a`` / ``_b``);
- the probed archive: of each live row only the key and the time are
  read, once a step (``Join_scanned_rows``: the live rows of the archive
  a step probed, summed over the steps);
- pairs out: every delivered row is written once at the output's width
  (``Join_pairs``);
- rows archived: written once at their input's width
  (``Join_archived_rows_a`` / ``_b``).

Purging (a compaction of both archives a step), the sort's passes and
the gathers' index traffic are the implementation's and are not counted:
no implementation needs fewer bytes, so none reads over 100%. A few
integer operations a byte, far under the chip's ridge: bytes-bound.
params: {"modules": <regex over XLA module names>, "a_words", "b_words",
"out_words": 4-byte columns of an A row, a B row, an output row}.
Nothing without a trace, where no such module ran in it, or from a
program without the counters."""

from harness import roofline, trace

WORD = 4


def join_step_bytes(probe_a, probe_b, scanned, pairs, archived_a,
                    archived_b, a_words, b_words, out_words) -> float:
    """Least bytes of join steps with these counts (see above)."""
    a_row, b_row = (a_words + 1) * WORD, (b_words + 1) * WORD
    return ((probe_a + archived_a) * a_row + (probe_b + archived_b) * b_row
            + scanned * 2 * WORD + pairs * (out_words + 1) * WORD)


def read(ctx, params):
    t, st = ctx.trace, ctx.stats
    if t is None or ctx.offered_s <= 0:
        return None
    if not any("Join_scanned_rows" in tot for tot in st.end.values()):
        return None
    dev_s = trace.modules_seconds(t, params["modules"])
    if dev_s <= 0:
        return None
    d = lambda field: st.delta("window", field)  # noqa: E731
    need = join_step_bytes(
        d("Join_probe_rows_a"), d("Join_probe_rows_b"),
        d("Join_scanned_rows"), d("Join_pairs"), d("Join_archived_rows_a"),
        d("Join_archived_rows_b"), params["a_words"], params["b_words"],
        params["out_words"])
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return need * (t["window_s"] / ctx.offered_s) / peak / dev_s * 100.0
