"""``window_step_roofline.py`` for cells of COUNT-based windows: the
least bytes the algorithm needs per batch (harness/roofline.py) over the
chip's peak, against the device time of the window operator's XLA modules
per batch in the traced window. Bytes-bound (see roofline.py).
params: {"modules": <regex over XLA module names>, "fields": <4-byte words
an aggregate holds, 1 where not given>}. Nothing when no such module ran
in the trace. It reads the configuration's ``count_window`` (``win_rows``,
``slide_rows``): a leaf is a key's arrival, so a batch adds ``rows /
keys`` leaves a key, and what fired is the window operator's own
``Windows_fired`` (the windows a filter after it drops were answered
too)."""

from harness import roofline, trace


def read(ctx, params):
    t = ctx.trace
    if t is None:
        return None
    dev_s = trace.modules_seconds(t, params["modules"])
    batches = ctx.stats.delta("window", "Device_batches_in")
    if dev_s <= 0 or batches <= 0 or ctx.offered_s <= 0:
        return None
    # device seconds per batch: traced module time over the batches the
    # operator took in the same span (batches flow evenly over the window)
    per_batch_s = dev_s / (batches * t["window_s"] / ctx.offered_s)
    w = ctx.cfg["count_window"]
    rows = ctx.stats.delta("window", "Inputs_received") / batches
    keys = min(ctx.cfg["keys"]["count"], rows)
    need = roofline.window_step_bytes(
        rows=rows, keys_touched=keys, panes_per_batch=rows / keys,
        fired=ctx.stats.delta("window", "Windows_fired") / batches,
        ring=roofline.ring_size(w["win_rows"], w["slide_rows"]),
        win_units=w["win_rows"], fields=params.get("fields", 1))
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return need / peak / per_batch_s * 100.0
