"""Share of the HBM roofline the window operator's programs reach: the
least bytes the algorithm needs per batch (harness/roofline.py) over the
chip's peak, against the device time of the operator's XLA modules per
batch in the traced window. Bytes-bound (see roofline.py).
params: {"modules": <regex over XLA module names>, "fields": <4-byte words
an aggregate holds, 1 where not given>}. Nothing when no such module ran
in the trace. A reader for cells of TIME-based windows, which list it:
it reads the configuration's ``window`` (``win_us``, ``slide_us``), the
key a configuration that says ``results_due`` and ``windows_per_event``
itself need not have."""

import math

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
    w = ctx.cfg["window"]
    pane = math.gcd(w["win_us"], w["slide_us"])
    wu, su = w["win_us"] // pane, w["slide_us"] // pane
    rows = ctx.stats.delta("window", "Inputs_received") / batches
    n_keys = ctx.cfg.get("keys", {}).get("count") or ctx.cfg["key_capacity"]
    block_us = ctx.clock.rows * 1e6 / ctx.clock.rate
    need = roofline.window_step_bytes(
        rows=rows, keys_touched=min(n_keys, rows),
        panes_per_batch=block_us / pane + 1,
        fired=ctx.fired_in_window() / batches,
        ring=roofline.ring_size(wu, su), win_units=wu,
        fields=params.get("fields", 1))
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return need / peak / per_batch_s * 100.0
