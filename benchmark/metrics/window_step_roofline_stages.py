"""``window_step_roofline.py`` for a configuration with more than one
window operator: the least bytes a block needs (harness/roofline.py) are
summed over the stages, each from its own counters, against the device
time of the operators' XLA modules (they share their names) per block in
the traced window. Bytes-bound (see roofline.py).
params: {"modules": <regex over XLA module names>, "stages": [{"role": <a
role of the configuration>, "win": <key of cfg["window"] that holds the
stage's window length>, "slide": <likewise its slide>, "fields": <4-byte
words an aggregate holds>}, ...]}; the first stage is the one whose input
batches are the blocks. A stage's rows, fired windows and batches are its
``Inputs_received``, ``Windows_fired`` and ``Device_batches_in``; the keys
a batch touches are taken as the keys it ADMITS (``Keys_admitted`` a
batch, at least one): fewer than it touches, so fewer bytes. Nothing
without a trace, when no such module ran in it, or from a program whose
window operators do not count their keys."""

import math

from harness import roofline, trace


def read(ctx, params):
    t = ctx.trace
    if t is None:
        return None
    dev_s = trace.modules_seconds(t, params["modules"])
    st, stages = ctx.stats, params["stages"]
    blocks = st.delta(stages[0]["role"], "Device_batches_in")
    if dev_s <= 0 or blocks <= 0 or ctx.offered_s <= 0:
        return None
    if not any("Keys_admitted" in tot for tot in st.end.values()):
        return None
    # device seconds per block: traced module time over the blocks the
    # first stage took in the same span (blocks flow evenly)
    per_block_s = dev_s / (blocks * t["window_s"] / ctx.offered_s)
    w = ctx.cfg["window"]
    block_us = ctx.clock.rows * 1e6 / ctx.clock.rate
    need = 0.0
    for s in stages:
        batches = st.delta(s["role"], "Device_batches_in")
        if batches <= 0:
            continue
        win, slide = w[s["win"]], w[s["slide"]]
        pane = math.gcd(win, slide)
        wu, su = win // pane, slide // pane
        per_batch = roofline.window_step_bytes(
            rows=st.delta(s["role"], "Inputs_received") / batches,
            keys_touched=max(1.0, st.delta(s["role"], "Keys_admitted")
                             / batches),
            panes_per_batch=block_us / pane + 1,
            fired=st.delta(s["role"], "Windows_fired") / batches,
            ring=roofline.ring_size(wu, su), win_units=wu,
            fields=s.get("fields", 1))
        need += per_batch * batches / blocks
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return need / peak / per_block_s * 100.0
