"""Share of the traced window the device spent in the XLA modules a regex
names: device seconds of those modules (mean over the chips used) over
the traced window's seconds. params: {"modules": <regex over XLA module
names>}. Nothing without a trace, or when no such module ran in it."""

from harness import trace


def read(ctx, params):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    dev_s = trace.modules_seconds(t, params["modules"])
    if dev_s <= 0:
        return None
    return dev_s / t["window_s"] * 100.0
