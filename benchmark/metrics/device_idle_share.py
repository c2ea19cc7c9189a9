"""1 - union of device-operation intervals over the traced window, mean
over the chips used. Nothing without a trace that holds device work."""


def read(ctx, params):
    t = ctx.trace
    if t is None or t["devices"] == 0 or t["busy_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
