"""Ratio of counter deltas over the window.
params: {"num": [[role, field], ...], "den": [[role, field], ...] |
"events" | "window_us", "scale": 1.0}. The role "*" is every operator.
Returns nothing when the denominator is 0."""


def _sum(ctx, pairs):
    total = 0.0
    for role, field in pairs:
        if role == "*":
            total += ctx.stats.total(field) - ctx.stats.total(field, False)
        else:
            total += ctx.stats.delta(role, field)
    return total


def read(ctx, params):
    den = params["den"]
    if den == "events":
        d = float(ctx.events)
    elif den == "window_us":
        d = ctx.window_s * 1e6
    else:
        d = _sum(ctx, den)
    if d <= 0:
        return None
    return _sum(ctx, params["num"]) / d * params.get("scale", 1.0)
