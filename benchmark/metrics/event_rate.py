"""Events offered in the window over the time from its start until the
sink had the last result of the drained stream."""


def read(ctx, params):
    if ctx.window_s <= 0 or ctx.events <= 0:
        return None
    return ctx.events / ctx.window_s
