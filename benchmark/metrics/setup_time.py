"""Process start to the measured window's start."""


def read(ctx, params):
    return float(ctx.setup_s)
