"""A counter's delta over the window, summed over the operators of a role.
params: {"role": <role or operator name>, "field": <get_stats() field>}"""


def read(ctx, params):
    return float(ctx.stats.delta(params["role"], params["field"]))
