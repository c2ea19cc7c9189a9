"""A gauge at the end of the run: the largest value of a field among the
operators of a role. params: {"role": <role or operator name>, "field":
<get_stats() field>}. Nothing where no operator's ``get_stats()`` has the
field."""


def read(ctx, params):
    if not any(params["field"] in tot for tot in ctx.stats.end.values()):
        return None
    return float(ctx.stats.final(params["role"], params["field"]))
