"""Ratio of two gauges at the end of the run: the largest value of each
field among the operators of a role. params: {"role": <role or operator
name>, "num": <get_stats() field>, "den": <get_stats() field>, "scale":
1.0}. Nothing where no operator's ``get_stats()`` has both fields (a
commit from before the counters existed), or where the denominator is
0."""


def read(ctx, params):
    st = ctx.stats
    for field in (params["num"], params["den"]):
        if not any(field in tot for tot in st.end.values()):
            return None
    den = float(st.final(params["role"], params["den"]))
    if den <= 0:
        return None
    return float(st.final(params["role"], params["num"])) / den \
        * params.get("scale", 1.0)
