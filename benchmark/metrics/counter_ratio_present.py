"""``counter_ratio.py`` for counters a later PR added to the program:
the same ratio and the same params, and nothing where no operator's
``get_stats()`` has one of the numerator's fields (a commit from before
the counter existed: the metric is then left out, never reported as 0)."""

import os

from harness.cell import BENCH_DIR, load_module

_ratio = load_module(os.path.join(BENCH_DIR, "metrics", "counter_ratio.py"))


def read(ctx, params):
    for _, field in params["num"]:
        if not any(field in tot for tot in ctx.stats.end.values()):
            return None
    return _ratio.read(ctx, params)
