"""``get_stats()`` snapshots and their deltas between the window's start
and its end. Counters are summed over an operator's replicas; an operator
is found by its name, or by the fused stage whose label holds its name."""

from __future__ import annotations

import re

_MAX_FIELDS = ("Queue_emit_fifo_depth_max", "Queue_depth_max",
               "Dispatch_queue_depth_max")


def snapshot(graph) -> dict:
    ops = {}
    for o in graph.get_stats()["Operators"]:
        tot = {}
        for rep in o["replicas"]:
            for k, v in rep.items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                tot[k] = max(tot.get(k, 0), v) if k in _MAX_FIELDS \
                    else tot.get(k, 0) + v
        tot["_last_compile"] = [r.get("Compile_last_signature", "")
                                for r in o["replicas"]]
        ops[o["name"]] = tot
    return ops


def find(snap: dict, name: str) -> dict:
    if name in snap:
        return snap[name]
    for label, tot in snap.items():
        if name in re.split(r"[^\w.]+", label):   # "views∘join"
            return tot
    raise KeyError(f"no operator {name!r} in stats: {sorted(snap)}")


class StatsWindow:
    """Counter deltas over the measured window."""

    def __init__(self, start: dict, end: dict, roles: dict):
        self.start, self.end, self.roles = start, end, roles

    def delta(self, role_or_name: str, field: str) -> float:
        """``field`` of one operator (a role of the configuration, or an
        operator's name), end minus start."""
        name = self.roles.get(role_or_name, role_or_name)
        names = name if isinstance(name, list) else [name]
        return sum(find(self.end, n).get(field, 0)
                   - find(self.start, n).get(field, 0) for n in names)

    def final(self, role_or_name: str, field: str) -> float:
        name = self.roles.get(role_or_name, role_or_name)
        names = name if isinstance(name, list) else [name]
        return max(find(self.end, n).get(field, 0) for n in names)

    def total(self, field: str, at_end: bool = True) -> float:
        snap = self.end if at_end else self.start
        return sum(t.get(field, 0) for t in snap.values())
