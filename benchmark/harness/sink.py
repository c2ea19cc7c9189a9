"""The recording sink: keeps the arrays it is handed, with one clock
reading per call, and reduces them after the window."""

from __future__ import annotations

import threading
import time

import numpy as np


class RecordingSink:
    """``with_columns`` sink functor. ``names`` maps the roles key, wid,
    value, valid to the configuration's result columns."""

    def __init__(self, names: dict):
        self.names = names
        self.calls = []           # (clock, key, wid, value, valid)
        self.eos_at = None
        self.n_valid = 0          # valid rows delivered so far
        self._lock = threading.Lock()

    def __call__(self, cols, ts) -> None:
        now = time.perf_counter()
        if cols is None:
            self.eos_at = now
            return
        n = self.names
        part = (now, cols[n["key"]], cols[n["wid"]], cols[n["value"]],
                cols[n["valid"]])
        with self._lock:
            self.calls.append(part)
            self.n_valid += int(np.count_nonzero(part[4]))

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def last_delivery(self):
        """Clock of the last call that delivered rows, or None."""
        with self._lock:
            return self.calls[-1][0] if self.calls else None

    def columns(self):
        """(clock per row, key, wid, value, valid) over every call."""
        with self._lock:
            calls = list(self.calls)
        if not calls:
            z = np.zeros(0, np.int64)
            return np.zeros(0), z, z, z, np.zeros(0, bool)
        at = np.concatenate([np.full(len(c[1]), c[0]) for c in calls])
        return (at, *(np.concatenate([np.asarray(c[i]) for c in calls])
                      for i in range(1, 5)))
