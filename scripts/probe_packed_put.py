#!/usr/bin/env python3
"""PR 26's probe: what does one staged batch of nine (16384,) int32 columns
cost to ``jax.device_put`` (a) a column a call, (b) as a dict in one call,
(c) as one flat (9*16384,) array, (d) as one (9, 16384) array; and what
does a jitted filter+gather cost over each form. From a Python thread, quiet
and beside two threads that want the interpreter lock. Prints one JSON line.
"""
import json
import statistics
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

C, N, REPS, RING = 9, 16384, 300, 16
NAMES = [f"c{i}" for i in range(C)]
rng = np.random.default_rng(7)
ring = [rng.integers(0, 3, (C, N)).astype(np.int32) for _ in range(RING)]
FORMS = {
    "a_nine_puts": lambda m: {n: jax.device_put(m[i]) for i, n in enumerate(NAMES)},
    "b_dict_one_call": lambda m: jax.device_put({n: m[i] for i, n in enumerate(NAMES)}),
    "c_flat": lambda m: jax.device_put(m.reshape(-1)),
    "d_2d": lambda m: jax.device_put(m),
}


def cols_of(x):
    if isinstance(x, dict):
        return x
    if x.ndim == 1:
        return {n: x[i * N:(i + 1) * N] for i, n in enumerate(NAMES)}
    return {n: x[i] for i, n in enumerate(NAMES)}


@jax.jit
def prog(x):
    f = cols_of(x)
    keep = f["c0"] == 0
    pos = jnp.where(keep, jnp.cumsum(keep) - 1, jnp.sum(keep) + jnp.cumsum(~keep) - 1)
    order = jnp.zeros(N, jnp.int32).at[pos].set(jnp.arange(N, dtype=jnp.int32))
    return {k: v[order] for k, v in f.items()}, jnp.sum(keep)


def measure(out):
    for name, put in FORMS.items():
        ref = jax.tree_util.tree_map(np.asarray, prog(FORMS["a_nine_puts"](ring[0])))
        got = jax.tree_util.tree_map(np.asarray, prog(put(ring[0])))   # warm + check
        same = all(np.array_equal(ref[0][k], got[0][k]) for k in NAMES) and ref[1] == got[1]
        issue, ready, launch, run = [], [], [], []
        for r in range(REPS):
            m = ring[r % RING]
            t0 = time.perf_counter()
            x = put(m)
            t1 = time.perf_counter()
            jax.block_until_ready(x)
            t2 = time.perf_counter()
            y = prog(x)
            t3 = time.perf_counter()
            jax.block_until_ready(y)
            t4 = time.perf_counter()
            issue.append(t1 - t0); ready.append(t2 - t0)
            launch.append(t3 - t2); run.append(t4 - t2)
        med = lambda v: round(statistics.median(v) * 1e6, 1)
        out[name] = {"put_issue_us": med(issue), "put_ready_us": med(ready),
                     "prog_launch_us": med(launch), "prog_done_us": med(run),
                     "same_outputs": bool(same)}


def in_thread(fn, *a):
    t = threading.Thread(target=fn, args=a)
    t.start()
    t.join()


def main():
    res = {"device": jax.devices()[0].device_kind, "quiet": {}, "contended": {}}
    in_thread(measure, res["quiet"])
    stop = threading.Event()

    def spin():           # a worker's shape: some Python, then a native copy
        a, b = np.zeros(N, np.int32), np.zeros(N, np.int32)
        while not stop.is_set():
            for _ in range(200):
                pass
            np.copyto(a, b)

    spinners = [threading.Thread(target=spin, daemon=True) for _ in range(2)]
    for s in spinners:
        s.start()
    in_thread(measure, res["contended"])
    stop.set()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
