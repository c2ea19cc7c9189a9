#!/usr/bin/env python
"""A/B the FFAT bench configs under an env lever.

Usage: ab_ffat.py ENV_VAR label_when_0 label_when_1

Prints the active jax backend first, so a run on the CPU backend is
never read as a TPU A/B (on the CPU backend the WF_FORCE_HOST_SEG legs
would measure the same path twice)."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    env_var, label0, label1 = sys.argv[1], sys.argv[2], sys.argv[3]
    import jax

    import bench

    backend = jax.default_backend()
    print(f"backend={backend}", flush=True)
    if backend == "cpu":
        print(f"NOT a TPU A/B: backend is cpu; {env_var} legs are not "
              "meaningful here", flush=True)
    for flag, label in (("0", label0), ("1", label1)):
        os.environ[env_var] = flag
        chunks, _, _, progs = bench._run_config(
            bench.N_KEYS, bench.WIN_PER_BATCH, 12, repeats=2)
        st = bench._chunk_stats(chunks)
        print(f"{label}: 64keys mean {st['mean']/1e6:.1f}M / best "
              f"{st['best']/1e6:.1f}M t/s ({progs} programs)", flush=True)
        hchunks, _, _, _ = bench._run_config(
            bench.HC_KEYS, bench.HC_WIN_PER_BATCH, 6, repeats=2)
        hs = bench._chunk_stats(hchunks)
        print(f"{label}: 10k keys mean {hs['mean']/1e6:.1f}M t/s, "
              f"{hs['wps_mean']/1e6:.2f}M win/s", flush=True)


if __name__ == "__main__":
    main()
