#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails without a TPU: there is no CPU fallback. ``--rehearse-cpu`` is for
the harness's own tests on the CPU backend; its metrics carry the suffix
``.cpu_rehearsal`` and never a device metric's name. ``--control 1`` also
computes the control of the comparison (the builder's check; the driver's
runs do not use it). The last line of standard output is the result.
"""

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness.cell import Cell
    from harness.runner import run_cell

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(Cell(args.workload, args.rehearse_cpu), args.seed, args.seconds,
                      bool(args.trace), T_PROC0, control=bool(args.control),
                      log=log)
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
