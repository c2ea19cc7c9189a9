"""The harness's own tests: ``python -m pytest benchmark/tests`` on the CPU
backend. Not under ``tests/``; the tier-1 count is untouched."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
