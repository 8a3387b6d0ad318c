"""Tests of the benchmark's own yardstick. Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite (``tests/``); this PR may add no
file there."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

REHEARSAL = "benchmark/tests/rehearsal/REGISTRY.json"
