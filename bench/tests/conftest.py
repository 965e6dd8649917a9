"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They import the benchmark's modules by name, as ``bench/run.py`` does."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
