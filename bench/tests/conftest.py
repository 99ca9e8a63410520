"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They sit outside the repository's tier-1 ``testpaths``."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
