"""The benchmark's own tests: on the CPU, at tiny sizes.

    python3 -m pytest bench/tests

They sit outside the repository's tier-1 ``testpaths`` (``tests/``).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
