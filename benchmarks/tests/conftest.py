"""CPU tests of the harness: ``python -m pytest benchmarks/tests -q``.

They load no TPU library: jax is held to the CPU before anything imports
it, and the cells they run are the toys under ``cells/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
