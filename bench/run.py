#!/usr/bin/env python3
"""Benchmark of the rolling commitment planner on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the cell's metrics as the last line of standard output, one JSON
object, and each number compared for ``correct`` beside its limit as the
last lines of standard error.  Exits 1 without a result when JAX finds no
TPU, or fewer chips than the cell asks for.  See ``lib/harness.py``.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The compile cache lives at a fixed place in the checkout.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

from lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
