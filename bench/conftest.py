"""Puts the benchmark's own modules and the program on the path for
``pytest bench/``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
