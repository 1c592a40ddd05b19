"""Each test starts with JAX's in-memory caches empty, as each run of the
benchmark starts in a process of its own.  The program keeps its compiled
replay for the life of a process (``repro.core.replan``), so without this
a test that swaps out a function the replay traces (the faults of
``test_faults.py``) would get the program an earlier test traced, and a
later test the faulty one."""

import jax
import pytest


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
