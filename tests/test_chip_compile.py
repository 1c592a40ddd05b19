"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed next to the CPU backend, so the sweep
kernel can be handed to Mosaic at the shapes the rolling replay issues
without a chip: what the chip's compiler would refuse (a shape cast, a
block not aligned to the tiling, more VMEM than a kernel may use) fails
here.  The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  All such compiles live in this one file
so that they land on one worker.

The hindsight baseline's exact stack solve is compiled at the one-chip
scale replay's rows too: it is where that replay's memory peaks.

The persistent compilation cache is turned off around these compiles: an
entry written for a described chip cannot be read back without one.

The file also checks :mod:`repro.launch.compile_cache`, the helper that
places that cache for the chip entry points.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.capacity import pricing
from repro.core import portfolio as pf
from repro.kernels.commitment_sweep import ops
from repro.launch import compile_cache

#: One v5e chip's HBM.
V5E_HBM_BYTES = 16 * 1024**3


def hbm_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows,grid,hours,blocks", [
    # The rolling replay's grid sweep at P=1024 pools x 8 horizons.
    (8192, 128, 1344, (8, 128, 512)),
    # A wide candidate grid: bg grows to hold the HBM-pass budget and bt
    # shrinks to keep the broadcast temporary inside VMEM.
    (8192, 4096, 1344, (8, 512, 256)),
])
def test_sweep_kernel_compiles_for_v5e(one_chip, rows, grid, hours, blocks):
    assert ops.sweep_block_plan(rows, grid, hours) == blocks
    trace = jax.ShapeDtypeStruct((rows, hours), jnp.float32, sharding=one_chip)
    cands = jax.ShapeDtypeStruct((rows, grid), jnp.float32, sharding=one_chip)
    sweep = functools.partial(ops.commitment_sweep_over_under, interpret=False)
    compiled = jax.jit(sweep).lower(trace, cands, trace).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < hbm_bytes(compiled) < V5E_HBM_BYTES


def test_hindsight_stack_fits_one_v5e(one_chip):
    """P=1024 pools x N=16 scenarios, each row with its own cost lines,
    over the replay's 130-week evaluation window."""
    rows, hours, opts = 16384, 130 * 168, len(pf.options_from_pricing())
    demand = jax.ShapeDtypeStruct((rows, hours), jnp.float32,
                                  sharding=one_chip)
    lines = jax.ShapeDtypeStruct((rows, opts), jnp.float32, sharding=one_chip)
    solve = jax.vmap(functools.partial(
        pf.optimal_portfolio_stack, od_rate=pricing.on_demand_premium()
    ))
    compiled = jax.jit(solve).lower(demand, lines, lines).compile()
    assert 0 < hbm_bytes(compiled) < V5E_HBM_BYTES


class TestCompileCache:
    def test_environment_directory_is_left_to_jax(self, monkeypatch):
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_directory_is_fixed_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            compilation_cache.reset_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
