"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (jax locks the device count on first backend init, and the
dry-run must set XLA_FLAGS before that happens).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod: 256 chips as ("data", "model") = (16, 16).
    Multi-pod: 2 pods x 256 chips as ("pod", "data", "model") = (2, 16, 16).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D "data" mesh (smoke tests,
    examples).  Kept separate so tests never build the 512-way mesh."""
    n = len(jax.devices())
    return jax.make_mesh(
        (n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )


def shard_rows(*arrays):
    """Shard each array's leading axis across the local 1-D "data" mesh.

    The fleet-scale replay flattens (N scenarios x P pools) into one row
    axis and every per-row op is elementwise along it, so placing the rows
    once lets XLA's computation-follows-data propagation shard the whole
    scan.  Host (numpy) arrays go to their shards directly, so the whole
    batch never lands on one device first.  On a single-device host, when
    the row count doesn't divide the device count, or while
    ``jax.default_device`` pins work to one device, the arrays stay whole
    on the default device, so the compiled program — and its bit-exact
    outputs — are unchanged.  Returns the arrays in order (a single array
    when called with one argument)."""
    n = len(jax.devices())
    if (
        n > 1 and jax.config.jax_default_device is None
        and all(a.shape[0] % n == 0 for a in arrays)
    ):
        mesh = make_host_mesh()
        spec = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")
        )
        arrays = tuple(jax.device_put(a, spec) for a in arrays)
    else:
        arrays = tuple(jnp.asarray(a) for a in arrays)
    return arrays[0] if len(arrays) == 1 else arrays
