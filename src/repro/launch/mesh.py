"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host
devices before first jax init, smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 v5e chips) or 2x16x16 two-pod (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """A mesh with Auto axes: shardings propagate through ``jit`` the
    way the engines' ``shard_map`` programs expect (``jax.make_mesh``
    defaults to Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Development mesh over however many devices exist."""
    n = len(jax.devices())
    n_data = min(n_data, n)
    n_model = max(1, min(n_model, n // n_data))
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
