"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization.  Every mesh here uses Auto axis
types, so bare-PartitionSpec sharding constraints resolve inside jit under
``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e target: 16×16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — a 1×N mesh for tests/examples."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh (everything except 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")


def batch_shards(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in data_axes(mesh))
