"""Production meshes.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
device initialization.

Axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` (distributed/sharding.constrain), which JAX
accepts only on Auto mesh axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_local_mesh():
    """1-device mesh with the production axis names (for CPU smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))


def data_parallel_size(mesh) -> int:
    s = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            s *= mesh.shape[ax]
    return s


def model_parallel_size(mesh) -> int:
    return mesh.shape.get("model", 1)
