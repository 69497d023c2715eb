"""Mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state.

Every mesh here has ``Auto`` axes: the step pins its intermediates with
``with_sharding_constraint`` (``launch.sharding``), which refuses the
``Explicit`` axes ``jax.make_mesh`` defaults to on JAX 0.9.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_elastic_mesh(model_parallel: int = 16):
    """Derive a mesh from whatever devices exist right now (elastic restarts:
    pod count is discovered, not configured)."""
    n = jax.device_count()
    assert n % model_parallel == 0, (n, model_parallel)
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    """Axes that carry the batch (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
