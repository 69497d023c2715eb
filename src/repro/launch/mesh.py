"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Every mesh here has ``Auto`` axes: the step pins its intermediates with
``with_sharding_constraint`` (``launch.sharding``), which refuses the
``Explicit`` axes ``jax.make_mesh`` defaults to on JAX 0.9.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips).

    Axes: 'data' carries DP + FSDP; 'model' carries TP (+ MoE ff sharding);
    'pod' is pure DP across the slower inter-pod links (its gradient
    all-reduce is the natural place for int8 compression).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


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
