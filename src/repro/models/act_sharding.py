"""Activation-sharding constraints (MaxText-style).

Left to itself, XLA's sharding propagation sometimes picks batch-replicated /
d-model-sharded layouts for the layer-scan carry (observed on the 256-chip
dry-run, since deleted: 4.6 GiB replicated logits and 10 TB of spurious
all-reduces). These hooks pin the canonical layout:

    activations [batch, seq, d]  -> P(data_axes, None, None)
    logits      [batch, seq, V]  -> P(data_axes, None, 'model')

The hooks are global + optional: model code calls :func:`constrain` which is
a no-op unless :func:`set_activation_specs` installed specs for the current
mesh. No training path installs them today (``tests/test_sharding.py``
does); single-device runs are untouched.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_SPECS: Optional[dict] = None


def set_activation_specs(data_axes, model_axis: str = "model",
                         model_size: int = 0):
    """Install constraint specs. data_axes: tuple like ('data',) or
    ('pod','data'). Pass None to clear."""
    global _SPECS
    if data_axes is None:
        _SPECS = None
        return
    _SPECS = {
        "btd": P(data_axes, None, None),
        "logits": P(data_axes, None, model_axis),
        "bd": P(data_axes, None),
        # attention: query heads shard on the model axis when they divide it
        # (see ModelConfig.q_head_pad); K/V heads replicate (GQA TP > KV).
        "heads": P(data_axes, None, model_axis, None),
        "kv": P(data_axes, None, None, None),
        # GLA/SSM operands [B, H, T, dk]: shard heads on 'model'
        "bhtd": P(data_axes, model_axis, None, None),
    }
    _SPECS["_model_size"] = model_size


def clear_activation_specs():
    set_activation_specs(None)


def constrain(x, kind: str = "btd"):
    if _SPECS is None or kind not in _SPECS:
        return x
    spec = _SPECS[kind]
    if x.ndim != len(spec):
        return x
    if kind in ("heads", "bhtd"):
        n = _SPECS.get("_model_size") or 0
        dim = 2 if kind == "heads" else 1
        if n == 0 or x.shape[dim] % n:
            return x             # heads don't divide TP: leave to XLA
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no mesh context / incompatible shape: stay unconstrained
