"""Weight-only int8 quantization for serving (beyond-paper §Perf feature).

Decode is weights-read-bound: every parameter crosses HBM once per token.
Storing big weights as int8 + per-output-channel f32 scales halves that
traffic and the resident footprint; dequantization happens per layer inside
the decode scan (a [1-layer] bf16 transient, never the full stack).

A quantized leaf is the dict ``{"q": int8[...], "s": f32[out_dim]}`` in the
same tree position as the original array — the scan slices it per layer like
any other stacked weight, and :func:`maybe_dequant` restores plain arrays at
the top of the block body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MIN_QUANT_SIZE = 1 << 20     # leaves smaller than 1M elements stay bf16


def _is_qleaf(x) -> bool:
    return isinstance(x, dict) and set(x.keys()) == {"q", "s"}


def quantize_leaf(w: jax.Array):
    """Per-output-channel (last axis) symmetric int8; >=3D (stacked /
    expert) weights keep their leading axis in the scale."""
    w32 = w.astype(jnp.float32)
    red = tuple(range(w.ndim - 1)) if w.ndim <= 2 else tuple(range(1, w.ndim - 1))
    amax = jnp.max(jnp.abs(w32), axis=red, keepdims=True)
    s = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": jnp.squeeze(s, axis=red)}


def dequantize_leaf(d, dtype=jnp.bfloat16):
    q, s = d["q"], d["s"]
    if s.ndim == 2:          # [lead, out] -> broadcast over middle dims
        s = s.reshape(s.shape[0], *([1] * (q.ndim - 2)), s.shape[-1])
    return (q.astype(jnp.float32) * s).astype(dtype)


def _eligible(path, leaf, min_size) -> bool:
    """Big matmul weights only: stacked block weights are 3D+ ([L, in, out]);
    unstacked ones (lm_head) are 2D. Embeddings are gathered, not matmul'd —
    excluded. 1D-per-layer params (norms, mus) stay bf16."""
    names = [getattr(k, "key", getattr(k, "name", k)) for k in path]
    joined = "/".join(str(n) for n in names)
    if "embed" in joined or leaf.size < min_size:
        return False
    stacked = any(str(n).endswith("blocks") for n in names)
    return leaf.ndim >= (3 if stacked else 2)


def quantize_params(params, min_size: int = MIN_QUANT_SIZE):
    def one(path, leaf):
        return quantize_leaf(leaf) if _eligible(path, leaf, min_size) else leaf
    return jax.tree_util.tree_map_with_path(one, params)


def maybe_dequant(tree, dtype=jnp.bfloat16):
    """Restore plain arrays from any quantized leaves in ``tree``."""
    return jax.tree.map(
        lambda x: dequantize_leaf(x, dtype) if _is_qleaf(x) else x,
        tree, is_leaf=_is_qleaf)
