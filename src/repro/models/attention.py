"""Chunked (flash-style) attention in pure JAX.

Online-softmax over KV chunks with query-chunk outer loop (``lax.map``), so
peak logits memory is O(q_chunk * kv_chunk) instead of O(T * S) — mandatory
for the 4k-train and 32k-prefill cells (a naive [B,H,T,S] tensor at 32k is
~TBs). Differentiates through the scans (with remat this recomputes chunks
in the backward, flash-attention-style).

Note on causal overcompute: all KV chunks are visited for every Q chunk and
masked — ~2x the useful attention FLOPs for causal inputs (block-triangular
chunk enumeration would remove it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (whisper's 1500-frame encoder
    is not a power of two; chunks must tile the sequence exactly)."""
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_chunk: int = 512, kv_chunk: int = 1024,
                    q_offset: int = 0):
    """q: [B, T, H, hd]; k, v: [B, S, KV, hd] (GQA folded internally).

    Returns [B, T, H, hd] in q.dtype. Masking: key s visible to query t iff
    ``s <= q_offset + t`` (causal) and ``s > q_offset + t - window``.
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    R = H // KV
    scale = hd ** -0.5

    Tc = _pick_chunk(T, q_chunk)
    Sc = _pick_chunk(S, kv_chunk)
    nq, nk = T // Tc, S // Sc

    qf = (q.astype(jnp.float32) * scale).reshape(B, nq, Tc, KV, R, hd)
    kf = k.astype(jnp.float32).reshape(B, nk, Sc, KV, hd)
    vf = v.astype(jnp.float32).reshape(B, nk, Sc, KV, hd)

    # Sliding-window chunk skipping: query chunk qi only sees key positions
    # in (qi*Tc + q_offset - window, qi*Tc + Tc - 1 + q_offset]; that span
    # covers a *constant* number of KV chunks, so the inner scan iterates
    # only those instead of all nk (8x fewer attention FLOPs for mixtral's
    # 4k window at 32k prefill; ~3x for hymba). Causal-only inputs still
    # sweep every chunk (triangular trip counts don't fit a static scan) —
    # that ~2x shows up in `useful` and is a recorded future iteration.
    if window is not None and causal:
        nk_visit = min(nk, (window + Tc - 2) // Sc + 2)
    else:
        nk_visit = nk

    def one_q_chunk(qi):
        q_c = qf[:, qi]                                   # [B, Tc, KV, R, hd]
        qpos = q_offset + qi * Tc + jnp.arange(Tc)
        if nk_visit < nk:
            # last chunk any query in this q-chunk may attend to
            last_kj = jnp.minimum((qi * Tc + Tc - 1 + q_offset) // Sc, nk - 1)
            first_kj = jnp.maximum(last_kj - (nk_visit - 1), 0)
        else:
            first_kj = jnp.int32(0)

        # checkpoint the kv step: without it, scan-VJP residuals materialize
        # the full T x S logits (exactly what flash attention must avoid) —
        # with it, the backward recomputes each chunk's probs from q/k/v.
        @jax.checkpoint
        def kv_step(carry, j):
            m, l, acc = carry
            kj = first_kj + j
            k_c = jax.lax.dynamic_index_in_dim(kf, kj, 1, keepdims=False)
            v_c = jax.lax.dynamic_index_in_dim(vf, kj, 1, keepdims=False)
            logits = jnp.einsum("btkrh,bskh->bkrts", q_c, k_c)  # [B,KV,R,Tc,Sc]
            kpos = kj * Sc + jnp.arange(Sc)
            mask = kpos[None, :] <= qpos[:, None] if causal else \
                jnp.ones((Tc, Sc), bool)
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            new_m = jnp.maximum(m, logits.max(-1))
            corr = jnp.exp(m - new_m)
            p = jnp.exp(logits - new_m[..., None])
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bkrts,bskh->bkrth", p, v_c)
            return (new_m, l, acc), None

        m0 = jnp.full((B, KV, R, Tc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, R, Tc), jnp.float32)
        a0 = jnp.zeros((B, KV, R, Tc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk_visit))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # [B, KV, R, Tc, hd]
        return out

    outs = jax.lax.map(one_q_chunk, jnp.arange(nq))       # [nq, B, KV, R, Tc, hd]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, T, H, hd)
    return out.astype(q.dtype)
