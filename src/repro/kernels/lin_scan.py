"""Pallas TPU kernel: chunked gated-linear-attention (GLA) scan.

TPU-native replacement for the CUDA WKV kernels that RWKV6 ships with, also
used for Hymba's Mamba-style SSM heads (same diagonal linear recurrence —
see ``repro.kernels.ref.gla_scan_ref`` for the exact algebra).

Design (HBM -> VMEM blocking):

* grid = (B*H, T // CHUNK): the per-(batch, head) state matrix
  ``S: [DK, DV]`` lives in a VMEM scratch buffer and persists across the
  sequence-chunk grid dimension (TPU executes the minor grid dim
  sequentially, so chunk i+1 sees chunk i's state).
* each grid step streams one [CHUNK, DK] q/k/w tile and [CHUNK, DV] v tile
  into VMEM and runs the recurrence with an in-kernel ``fori_loop`` — the
  per-step outer product k_t^T v_t and the q_t @ S contraction are [DK, DV]
  VPU/MXU ops entirely in VMEM. Nothing round-trips HBM inside a chunk.
* DK, DV are head-sized (64/128): S is at most 128x128x4B = 64 KB — tiny.
  VMEM per step ~= (3*CHUNK*DK + 2*CHUNK*DV + DK*DV) * 4B; CHUNK=256 with
  DK=DV=128 is ~1.6 MB, far under the 16 MB budget.

Numerics: f32 state and accumulation (decay products underflow bf16 fast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 256


def _gla_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scratch, *,
                post_update: bool):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    u = u_ref[0, 0, :]  # [DK]

    def body(t, _):
        q_t = q_ref[0, t, :]          # [DK]
        k_t = k_ref[0, t, :]          # [DK]
        v_t = v_ref[0, t, :]          # [DV]
        w_t = w_ref[0, t, :]          # [DK]
        kv = k_t[:, None] * v_t[None, :]                    # [DK, DV]
        if post_update:               # Mamba convention: read post-state
            s_scratch[...] = w_t[:, None] * s_scratch[...] + kv
            o_t = (q_t[:, None] * s_scratch[...]).sum(axis=0)
        else:                         # RWKV convention: pre-state + u-bonus
            o_t = (q_t[:, None] * (s_scratch[...] + u[:, None] * kv)).sum(axis=0)
            s_scratch[...] = w_t[:, None] * s_scratch[...] + kv
        o_ref[0, t, :] = o_t
        return 0

    jax.lax.fori_loop(0, q_ref.shape[1], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "post_update"))
def gla_scan_pallas(q, k, v, w, u, *, interpret: bool = False,
                    post_update: bool = False):
    """q, k, w: [BH, T, DK]; v: [BH, T, DV]; u: [BH, DK] (zeros = no bonus).

    Returns o: [BH, T, DV] f32. The ``ops`` wrapper handles the
    [B, H, ...] <-> [BH, ...] reshapes, padding and u broadcasting.

    ``u`` enters the kernel as ``[BH, 1, DK]`` with ``(1, 1, DK)`` blocks:
    the TPU compiler refuses a ``(1, DK)`` block of a 2-D ``[BH, DK]`` array
    (the second-to-last block dim must be a multiple of 8 or the full dim).
    """
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % CHUNK == 0, (t, CHUNK)
    grid = (bh, t // CHUNK)
    o = pl.pallas_call(
        functools.partial(_gla_kernel, post_update=post_update),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, CHUNK, dk), lambda b, c: (b, c, 0)),  # q
            pl.BlockSpec((1, CHUNK, dk), lambda b, c: (b, c, 0)),  # k
            pl.BlockSpec((1, CHUNK, dv), lambda b, c: (b, c, 0)),  # v
            pl.BlockSpec((1, CHUNK, dk), lambda b, c: (b, c, 0)),  # w
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0)),      # u
        ],
        out_specs=pl.BlockSpec((1, CHUNK, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
      w.astype(jnp.float32), u.astype(jnp.float32).reshape(bh, 1, dk))
    return o
