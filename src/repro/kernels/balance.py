"""Pallas TPU kernel: fused sequential balance scan (GraB's inner loop).

The hot loop of GraB in sketch mode is, per microbatch t:

    dot  = <s, z_t>            (reduction over k)
    eps  = +1 if dot <= 0 else -1
    s   += eps * z_t           (axpy over k)

XLA lowers a ``lax.scan`` over this to m separate reduce/select/add HLO ops,
each of which round-trips ``s`` through HBM. This kernel keeps ``s`` resident
in VMEM across the whole scan and fuses the three ops per step:

* grid = (m // TILE_M,), sequential on TPU; the running sum lives in a VMEM
  scratch buffer that persists across grid steps (initialized from ``s0`` at
  step 0, flushed to the output at the last step).
* each grid step processes TILE_M rows with an in-kernel ``fori_loop``
  (the recurrence is inherently sequential — the parallelism is inside each
  row's dot/axpy, which maps onto the VPU lanes).
* the feature dim ``k`` is padded to a multiple of 128 (lane width) by the
  ``ops`` wrapper and laid out lane-dense as ``[k // 128, 128]``: each row's
  dot/axpy fills whole (8, 128) vregs instead of one sublane of eight, and
  the VMEM blocks carry no sublane padding. VMEM budget bounds k at ~128K
  f32 entries (tile + sum + scratch ≈ 5 MB of the 16 MB VMEM), which is
  exactly the sketch-mode regime.
* the signs are one scalar per row, so they go to an SMEM output (the whole
  ``[m]`` vector, written by index): the TPU compiler refuses scalar stores
  to VMEM and 1-D VMEM blocks that are not 128-aligned.

Arithmetic is f32 throughout (sign decisions are not robust in bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 8
LANES = 128


def _balance_kernel(s0_ref, g_ref, signs_ref, s_out_ref, s_scratch):
    step = pl.program_id(0)
    tile = g_ref.shape[0]

    @pl.when(step == 0)
    def _init():
        s_scratch[...] = s0_ref[...]

    def body(r, _):
        g_row = g_ref[r]                              # [k // 128, 128]
        s = s_scratch[...]
        dot = jnp.sum(s * g_row)
        eps = jnp.where(dot <= 0.0, 1.0, -1.0).astype(jnp.float32)
        s_scratch[...] = s + eps * g_row
        signs_ref[step * tile + r] = eps
        return 0

    jax.lax.fori_loop(0, tile, body, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        s_out_ref[...] = s_scratch[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def balance_scan_pallas(s0: jax.Array, g: jax.Array, *, interpret: bool = False):
    """Run the fused balance scan. s0: [k] f32, g: [m, k] f32.

    Returns (signs [m] f32 in {-1,+1}, s_out [k] f32). The wrapper in
    ``repro.kernels.ops`` handles padding and dtype; call that instead.
    """
    m, k = g.shape
    assert m % TILE_M == 0 and k % LANES == 0, (m, k)
    rows = k // LANES
    signs, s_out = pl.pallas_call(
        _balance_kernel,
        grid=(m // TILE_M,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),           # s0
            pl.BlockSpec((TILE_M, rows, LANES), lambda i: (i, 0, 0)),  # g tile
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # signs
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),           # s_out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        interpret=interpret,
    )(s0.reshape(rows, LANES), g.reshape(m, rows, LANES))
    return signs, s_out.reshape(k)
