"""Pallas TPU kernel: fused CD-GraB coordinated pair-balance scan.

The sketch-mode CD-GraB inner loop (``core.distributed.coordinated_pair_signs``)
is, per pair timestep, a *W-row* sequential scan against the one shared
running sum:

    for w in range(W):                    # worker-index order — the coordination
        z_w  = zprev_w - zcur_w           # pair difference (mean-free)
        dot  = <s, z_w>                   # reduction over k
        eps  = +1 if dot <= 0 else -1
        s   += eps * z_w                  # axpy over k

XLA lowers the ``lax.scan`` form to W separate subtract/reduce/select/add HLO
ops, each round-tripping ``s`` through HBM. This kernel is the same shape as
``kernels/balance.py`` but fuses one step further: the pair-difference
subtraction happens in registers, so the [W, k] difference matrix is never
materialized in HBM, and the running sum stays resident in VMEM across all W
dependent steps:

* grid = (W // TILE_W,), sequential on TPU; the running sum lives in a VMEM
  scratch buffer persisting across grid steps (initialized from ``s0`` at
  step 0, flushed to the output at the last step).
* each grid step consumes TILE_W rows of the stashed (``z_prev``) and current
  (``z_cur``) sketched gradients with an in-kernel ``fori_loop`` — the
  recurrence is inherently sequential; the parallelism is inside each row's
  subtract/dot/axpy, which maps onto the VPU lanes.
* the ``ops.coord_balance`` wrapper pads W to a TILE_W multiple with zero
  rows (dot 0 -> sign +1, sum unperturbed) and k to the 128-lane multiple,
  and promotes bf16 inputs to f32 — sign decisions are not robust in bf16.
  Inside, each k-vector is laid out lane-dense as ``[k // 128, 128]`` (the
  same layout as ``kernels/balance.py``), and the signs go to an SMEM output
  written by index — the TPU compiler refuses scalar stores to VMEM.
  With ``z_cur=None`` (differences already formed) the fusion degenerates to
  the plain balance scan and the wrapper delegates to ``ops.balance_scan``;
  this kernel only runs the genuine two-operand form.

Only the deterministic (Algorithm 5) balancer is fused; the Alweiss balancer
needs a per-row PRNG split and stays on the XLA scan. Likewise the SPMD mesh
path (``mesh_pair_signs``) keeps the XLA scan: a pallas_call inside pjit is
opaque to the partitioner (see ``core.distributed`` for the dispatch rules).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.balance import LANES

TILE_W = 8
# Chunked-k path: stream the sketched rows through VMEM CHUNK_K lanes at a
# time once the full-k tiles of the plain kernel would blow the VMEM budget
# (~k > 60K at TILE_W=8 — the ROADMAP's unexercised k > 64K case).
CHUNK_K = 65_536
# Conservative usable-VMEM budget (of ~16 MiB/core on v5e): leave headroom
# for pallas pipeline buffers and whatever else the step has resident.
VMEM_LIMIT_BYTES = 8 * 2**20


def plain_vmem_bytes(w_padded: int, k_padded: int) -> int:
    """VMEM footprint estimate of :func:`coord_balance_pallas`: the s0 block,
    the running-sum scratch, the s_out block (each [k // 128, 128],
    revisited — single buffered) and the double-buffered
    [TILE_W, k // 128, 128] z_prev/z_cur tiles."""
    del w_padded  # signs live in SMEM
    return 4 * k_padded * (3 + 2 * 2 * TILE_W)


def chunked_vmem_bytes(k_padded: int, chunk_k: int) -> int:
    """VMEM footprint estimate of :func:`coord_balance_chunked_pallas`: the
    full-k running-sum scratch plus six double-buffered chunk blocks
    (s0, s_out, and the two z operands each streamed twice — current row and
    deferred previous row)."""
    return 4 * (k_padded + 2 * 6 * chunk_k)


def _coord_balance_kernel(s0_ref, zp_ref, zc_ref, signs_ref, s_out_ref,
                          s_scratch):
    step = pl.program_id(0)
    tile = zp_ref.shape[0]

    @pl.when(step == 0)
    def _init():
        s_scratch[...] = s0_ref[...]

    def body(r, _):
        z_row = zp_ref[r] - zc_ref[r]                 # [k // 128, 128]
        s = s_scratch[...]
        dot = jnp.sum(s * z_row)
        eps = jnp.where(dot <= 0.0, 1.0, -1.0).astype(jnp.float32)
        s_scratch[...] = s + eps * z_row
        signs_ref[step * tile + r] = eps
        return 0

    jax.lax.fori_loop(0, tile, body, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        s_out_ref[...] = s_scratch[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def coord_balance_pallas(s0: jax.Array, z_prev: jax.Array, z_cur: jax.Array,
                         *, interpret: bool = False):
    """Run the fused coordinated pair-balance scan.

    s0: [k] f32; z_prev, z_cur: [W, k] f32 (stashed / current sketches; the
    balanced vectors are the rows of ``z_prev - z_cur``).
    Returns (signs [W] f32 in {-1,+1}, s_out [k] f32). The wrapper in
    ``repro.kernels.ops`` handles padding and dtype; call that instead.
    """
    w, k = z_prev.shape
    assert z_cur.shape == (w, k), (z_prev.shape, z_cur.shape)
    assert w % TILE_W == 0 and k % LANES == 0, (w, k)
    rows = k // LANES
    tile = pl.BlockSpec((TILE_W, rows, LANES), lambda i: (i, 0, 0))
    signs, s_out = pl.pallas_call(
        _coord_balance_kernel,
        grid=(w // TILE_W,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),   # s0 (revisited)
            tile,                                            # z_prev tile
            tile,                                            # z_cur tile
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # signs
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),   # s_out (revisited)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w,), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        interpret=interpret,
    )(s0.reshape(rows, LANES), z_prev.reshape(w, rows, LANES),
      z_cur.reshape(w, rows, LANES))
    return signs, s_out.reshape(k)


def _coord_balance_chunked_kernel(s0_ref, zp_ref, zc_ref, zp_prev_ref,
                                  zc_prev_ref, signs_ref, s_out_ref,
                                  s_scratch, acc_ref, eps_ref):
    w = pl.program_id(0)
    c = pl.program_id(1)
    n_rows = pl.num_programs(0) - 1          # last grid row is the flush pass
    n_chunks = pl.num_programs(1)
    rc = s0_ref.shape[0]                     # sublane rows per chunk
    sl = pl.ds(pl.multiple_of(c * rc, rc), rc)

    @pl.when(w == 0)
    def _init():
        s_scratch[sl, :] = s0_ref[...]

    # Row w-1's axpy is deferred to row w's sweep: when its sign was decided
    # (after chunk C-1) the earlier chunks of z_{w-1} were no longer
    # resident, so each (w, c) step first folds eps_{w-1} * z_{w-1,c} into
    # the running-sum chunk it is about to read. The ghost row w == n_rows
    # exists purely to apply the last row's pending axpy and flush s.
    @pl.when(w > 0)
    def _deferred_axpy():
        z_prev_row = zp_prev_ref[0] - zc_prev_ref[0]
        s_scratch[sl, :] = s_scratch[sl, :] + eps_ref[0] * z_prev_row

    @pl.when(w < n_rows)
    def _dot_and_sign():
        @pl.when(c == 0)
        def _reset():
            acc_ref[0] = 0.0

        z_row = zp_ref[0] - zc_ref[0]
        acc_ref[0] += jnp.sum(s_scratch[sl, :] * z_row)

        @pl.when(c == n_chunks - 1)
        def _sign():
            eps = jnp.where(acc_ref[0] <= 0.0, 1.0, -1.0).astype(jnp.float32)
            signs_ref[w] = eps
            eps_ref[0] = eps

    @pl.when(w == n_rows)
    def _flush():
        s_out_ref[...] = s_scratch[sl, :]


@functools.partial(jax.jit, static_argnames=("chunk_k", "interpret"))
def coord_balance_chunked_pallas(s0: jax.Array, z_prev: jax.Array,
                                 z_cur: jax.Array, *, chunk_k: int,
                                 interpret: bool = False):
    """Chunked-k fused coordinated pair-balance scan.

    Same contract as :func:`coord_balance_pallas`, for k too large to hold
    TILE_W full-k z tiles in VMEM: only the [k] running sum stays resident
    (a VMEM scratch addressed per chunk); the z rows stream through
    ``[1, chunk_k // 128, 128]`` blocks on a (W+1, k // chunk_k) grid, one
    worker row per outer step. Per row the chunk sweep accumulates the
    balance dot in SMEM; the sign lands after the last chunk, so the row's
    axpy is *deferred* to the next row's sweep (the z operands are streamed
    twice — current row and previous row — which is what keeps every chunk
    touched exactly when it is resident). The trailing ghost row applies the
    final pending axpy and flushes the sum.

    The z operands are passed as ``[W, k // 128, 128]`` so a row chunk is a
    block the TPU compiler accepts: a ``(1, chunk_k)`` block of a 2-D
    ``[W, k]`` array is refused (its second-to-last dim is neither a
    multiple of 8 nor the full W). On the chip ``chunk_k`` must therefore be
    a multiple of 1024 (8 sublanes of 128 lanes); interpret mode takes any
    multiple of 128.

    The dot is accumulated chunk-by-chunk, so at near-ties its f32 rounding
    can differ from the single full-k reduction of the plain kernel — same
    caveat as any blocked reduction.
    """
    w, k = z_prev.shape
    assert z_cur.shape == (w, k), (z_prev.shape, z_cur.shape)
    assert chunk_k % LANES == 0 and k % chunk_k == 0, (k, chunk_k)
    n_chunks = k // chunk_k
    rows, rc = k // LANES, chunk_k // LANES
    row = lambda i, c: (jnp.minimum(i, w - 1), c, 0)      # ghost reads row W-1
    prev_row = lambda i, c: (jnp.maximum(i - 1, 0), c, 0)  # deferred-axpy rows
    # s0 is read in row 0 only and s_out written in the ghost row only; in
    # between, each holds one block index, so s0 is not fetched again and
    # s_out is written back once per chunk, after its final value (an output
    # block revisited after another index was visited would be written back
    # with whatever the buffer held)
    s0_chunk = lambda i, c: (jnp.where(i == 0, c, n_chunks - 1), 0)
    s_out_chunk = lambda i, c: (jnp.where(i == w, c, 0), 0)
    zp3 = z_prev.reshape(w, rows, LANES)
    zc3 = z_cur.reshape(w, rows, LANES)
    signs, s_out = pl.pallas_call(
        _coord_balance_chunked_kernel,
        grid=(w + 1, n_chunks),
        in_specs=[
            pl.BlockSpec((rc, LANES), s0_chunk),            # s0 chunk
            pl.BlockSpec((1, rc, LANES), row),              # z_prev row
            pl.BlockSpec((1, rc, LANES), row),              # z_cur row
            pl.BlockSpec((1, rc, LANES), prev_row),         # z_prev row-1
            pl.BlockSpec((1, rc, LANES), prev_row),         # z_cur row-1
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # signs
            pl.BlockSpec((rc, LANES), s_out_chunk),         # s_out chunk
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w,), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.SMEM((1,), jnp.float32),
                        pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(s0.reshape(rows, LANES), zp3, zc3, zp3, zc3)
    return signs, s_out.reshape(k)
