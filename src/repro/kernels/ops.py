"""Jit'd public wrappers around the Pallas kernels.

These handle padding/reshaping/dtype so callers (the GraB train step, the
RWKV6/Hymba blocks) can pass natural shapes. Kernels compile for the TPU
unless the caller passes ``interpret=True``, which runs the kernel body in
the Pallas interpreter (how the CPU tests exercise them) and is refused on a
TPU backend, so a chip run can never time the interpreter by accident.
"""
from __future__ import annotations

import logging
import os

import jax
import jax.numpy as jnp

from repro.kernels.balance import TILE_M, balance_scan_pallas
from repro.kernels.coord_balance import (CHUNK_K, TILE_W, VMEM_LIMIT_BYTES,
                                         chunked_vmem_bytes,
                                         coord_balance_chunked_pallas,
                                         coord_balance_pallas,
                                         plain_vmem_bytes)
from repro.kernels.lin_scan import CHUNK, gla_scan_pallas
from repro.kernels import ref


_log = logging.getLogger(__name__)
_ref_fallback_logged = False


def _check_interpret(interpret: bool) -> None:
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("interpret=True on a TPU backend: the kernels run "
                         "compiled on the chip; interpret mode is for CPU "
                         "tests only")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def balance_scan(s0: jax.Array, g: jax.Array, interpret: bool = False):
    """Fused GraB balance scan. s0: [k], g: [m, k] -> (signs [m] int32, s [k]).

    Pads m to a TILE_M multiple with zero rows (zero rows get sign +1 and do
    not perturb the sum) and k to a lane multiple.
    """
    _check_interpret(interpret)
    m, k = g.shape
    mp, kp = _round_up(max(m, TILE_M), TILE_M), _round_up(max(k, 128), 128)
    gp = jnp.zeros((mp, kp), jnp.float32).at[:m, :k].set(g.astype(jnp.float32))
    sp = jnp.zeros((kp,), jnp.float32).at[:k].set(s0.astype(jnp.float32))
    signs, s_out = balance_scan_pallas(sp, gp, interpret=interpret)
    return signs[:m].astype(jnp.int32), s_out[:k]


def _coord_vmem_budget(vmem_budget: int | None) -> int:
    if vmem_budget is not None:
        return vmem_budget
    env = os.environ.get("REPRO_COORD_VMEM_BUDGET")
    if env is not None:
        try:
            return int(env)
        except ValueError as e:
            raise ValueError(
                f"REPRO_COORD_VMEM_BUDGET={env!r} is not an integer byte "
                f"count") from e
    return VMEM_LIMIT_BYTES


def select_coord_impl(w: int, k: int, chunk_k: int | None = None,
                      vmem_budget: int | None = None):
    """VMEM-budget guard for :func:`coord_balance`: pick the kernel variant
    whose footprint fits.

    Returns ("plain", None) for the full-k tiled kernel, ("chunked", ck) for
    the streamed chunked-k kernel, or ("ref", None) when even the chunked
    form's running sum would not fit — the caller falls back to the pure-jnp
    oracle so the scan stays correct at any k, and says so in one log line
    the first time a process takes that fallback. An explicit ``chunk_k``
    forces the chunked path unconditionally (tests exercise the chunk
    boundary at small k; the budget only steers the automatic choice).
    """
    kp = _round_up(max(k, 128), 128)
    if chunk_k is not None:
        return "chunked", _round_up(min(chunk_k, kp), 128)
    budget = _coord_vmem_budget(vmem_budget)
    wp = _round_up(max(w, TILE_W), TILE_W)
    if plain_vmem_bytes(wp, kp) <= budget:
        return "plain", None
    ck = _round_up(min(CHUNK_K, kp), 128)
    if chunked_vmem_bytes(_round_up(kp, ck), ck) <= budget:
        return "chunked", ck
    return "ref", None


def coord_balance(s0: jax.Array, z_prev: jax.Array, z_cur: jax.Array | None = None,
                  interpret: bool = False, *, chunk_k: int | None = None,
                  vmem_budget: int | None = None):
    """Fused CD-GraB coordinated pair-balance scan (the W-row sequential
    inner loop of ``core.distributed.coordinated_pair_signs``).

    s0: [k]; z_prev, z_cur: [W, k] — balances the rows of ``z_prev - z_cur``
    in worker-index order. Pass ``z_cur=None`` when the differences are
    already formed: that degenerate case IS the plain balance scan, so it
    delegates to :func:`balance_scan` (same contract, no zero-matrix
    streaming) and only the two-operand form runs the fused-subtract kernel.
    Returns (signs [W] int32 in {-1,+1}, s_out [k] f32).

    Pads W to a TILE_W multiple with zero rows (dot 0 -> sign +1, the sum is
    unperturbed) and k to the 128-lane multiple; bf16 inputs are promoted to
    f32 before the scan (sign decisions are not robust in bf16).

    VMEM-budget guard (:func:`select_coord_impl`): when the full-k tiles
    would not fit (k > ~60K at the default budget), the scan switches to the
    chunked-k kernel (``coord_balance_chunked_pallas`` — only the running
    sum stays VMEM-resident, rows stream chunk_k lanes at a time), and past
    even that budget it falls back to the pure-jnp oracle, so results stay
    correct at any k. ``chunk_k`` forces the chunked path; ``vmem_budget``
    (or ``REPRO_COORD_VMEM_BUDGET``) overrides the byte budget.
    """
    if z_cur is None:
        return balance_scan(s0, z_prev, interpret=interpret)
    _check_interpret(interpret)
    w, k = z_prev.shape
    impl, ck = select_coord_impl(w, k, chunk_k=chunk_k,
                                 vmem_budget=vmem_budget)
    if impl == "ref":
        global _ref_fallback_logged
        if not _ref_fallback_logged:
            _ref_fallback_logged = True
            _log.warning("coord_balance: W=%d, k=%d exceeds the VMEM budget "
                         "of both kernels; running the jnp reference scan",
                         w, k)
        signs, s_out = ref.coord_balance_ref(s0, z_prev, z_cur)
        return signs.astype(jnp.int32), s_out
    if impl == "chunked":
        kp = _round_up(max(k, ck), ck)
        zp = jnp.zeros((w, kp), jnp.float32).at[:, :k].set(
            z_prev.astype(jnp.float32))
        zc = jnp.zeros((w, kp), jnp.float32).at[:, :k].set(
            z_cur.astype(jnp.float32))
        sp = jnp.zeros((kp,), jnp.float32).at[:k].set(s0.astype(jnp.float32))
        signs, s_out = coord_balance_chunked_pallas(sp, zp, zc, chunk_k=ck,
                                                    interpret=interpret)
        return signs.astype(jnp.int32), s_out[:k]
    wp, kp = _round_up(max(w, TILE_W), TILE_W), _round_up(max(k, 128), 128)
    zp = jnp.zeros((wp, kp), jnp.float32).at[:w, :k].set(
        z_prev.astype(jnp.float32))
    zc = jnp.zeros((wp, kp), jnp.float32).at[:w, :k].set(
        z_cur.astype(jnp.float32))
    sp = jnp.zeros((kp,), jnp.float32).at[:k].set(s0.astype(jnp.float32))
    signs, s_out = coord_balance_pallas(sp, zp, zc, interpret=interpret)
    return signs[:w].astype(jnp.int32), s_out[:k]


def gla_scan(q, k, v, w, u=None, interpret: bool = False,
             post_update: bool = False):
    """Gated linear attention. q,k,w: [B,H,T,DK]; v: [B,H,T,DV]; u: [H,DK]|None.

    Pads T to a CHUNK multiple (padded steps have k=0, w=1 so the state is
    unchanged and their outputs are dropped). Returns o: [B, H, T, DV] f32.
    """
    _check_interpret(interpret)
    B, H, T, DK = q.shape
    DV = v.shape[-1]
    Tp = _round_up(T, CHUNK)
    pad = Tp - T

    def pad_t(x, fill):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)),
                       constant_values=fill) if pad else x

    qp, kp_, vp = pad_t(q, 0.0), pad_t(k, 0.0), pad_t(v, 0.0)
    wp = pad_t(w, 1.0)
    u_full = jnp.zeros((H, DK), jnp.float32) if u is None else u.astype(jnp.float32)
    u_bh = jnp.broadcast_to(u_full[None], (B, H, DK)).reshape(B * H, DK)

    def r(x):
        return x.reshape(B * H, Tp, x.shape[-1])

    o = gla_scan_pallas(r(qp), r(kp_), r(vp), r(wp), u_bh, interpret=interpret,
                        post_update=post_update)
    return o.reshape(B, H, Tp, DV)[:, :, :T, :]


# Re-export oracles for test convenience.
balance_scan_ref = ref.balance_scan_ref
coord_balance_ref = ref.coord_balance_ref
gla_scan_ref = ref.gla_scan_ref


def gla(q, k, v, w, u=None, return_state: bool = False,
        post_update: bool = False):
    """Implementation dispatcher used by the model blocks.

    * ``pallas`` — the VMEM-resident kernel (default on real TPU).
    * ``xla``    — pure-jnp ``lax.scan`` (default off-TPU; a pallas_call
      inside a pjit would be opaque to the SPMD partitioner, so sharded
      lowering paths use plain XLA).

    Override with REPRO_GLA_IMPL=pallas|xla. ``return_state`` (prefill
    cache priming) always takes the XLA path.
    """
    impl = os.environ.get("REPRO_GLA_IMPL")
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas" and not return_state:
        return gla_scan(q, k, v, w, u, post_update=post_update)
    return ref.gla_scan_ref(q, k, v, w, u, return_state=return_state,
                            post_update=post_update)
