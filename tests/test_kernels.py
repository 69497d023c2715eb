"""Pallas kernels vs pure-jnp oracles, swept over shapes and dtypes
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import (balance_scan, balance_scan_ref, coord_balance,
                               coord_balance_ref, gla_scan, gla_scan_ref)


@pytest.mark.parametrize("m,k", [(1, 8), (5, 37), (8, 128), (16, 128),
                                 (23, 300), (64, 1024)])
def test_balance_kernel_matches_ref(m, k):
    rng = np.random.default_rng(m * 1000 + k)
    g = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = balance_scan(s0, g, interpret=True)
    signs_r, s_r = balance_scan_ref(s0, g)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_balance_kernel_dtypes(dtype):
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.normal(size=(8, 64)), dtype)
    s0 = jnp.zeros((64,), dtype)
    signs_k, s_k = balance_scan(s0, g, interpret=True)
    signs_r, s_r = balance_scan_ref(s0, g)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 40), k=st.integers(1, 200), seed=st.integers(0, 2**16))
def test_balance_kernel_property(m, k, seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = balance_scan(s0, g, interpret=True)
    signs_r, s_r = balance_scan_ref(s0, g)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# coord_balance: the fused CD-GraB W-row coordinated pair-balance scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w,k", [(1, 8), (3, 96), (5, 37), (8, 128),
                                 (11, 130), (16, 300), (40, 1024)])
def test_coord_balance_kernel_matches_ref(w, k):
    """Edge shapes on purpose: k not a lane (128) multiple, W not a TILE_W
    multiple — the wrapper's zero-row/zero-column padding must be inert."""
    rng = np.random.default_rng(w * 1000 + k)
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = coord_balance(s0, zp, zc, interpret=True)
    signs_r, s_r = coord_balance_ref(s0, zp, zc)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(w=st.integers(1, 40), k=st.integers(1, 200), seed=st.integers(0, 2**16),
       prediffed=st.booleans())
def test_coord_balance_kernel_property(w, k, seed, prediffed):
    """Property parity vs the pure scan, both call forms: fused (z_prev,
    z_cur) and pre-diffed (z_cur=None) must agree with the reference."""
    rng = np.random.default_rng(seed)
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    if prediffed:
        signs_k, s_k = coord_balance(s0, zp - zc, None, interpret=True)
    else:
        signs_k, s_k = coord_balance(s0, zp, zc, interpret=True)
    signs_r, s_r = coord_balance_ref(s0, zp, zc)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=2e-5, atol=2e-5)


def test_coord_balance_zero_dot_ties():
    """Algorithm 5 resolves <s,z> == 0 to +1, and IEEE says -0.0 <= 0: both
    +0.0 and -0.0 dots must give sign +1 in kernel and reference alike."""
    k = 8
    # s0 = 0 -> every dot is +0.0; rows include -0.0 entries
    z = jnp.asarray(np.array([[-0.0, 1, -1, 0, 0, 0, 0, 0],
                              [0.0, -1, 1, -0.0, 0, 0, 0, 0]]), jnp.float32)
    s0 = jnp.zeros((k,), jnp.float32)
    signs_k, _ = coord_balance(s0, z, None, interpret=True)
    signs_r, _ = coord_balance_ref(s0, z)
    assert np.asarray(signs_k).tolist() == [1, 1]
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    # dot exactly -0.0: s = e_0, z_row0 = (-0.0, ...) -> <s, z> = -0.0 -> +1
    s1 = jnp.zeros((k,), jnp.float32).at[0].set(1.0)
    zneg = jnp.zeros((1, k), jnp.float32).at[0, 0].set(-0.0)
    signs_k, _ = coord_balance(s1, zneg, None, interpret=True)
    signs_r, _ = coord_balance_ref(s1, zneg)
    assert int(signs_k[0]) == 1 == int(signs_r[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coord_balance_dtype_promotion(dtype):
    """bf16 inputs are promoted to f32 before the scan; signs must match the
    reference run on the same promoted values exactly."""
    rng = np.random.default_rng(11)
    zp = jnp.asarray(rng.normal(size=(6, 64)), dtype)
    zc = jnp.asarray(rng.normal(size=(6, 64)), dtype)
    s0 = jnp.asarray(rng.normal(size=(64,)), dtype)
    signs_k, s_k = coord_balance(s0, zp, zc, interpret=True)
    signs_r, s_r = coord_balance_ref(s0.astype(jnp.float32),
                                     zp.astype(jnp.float32),
                                     zc.astype(jnp.float32))
    assert s_k.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)


def test_coord_balance_matches_coordinated_pair_signs_dispatch():
    """The core-layer dispatcher and the kernel agree on both impls. The
    dispatcher compiles the kernel for the chip, so on the CPU the test
    puts it in the TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.core.distributed import coordinated_pair_signs
    rng = np.random.default_rng(12)
    zs = jnp.asarray(rng.normal(size=(7, 50)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(50,)), jnp.float32)
    s_x, signs_x = coordinated_pair_signs(s0, zs, impl="xla")
    with pltpu.force_tpu_interpret_mode():
        s_p, signs_p = coordinated_pair_signs(s0, zs, impl="pallas")
    np.testing.assert_array_equal(np.asarray(signs_x), np.asarray(signs_p))
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,T,DK,DV", [
    (1, 1, 16, 8, 8), (2, 3, 50, 16, 24), (1, 2, 256, 32, 32),
    (2, 1, 300, 64, 16),
])
def test_gla_kernel_matches_ref(B, H, T, DK, DV):
    rng = np.random.default_rng(B + H + T)
    q = jnp.asarray(rng.normal(size=(B, H, T, DK)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, DK)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, DV)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.4, 1.0, size=(B, H, T, DK)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, DK)), jnp.float32)
    for bonus in (u, None):
        o_k = gla_scan(q, k, v, w, bonus, interpret=True)
        o_r = gla_scan_ref(q, k, v, w, bonus)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=3e-4, atol=3e-4)


def test_gla_kernel_bf16_inputs():
    rng = np.random.default_rng(3)
    shape = (1, 2, 64, 16)
    q, k, w = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))
    w = jnp.abs(w) * 0.5
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 8)), jnp.bfloat16)
    o_k = gla_scan(q, k, v, w, None, interpret=True)
    o_r = gla_scan_ref(q, k, v, w, None)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-2, atol=2e-2)


def test_gla_ref_final_state_consistency():
    """Running the scan in two halves with the carried state equals one go."""
    rng = np.random.default_rng(4)
    B, H, T, DK, DV = 1, 1, 32, 8, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, DK)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, DK)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, DV)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.0, size=(B, H, T, DK)), jnp.float32)
    o_full, S_full = gla_scan_ref(q, k, v, w, return_state=True)
    o1, S1 = gla_scan_ref(q[:, :, :16], k[:, :, :16], v[:, :, :16],
                          w[:, :, :16], return_state=True)
    # continue from S1 by unrolling manually
    S = S1
    outs = []
    for t in range(16, 32):
        kv = k[0, 0, t][:, None] * v[0, 0, t][None, :]
        outs.append(q[0, 0, t] @ (S[0, 0] + 0 * kv))
        S = S.at[0, 0].set(w[0, 0, t][:, None] * S[0, 0] + kv)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# coord_balance chunked-k path + VMEM-budget guard (k > 64K stays correct)
# ---------------------------------------------------------------------------

def test_select_coord_impl_vmem_guard():
    """The dispatcher picks by estimated VMEM footprint: plain full-k tiles
    while they fit, the chunked-k kernel past the budget, and the pure-jnp
    oracle when even the chunked running sum would not fit."""
    from repro.kernels.ops import select_coord_impl
    from repro.kernels.coord_balance import CHUNK_K

    assert select_coord_impl(8, 1024) == ("plain", None)
    impl, ck = select_coord_impl(8, 100_000)       # ROADMAP's k > 64K case
    assert impl == "chunked" and ck == CHUNK_K
    assert select_coord_impl(8, 100_000, vmem_budget=1024) == ("ref", None)
    # an explicit chunk_k forces the chunked path even at small k
    impl, ck = select_coord_impl(4, 256, chunk_k=128)
    assert impl == "chunked" and ck == 128


@pytest.mark.parametrize("w,k,ck", [
    (3, 129, 128),      # k just above the chunk boundary (pads to 2 chunks)
    (1, 130, 128),      # single row still needs the ghost flush pass
    (5, 384, 128),      # k an exact chunk multiple
    (8, 900, 256),      # W a TILE_W multiple, ragged final chunk
])
def test_coord_balance_chunked_matches_ref(w, k, ck):
    rng = np.random.default_rng(w * 1000 + k)
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = coord_balance(s0, zp, zc, interpret=True, chunk_k=ck)
    signs_r, s_r = coord_balance_ref(s0, zp, zc)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=2e-5, atol=2e-5)


def test_coord_balance_chunked_equals_plain_kernel():
    """Same inputs through both kernel variants: the signs must agree and
    the sums match to reduction-reorder tolerance."""
    rng = np.random.default_rng(77)
    w, k = 6, 512
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_p, s_p = coord_balance(s0, zp, zc, interpret=True)
    signs_c, s_c = coord_balance(s0, zp, zc, interpret=True, chunk_k=128)
    np.testing.assert_array_equal(np.asarray(signs_p), np.asarray(signs_c))
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_c),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_coord_balance_past_64k_via_guard():
    """k > 64K end-to-end through the default guard (no forced chunk_k):
    the chunked kernel is selected and stays correct."""
    from repro.kernels.ops import select_coord_impl

    w, k = 4, 66_000
    assert select_coord_impl(w, k)[0] == "chunked"
    rng = np.random.default_rng(13)
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = coord_balance(s0, zp, zc, interpret=True)
    signs_r, s_r = coord_balance_ref(s0, zp, zc)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-4)


def test_coord_balance_ref_fallback_past_budget():
    """Past even the chunked budget the wrapper falls back to the oracle —
    correct at any k, same int32 sign contract."""
    rng = np.random.default_rng(14)
    w, k = 3, 1024
    zp = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    zc = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    signs_k, s_k = coord_balance(s0, zp, zc, vmem_budget=512)
    assert signs_k.dtype == jnp.int32
    signs_r, s_r = coord_balance_ref(s0, zp, zc)
    np.testing.assert_array_equal(np.asarray(signs_k), np.asarray(signs_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)
