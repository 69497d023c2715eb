"""GraB state-machine tests (Algorithm 4 semantics)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.balance import balance_sequence
from repro.core.grab import (GrabConfig, grab_epoch_end, grab_running_sum,
                             grab_step, init_grab_state, make_sketch)
from repro.core.herding import reorder_from_signs


def _tree(vec):
    return {"w": jnp.asarray(vec[:12].reshape(3, 4)), "b": jnp.asarray(vec[12:])}


def test_grab_step_centers_with_stale_mean_and_accumulates():
    cfg = GrabConfig()
    rng = np.random.default_rng(0)
    g1 = rng.normal(size=16).astype(np.float32)
    st = init_grab_state(_tree(g1), cfg)
    st, eps1 = grab_step(st, _tree(g1), n_per_epoch=2, cfg=cfg)
    # epoch 1: stale mean is zero, so s == eps1 * g1
    flat_s = np.concatenate([np.asarray(st.s["w"]).ravel(), np.asarray(st.s["b"])])
    np.testing.assert_allclose(flat_s, int(eps1) * g1, rtol=1e-5)
    g2 = rng.normal(size=16).astype(np.float32)
    st, _ = grab_step(st, _tree(g2), n_per_epoch=2, cfg=cfg)
    st = grab_epoch_end(st, cfg)
    # m_prev now holds mean of the epoch's gradients; s reset
    flat_m = np.concatenate([np.asarray(st.m_prev["w"]).ravel(),
                             np.asarray(st.m_prev["b"])])
    np.testing.assert_allclose(flat_m, (g1 + g2) / 2, rtol=1e-5)
    assert float(jnp.abs(st.s["w"]).max()) == 0.0


def test_grab_matches_balance_sequence_when_mean_known():
    """With m_prev = true mean, a GraB epoch's signs equal Alg.5 balancing of
    the centered vectors, and the host reorder equals Alg.3."""
    cfg = GrabConfig()
    rng = np.random.default_rng(1)
    zs = rng.normal(size=(16, 16)).astype(np.float32)
    mean = zs.mean(0)

    st = init_grab_state(_tree(zs[0]), cfg)
    st = st._replace(m_prev=_tree(mean))
    eps_grab = []
    for t in range(16):
        st, e = grab_step(st, _tree(zs[t]), n_per_epoch=16, cfg=cfg)
        eps_grab.append(int(e))

    signs_ref, _ = balance_sequence(jnp.asarray(zs - mean))
    assert eps_grab == [int(x) for x in np.asarray(signs_ref)]

    sigma = reorder_from_signs(np.arange(16), np.array(eps_grab))
    assert sorted(sigma.tolist()) == list(range(16))


def test_sketch_mode_uses_k_dims():
    cfg = GrabConfig(sketch_dim=6)
    tmpl = _tree(np.zeros(16, np.float32))
    sk = make_sketch(tmpl, 6, seed=0)
    st = init_grab_state(tmpl, cfg)
    assert st.s.shape == (6,)
    g = _tree(np.random.default_rng(0).normal(size=16).astype(np.float32))
    st, eps = grab_step(st, g, n_per_epoch=4, cfg=cfg, sketch=sk)
    assert int(eps) in (-1, 1)
    assert float(jnp.abs(st.s).sum()) > 0


def test_grab_step_is_jittable():
    cfg = GrabConfig()
    tmpl = _tree(np.zeros(16, np.float32))
    st = init_grab_state(tmpl, cfg)
    f = jax.jit(lambda s, g: grab_step(s, g, 4, cfg))
    g = _tree(np.ones(16, np.float32))
    st, eps = f(st, g)
    st, eps = f(st, g)
    assert int(st.t) == 2


def test_alweiss_grab_runs():
    cfg = GrabConfig(balancer="alweiss", alweiss_c=10.0)
    tmpl = _tree(np.zeros(16, np.float32))
    st = init_grab_state(tmpl, cfg)
    g = _tree(np.random.default_rng(2).normal(size=16).astype(np.float32))
    st, eps = grab_step(st, g, 4, cfg)
    assert int(eps) in (-1, 1)


@pytest.mark.parametrize("mode", ["sketch", "pair", "pair-sketch"])
def test_sketch_and_pair_modes_keep_the_centered_sum(mode):
    """Sketch mode and pair balancing keep the centered running sum: after
    a few steps (a nonzero stale mean in sketch mode) ``s`` is bit for bit
    the centered recurrence ``s += eps z`` with ``eps`` from ``<s, z>``, ``z``
    the sketched centered gradient or the pair's difference, and ``e``
    stays 0, so ``grab_running_sum`` is ``s`` itself."""
    from repro.utils.tree import tree_dot

    cfg = GrabConfig(sketch_dim=6 if "sketch" in mode else 0,
                     pair_balance="pair" in mode)
    rng = np.random.default_rng(4)
    tmpl = _tree(np.zeros(16, np.float32))
    sk = make_sketch(tmpl, 6, seed=0) if cfg.sketch_dim else None
    st = init_grab_state(tmpl, cfg)
    if not cfg.pair_balance:
        st = st._replace(m_prev=_tree(rng.normal(size=16).astype(np.float32)))
    ref_s, stash = st.s, None
    for t in range(6):
        g = _tree(rng.normal(size=16).astype(np.float32))
        st, eps = grab_step(st, g, n_per_epoch=6, cfg=cfg, sketch=sk)
        if cfg.pair_balance and t % 2 == 0:
            stash = g
            assert int(eps) == 0
            continue
        z = (jax.tree.map(jnp.subtract, stash, g) if cfg.pair_balance
             else jax.tree.map(jnp.subtract, g, st.m_prev))
        if sk is not None:
            z = sk.apply(z)
            dot = jnp.vdot(ref_s, z)
        else:
            dot = tree_dot(ref_s, z)
        want = 1 if float(dot) <= 0 else -1
        assert int(eps) == want
        ref_s = jax.tree.map(lambda a, b: a + jnp.float32(want) * b, ref_s, z)
    for a, b in zip(jax.tree.leaves(st.s), jax.tree.leaves(ref_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(st.e) == 0.0
    for a, b in zip(jax.tree.leaves(grab_running_sum(st, cfg)),
                    jax.tree.leaves(st.s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# make_sketch allocation invariant (regression: the old largest-leaves
# round-robin could crash on 0-d leaves and under-allocate vs min(k, total))
# ---------------------------------------------------------------------------

def test_make_sketch_tiny_leaf_allocation_property():
    from hypothesis import given, settings, strategies as st

    shape_st = st.lists(
        st.tuples(st.integers(0, 2),            # rank (0 = scalar leaf)
                  st.integers(1, 6), st.integers(1, 6)),
        min_size=1, max_size=8)

    @settings(max_examples=60, deadline=None)
    @given(raw=shape_st, k=st.integers(1, 200), seed=st.integers(0, 2**16))
    def check(raw, k, seed):
        shapes = [tuple(dims[:rank]) for rank, *dims in raw]
        tree = {f"l{i}": jnp.zeros(s, jnp.float32)
                for i, s in enumerate(shapes)}
        total = sum(int(np.prod(s)) for s in shapes)
        sk = make_sketch(tree, k, seed=seed)
        assert sk.dim == min(k, total), (shapes, k)
        z = sk.apply(tree)
        assert z.shape == (min(k, total),)       # matches the [k] running sum
        assert z.dtype == jnp.float32

    check()


def test_make_sketch_scalar_leaves_sampled():
    """0-d leaves used to crash np.unravel_index; they are one coordinate."""
    tree = {"a": jnp.float32(3.0), "b": jnp.ones((2, 2), jnp.float32)}
    sk = make_sketch(tree, 5)
    assert sk.dim == 5
    z = np.asarray(sk.apply(tree))
    assert z.shape == (5,)
    assert 3.0 in z                              # the scalar's coordinate


def test_make_sketch_full_leaf_plus_remainder():
    """Remainder redistribution must target leaves with headroom: with one
    dominant leaf near saturation the spare slots go to the small leaves."""
    tree = {"big": jnp.zeros((8,), jnp.float32),
            "s1": jnp.zeros((1,), jnp.float32),
            "s2": jnp.zeros((1,), jnp.float32),
            "s3": jnp.zeros((1,), jnp.float32)}
    sk = make_sketch(tree, 11)                   # == total: every coordinate
    assert sk.dim == 11
    assert sk.apply(tree).shape == (11,)


# ---------------------------------------------------------------------------
# expand_pair_signs: odd-length streams fail loud (regression: bare assert)
# ---------------------------------------------------------------------------

def test_expand_pair_signs_odd_length_raises_actionable():
    from repro.core.grab import expand_pair_signs

    with pytest.raises(ValueError, match=r"even-length.*got 5"):
        expand_pair_signs(np.array([0, 1, 0, -1, 0]))
    with pytest.raises(ValueError, match="pair"):
        expand_pair_signs(np.array([[0, 0], [1, -1], [0, 0]]))  # odd T, 2D
