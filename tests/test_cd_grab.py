"""CD-GraB distributed ordering subsystem: coordination, equivalence with
single-worker pair-balanced GraB at W=1, herding advantage over RR at W>1,
and checkpointability of every piece of ordering state."""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.balance import balance_sequence
from repro.core.distributed import coordinated_pair_signs, mesh_pair_signs
from repro.core.grab import (GrabConfig, expand_pair_signs, grab_epoch_end,
                             grab_step, grab_step_workers, init_grab_state,
                             init_parallel_grab_state)
from repro.core.herding import herding_objective
from repro.core.orderings import GrabOrder, ParallelGrabOrder, make_policy


def _tree(vec):
    return {"w": jnp.asarray(vec[:12].reshape(3, 4)), "b": jnp.asarray(vec[12:])}


# ---------------------------------------------------------------------------
# Ordering invariants (property-based)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(w=st.sampled_from([1, 2, 4]), m=st.integers(1, 12),
       seed=st.integers(0, 2**16), epoch=st.integers(0, 3))
def test_parallel_epoch_order_is_permutation(w, m, seed, epoch):
    n = w * 2 * m
    p = ParallelGrabOrder(n, workers=w, seed=seed)
    order = p.epoch_order(epoch)
    assert sorted(order.tolist()) == list(range(n))
    # time-major interleave: slot t*W + i belongs to worker i's shard
    owners = order.reshape(-1, w) // (n // w)
    assert np.array_equal(owners, np.tile(np.arange(w), (2 * m, 1)))


@settings(max_examples=30, deadline=None)
@given(w=st.sampled_from([1, 2, 4]), m=st.integers(1, 12),
       seed=st.integers(0, 2**16))
def test_parallel_order_stays_permutation_after_reorder(w, m, seed):
    n = w * 2 * m
    rng = np.random.default_rng(seed)
    p = ParallelGrabOrder(n, workers=w, seed=seed)
    for epoch in range(3):
        raw = np.zeros((2 * m, w), np.int64)
        raw[1::2] = rng.choice([-1, 1], size=(m, w))
        p.record_step_signs(raw)
        p.end_epoch(epoch)
        order = p.epoch_order(epoch + 1)
        assert sorted(order.tolist()) == list(range(n))
        # worker shards never exchange data
        for w_ in range(w):
            assert np.array_equal(np.sort(p.sigmas[w_]),
                                  np.arange(w_ * 2 * m, (w_ + 1) * 2 * m))


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 64), seed=st.integers(0, 2**16))
def test_expand_pair_signs_roundtrip(m, seed):
    rng = np.random.default_rng(seed)
    raw = np.zeros(2 * m, np.int64)
    raw[1::2] = rng.choice([-1, 1], m)
    out = expand_pair_signs(raw)
    assert set(np.unique(out)) <= {-1, 1}
    assert np.array_equal(out[0::2], -out[1::2])
    assert np.array_equal(out[0::2], raw[1::2])          # round-trips the pairs


def test_expand_pair_signs_2d_expands_per_worker():
    raw = np.array([[0, 0], [1, -1], [0, 0], [-1, 1]])
    out = expand_pair_signs(raw)
    assert out.shape == (4, 2)
    assert out[:, 0].tolist() == [1, -1, -1, 1]
    assert out[:, 1].tolist() == [-1, 1, 1, -1]


# ---------------------------------------------------------------------------
# Coordination machinery
# ---------------------------------------------------------------------------

def test_coordinated_pair_signs_is_sequential_balancing():
    """The worker scan must equal feeding the rows one-by-one to the plain
    Alg.5 balancer — that sequential semantics is the coordination."""
    rng = np.random.default_rng(0)
    zs = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    s0 = jnp.zeros(16, jnp.float32)
    new_s, signs = coordinated_pair_signs(s0, zs)
    signs_ref, s_ref = balance_sequence(zs)
    assert np.array_equal(np.asarray(signs), np.asarray(signs_ref))
    np.testing.assert_array_equal(np.asarray(new_s), np.asarray(s_ref))


def test_mesh_pair_signs_matches_host_scan():
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(1)
    zs = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=8), jnp.float32)
    s_mesh, signs_mesh = mesh_pair_signs(s0, zs, mesh)
    s_host, signs_host = coordinated_pair_signs(s0, zs)
    assert np.array_equal(np.asarray(signs_mesh), np.asarray(signs_host))
    np.testing.assert_array_equal(np.asarray(s_mesh), np.asarray(s_host))


# ---------------------------------------------------------------------------
# W=1 reproduces single-worker pair-balanced GraB bit-for-bit
# ---------------------------------------------------------------------------

def test_w1_device_signs_match_pair_mode_bitwise():
    cfg = GrabConfig(pair_balance=True)
    rng = np.random.default_rng(2)
    zs = rng.normal(size=(12, 16)).astype(np.float32)
    st_single = init_grab_state(_tree(zs[0]), cfg)
    st_multi = init_parallel_grab_state(_tree(zs[0]), cfg, 1)
    for t in range(12):
        st_single, e1 = grab_step(st_single, _tree(zs[t]), 12, cfg)
        st_multi, ew = grab_step_workers(
            st_multi, jax.tree.map(lambda x: x[None], _tree(zs[t])), cfg)
        assert int(e1) == int(ew[0])
    for a, b in zip(jax.tree.leaves(st_single.s), jax.tree.leaves(st_multi.s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_w1_policy_matches_grab_order_bitwise():
    n = 32
    rng = np.random.default_rng(3)
    single = GrabOrder(n, seed=7, pair=True)
    multi = make_policy("cd-grab", n, seed=7, workers=1)
    assert isinstance(multi, ParallelGrabOrder)
    assert np.array_equal(single.epoch_order(0), multi.epoch_order(0))
    for epoch in range(4):
        raw = np.zeros(n, np.int64)
        raw[1::2] = rng.choice([-1, 1], n // 2)
        single.record_step_signs(raw)
        single.end_epoch(epoch)
        multi.record_step_signs(raw.reshape(-1, 1))
        multi.end_epoch(epoch)
        assert np.array_equal(single.epoch_order(epoch + 1),
                              multi.epoch_order(epoch + 1))


# ---------------------------------------------------------------------------
# W>1: the coordinated order beats RR's herding bound
# ---------------------------------------------------------------------------

def _coordinated_bound(zs, n_workers, epochs, seed=0):
    n, d = zs.shape
    policy = ParallelGrabOrder(n, workers=n_workers, seed=seed)
    cfg = GrabConfig(pair_balance=True)
    state = init_parallel_grab_state({"g": jnp.zeros(d, jnp.float32)}, cfg,
                                     n_workers)
    step = jax.jit(lambda st, g: grab_step_workers(st, g, cfg))
    for epoch in range(epochs):
        order = policy.epoch_order(epoch)
        seq = zs[order].reshape(n // n_workers, n_workers, d)
        for t in range(n // n_workers):
            state, eps = step(state, {"g": jnp.asarray(seq[t])})
            policy.record_step_signs(np.asarray(eps))
        policy.end_epoch(epoch)
        state = grab_epoch_end(state, cfg)
    return float(herding_objective(jnp.asarray(zs),
                                   jnp.asarray(policy.epoch_order(epochs)),
                                   ord=2))


@pytest.mark.parametrize("n_workers", [2, 4])
def test_coordinated_order_beats_rr_median(n_workers):
    """Fixed-gradient harness: after a few coordinated epochs the global
    order's herding prefix bound is <= the RR median over 20 seeds."""
    rng = np.random.default_rng(5)
    zs = rng.normal(size=(64, 16)).astype(np.float32)
    cd = _coordinated_bound(zs, n_workers, epochs=4)
    rr = [float(herding_objective(
        jnp.asarray(zs),
        jnp.asarray(np.random.default_rng((99, s)).permutation(64)), ord=2))
        for s in range(20)]
    assert cd <= float(np.median(rr)), (cd, np.median(rr))


# ---------------------------------------------------------------------------
# Checkpoint / restore
# ---------------------------------------------------------------------------

def test_grab_order_roundtrip_mid_epoch():
    """Interrupt mid-epoch: state_dict carries sigma AND pending signs, and
    the restored policy finishes the epoch identically."""
    n = 16
    rng = np.random.default_rng(6)
    raw = np.zeros(n, np.int64)
    raw[1::2] = rng.choice([-1, 1], n // 2)
    a = GrabOrder(n, seed=1, pair=True)
    a.record_step_signs(raw[:8])                 # half the epoch, then "crash"
    d = a.state_dict()
    assert d["pending"].size == 8
    b = GrabOrder(n, seed=99)                    # wrong seed: state must win
    b.load_state_dict(d)
    for p in (a, b):
        p.record_step_signs(raw[8:])
        p.end_epoch(0)
    assert np.array_equal(a.epoch_order(1), b.epoch_order(1))


def test_parallel_grab_order_roundtrip_mid_epoch():
    w, n = 4, 32
    rng = np.random.default_rng(7)
    raw = np.zeros((n // w, w), np.int64)
    raw[1::2] = rng.choice([-1, 1], size=(n // w // 2, w))
    a = ParallelGrabOrder(n, workers=w, seed=2)
    a.record_step_signs(raw[:4])
    d = a.state_dict()
    assert d["pending"].shape == (4, w)
    assert d["sigmas"].shape == (w, n // w)
    b = ParallelGrabOrder(n, workers=w, seed=55)
    b.load_state_dict(d)
    for p in (a, b):
        p.record_step_signs(raw[4:])
        p.end_epoch(0)
    assert np.array_equal(a.epoch_order(1), b.epoch_order(1))


def test_parallel_grab_state_survives_tree_serialization():
    """GrabState with pair_balance=True (worker-stacked stash) must be a
    plain pytree: flatten/unflatten and checkpoint save/restore round-trip."""
    from repro.train.checkpoint import restore_checkpoint, save_checkpoint

    cfg = GrabConfig(pair_balance=True)
    tmpl = _tree(np.zeros(16, np.float32))
    state = init_parallel_grab_state(tmpl, cfg, 4)
    rng = np.random.default_rng(8)
    for t in range(4):
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.normal(size=(4,) + x.shape), jnp.float32),
            tmpl)
        state, _ = grab_step_workers(state, g, cfg)

    leaves, treedef = jax.tree.flatten(state)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert int(rebuilt.t) == 4

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, state)
        restored, step, _ = restore_checkpoint(d, state)
        assert step == 1
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# End-to-end loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cd_grab_trains_end_to_end():
    from repro.data.synthetic import synthetic_classification
    from repro.models.paper_models import logreg_init, logreg_loss
    from repro.optim import constant, sgdm
    from repro.train import LoopConfig, run_training

    class DS:
        def __init__(self, x, y):
            self.x, self.y = x, y

        def __len__(self):
            return len(self.x)

        def batch(self, i):
            return {"x": self.x[i], "y": self.y[i]}

    x, y = synthetic_classification(128, 16, seed=0)
    params = logreg_init(jax.random.PRNGKey(0), 16, 10)
    cfg = LoopConfig(epochs=3, n_micro=8, ordering="cd-grab", workers=2,
                     log_every=0)
    _, hist = run_training(lambda p, mb: (logreg_loss(p, mb), {}), params,
                           sgdm(0.9), constant(0.05), DS(x, y), 4, cfg)
    assert hist[-1]["loss"] < 0.5 * hist[0]["loss"]


def test_coord_impl_env_validation(monkeypatch):
    """Unknown REPRO_COORD_IMPL values (e.g. the typo 'palas') used to fall
    silently through to the XLA scan; they must raise with the allowed set."""
    from repro.core.distributed import _coord_impl

    monkeypatch.setenv("REPRO_COORD_IMPL", "palas")
    with pytest.raises(ValueError, match=r"palas.*pallas.*xla"):
        _coord_impl()
    rng = np.random.default_rng(21)
    zs = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    s0 = jnp.zeros(8, jnp.float32)
    with pytest.raises(ValueError, match="pallas"):
        coordinated_pair_signs(s0, zs)           # resolves via the env var
    for ok in ("pallas", "xla"):
        monkeypatch.setenv("REPRO_COORD_IMPL", ok)
        assert _coord_impl() == ok
    monkeypatch.delenv("REPRO_COORD_IMPL")
    assert _coord_impl() in ("pallas", "xla")


def test_coordinated_pair_signs_rejects_unknown_impl():
    rng = np.random.default_rng(22)
    zs = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)
    s0 = jnp.zeros(8, jnp.float32)
    with pytest.raises(ValueError, match=r"impl='cuda'.*pallas.*xla"):
        coordinated_pair_signs(s0, zs, impl="cuda")


def test_make_policy_cd_grab_spellings_and_errors():
    for name in ("cd-grab", "cd_grab", "cdgrab"):
        p = make_policy(name, 16, workers=4)
        assert isinstance(p, ParallelGrabOrder) and p.workers == 4
    with pytest.raises(AssertionError):
        make_policy("cd-grab", 15, workers=2)     # doesn't shard evenly


def test_cd_grab_sharding_specs():
    """launch wiring: the worker-stacked stash shards over the data axis,
    the shared running sum keeps the param rule, and every spec is actually
    placeable (no duplicate mesh axes — the FSDP rules put 'data' on inner
    dims, which must yield to the worker axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.sharding import ShardPolicy, cd_grab_state_specs
    from repro.optim import sgdm
    from repro.train.step import init_train_state

    params = {"mlp": {"wg": jnp.zeros((8, 16)), "wo": jnp.zeros((16, 8))}}
    state = init_train_state(params, sgdm(0.9),
                             GrabConfig(pair_balance=True), n_workers=4)
    specs = cd_grab_state_specs(state, ShardPolicy())
    assert specs.grab.m_acc["mlp"]["wg"] == P("data", None, "model")
    assert specs.grab.s["mlp"]["wg"] == specs.params["mlp"]["wg"]
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        NamedSharding(mesh, spec)      # raises on any duplicate-axis spec


def test_cd_grab_resume_from_mid_epoch_checkpoint():
    """A checkpoint written mid-epoch carries the *device-resident* sign
    buffer (partially filled) inside the TrainState — the policy holds no
    host-side pending signs — and resume continues from the exact step,
    reproducing the uninterrupted run bit-for-bit instead of replaying the
    epoch against a stale running sum."""
    from repro.data.synthetic import synthetic_classification
    from repro.models.paper_models import logreg_init, logreg_loss
    from repro.optim import constant, sgdm
    from repro.train import LoopConfig, run_training

    class DS:
        def __init__(self, x, y):
            self.x, self.y = x, y

        def __len__(self):
            return len(self.x)

        def batch(self, i):
            return {"x": self.x[i], "y": self.y[i]}

    x, y = synthetic_classification(64, 16, seed=0)
    params = logreg_init(jax.random.PRNGKey(0), 16, 10)
    loss = lambda p, mb: (logreg_loss(p, mb), {})
    import json
    import os
    import shutil

    from repro.train.checkpoint import list_checkpoints

    with tempfile.TemporaryDirectory() as d:
        cfg = LoopConfig(epochs=1, n_micro=8, ordering="cd-grab", workers=2,
                         ckpt_dir=d, ckpt_every_steps=1, log_every=0)
        state_full, _ = run_training(loss, params, sgdm(0.9), constant(0.05),
                                     DS(x, y), 4, cfg)
        # simulate a crash after the first optimizer step's save: drop the
        # epoch-boundary checkpoint so the newest one is genuinely mid-epoch
        ckpts = list_checkpoints(d)
        assert len(ckpts) == 2
        shutil.rmtree(ckpts[-1][1])
        with open(os.path.join(ckpts[0][1], "manifest.json")) as f:
            manifest = json.load(f)
        extra = manifest["extra"]
        assert extra["epoch"] == 0
        # pending signs live in the device buffer, not on the policy
        assert len(extra["order"]["pending"]["__ndarray__"]) == 0
        sign_entry = next(e for e in manifest["leaves"]
                          if e["path"].lstrip(".") == "signs")
        assert sign_entry["dtype"] == "int8"
        buf = np.load(os.path.join(ckpts[0][1], sign_entry["file"]))
        assert buf.shape == (8, 2)                   # [T = 16/2, W = 2]
        assert np.any(buf[:4] != 0)                  # step 1's rows recorded
        assert np.all(buf[4:] == 0)                  # step 2's rows pending
        state_res, hist = run_training(loss, params, sgdm(0.9),
                                       constant(0.05), DS(x, y), 4, cfg)
        assert {h["epoch"] for h in hist} == {0}
        assert len(hist) == 1                        # only step 2 re-ran
        # exact resume: bit-identical to the uninterrupted run
        for a, b in zip(jax.tree.leaves(state_full), jax.tree.leaves(state_res)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
