"""Training substrate: fused-GraB step, loop, checkpoint/restart."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.balance import alweiss_sign
from repro.core.grab import (GrabConfig, grab_epoch_end, grab_running_sum,
                             grab_step)
from repro.models.paper_models import logreg_init, logreg_loss
from repro.obs import MetricsRegistry
from repro.optim import adamw, constant, sgdm
from repro.train import (CheckpointManager, LoopConfig, build_train_step,
                         init_train_state, run_training)
from repro.train.loop import grab_rollover
from repro.data.synthetic import synthetic_classification


class ClsDataset:
    def __init__(self, x, y):
        self.x, self.y = x, y

    def __len__(self):
        return len(self.x)

    def batch(self, idx):
        return {"x": self.x[idx], "y": self.y[idx]}


def _setup(n=128, d=16):
    x, y = synthetic_classification(n, d, seed=0)
    params = logreg_init(jax.random.PRNGKey(0), d, 10)
    loss_fn = lambda p, mb: (logreg_loss(p, mb), {})
    return ClsDataset(x, y), params, loss_fn


def test_train_step_signs_and_loss():
    ds, params, loss_fn = _setup()
    cfg = GrabConfig()
    step = jax.jit(build_train_step(loss_fn, sgdm(0.9), constant(0.05),
                                    cfg, n_micro_per_epoch=16))
    state = init_train_state(params, sgdm(0.9), cfg)
    batch = {"x": ds.x[:32].reshape(8, 4, -1), "y": ds.y[:32].reshape(8, 4)}
    state, metrics = step(state, batch)
    assert metrics["signs"].shape == (8,)
    assert set(np.unique(np.asarray(metrics["signs"]))) <= {-1, 1}
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1


def _steps_against_grab_step(cfg, n_steps=2, n_micro=8):
    """Run ``n_steps`` built train steps of an epoch and, beside them,
    ``grab_step`` on each microbatch's gradient in turn, from the same
    state. Returns both GraB states and both sign sequences."""
    ds, params, loss_fn = _setup()
    n_per_epoch = n_steps * n_micro
    step = jax.jit(build_train_step(loss_fn, sgdm(0.9), constant(0.05), cfg,
                                    n_micro_per_epoch=n_per_epoch))
    grad = jax.jit(jax.grad(lambda p, mb: loss_fn(p, mb)[0]))
    ref_step = jax.jit(lambda st, g: grab_step(st, g, n_per_epoch, cfg))
    state = init_train_state(params, sgdm(0.9), cfg)
    ref, signs, ref_signs = state.grab, [], []
    for k in range(n_steps):
        rows = slice(4 * n_micro * k, 4 * n_micro * (k + 1))
        batch = {"x": ds.x[rows].reshape(n_micro, 4, -1),
                 "y": ds.y[rows].reshape(n_micro, 4)}
        for t in range(n_micro):
            g = grad(state.params, jax.tree.map(lambda a: a[t], batch))
            ref, eps = ref_step(ref, g)
            ref_signs.append(int(eps))
        state, metrics = step(state, batch)
        signs += np.asarray(metrics["signs"]).tolist()
    return state.grab, ref, signs, ref_signs


def test_step_folds_fresh_mean_once_like_grab_step():
    """The built step balances every microbatch but folds its gradient sum
    into the fresh mean once per step; two steps of an epoch match
    ``grab_step`` applied microbatch by microbatch. The running sum and the
    signs are the same bit for bit; the fresh mean, and the stale mean the
    epoch's end makes of it, differ only by the order of f32 additions."""
    cfg = GrabConfig()
    got, ref, signs, ref_signs = _steps_against_grab_step(cfg)
    assert signs == ref_signs and set(signs) == {-1, 1}
    assert int(got.t) == int(ref.t) == 16
    for a, b in zip(jax.tree.leaves(got.s), jax.tree.leaves(ref.s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(got.m_acc), jax.tree.leaves(ref.m_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    got, ref = grab_epoch_end(got, cfg), grab_epoch_end(ref, cfg)
    for a, b in zip(jax.tree.leaves(got.m_prev), jax.tree.leaves(ref.m_prev)):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_pair_balance_step_keeps_its_stash_in_m_acc():
    """Pair balancing runs ``grab_step`` whole in the step: ``m_acc`` is the
    pair stash, which after a step of eight microbatches holds the seventh's
    gradient, as ``grab_step`` microbatch by microbatch leaves it."""
    cfg = GrabConfig(pair_balance=True)
    got, ref, signs, ref_signs = _steps_against_grab_step(cfg)
    assert signs == ref_signs and set(signs[1::2]) <= {-1, 1}
    assert set(signs[0::2]) == {0}
    for tree in ("s", "m_acc"):
        for a, b in zip(jax.tree.leaves(getattr(got, tree)),
                        jax.tree.leaves(getattr(ref, tree))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(np.abs(np.asarray(x)).max() > 0
               for x in jax.tree.leaves(got.m_acc))


@pytest.mark.parametrize("mean", [1.0, 10.0])
@pytest.mark.parametrize("balancer", ["deterministic", "alweiss"])
def test_uncentered_sum_balances_like_the_centered_one(balancer, mean):
    """Over two epochs of the built step and its rollover, the uncentered
    running sum (``s = sum eps g``, ``e = sum eps``) picks the signs that
    the centered recurrence ``s += eps (g - m_prev)`` picks, in epoch 1
    against a nonzero stale mean too: ``grab_running_sum`` equals the
    centered sum within rtol 1e-5, ``e`` is the sum of the epoch's signs,
    and ``m_prev`` after each rollover is the epoch's mean gradient. The
    loss's gradient is the microbatch's row whatever the parameters, a mean
    of ``mean`` plus unit noise per coordinate, so the reference knows each
    gradient."""
    n_micro, steps, epochs = 8, 3, 2
    n = n_micro * steps
    rng = np.random.default_rng(7)
    rows = {"w": mean * rng.choice([-1.0, 1.0], (5, 4))
            + rng.normal(size=(n, 5, 4)),
            "b": mean + rng.normal(size=(n, 4))}
    rows = jax.tree.map(lambda x: x.astype(np.float32), rows)
    params = {"w": jnp.zeros((5, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}
    loss_fn = lambda p, mb: (sum(jnp.sum(p[k] * mb[k].mean(0)) for k in p),
                             {})
    cfg = GrabConfig(balancer=balancer, alweiss_c=30.0, seed=3)
    step = jax.jit(build_train_step(loss_fn, sgdm(0.9), constant(0.05), cfg,
                                    n_micro_per_epoch=n))
    rollover = jax.jit(grab_rollover, static_argnames="grab_cfg")
    state = init_train_state(params, sgdm(0.9), cfg)

    flat = lambda t: np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
    vecs = np.stack([flat(jax.tree.map(lambda x: x[i], rows))
                     for i in range(n)])
    ref_m, ref_key = np.zeros(vecs.shape[1], np.float32), jax.random.PRNGKey(3)
    for epoch in range(epochs):
        order = rng.permutation(n)
        ref_s, signs, ref_signs = np.zeros_like(ref_m), [], []
        for k in range(steps):
            idx = order[k * n_micro:(k + 1) * n_micro]
            batch = jax.tree.map(lambda x: x[idx][:, None], rows)
            state, metrics = step(state, batch)
            signs += np.asarray(metrics["signs"]).tolist()
            for z in vecs[idx] - ref_m:          # the centered recurrence
                dot = np.float32(np.dot(ref_s, z))
                if balancer == "deterministic":
                    eps = 1 if dot <= 0 else -1
                else:
                    ref_key, sub = jax.random.split(ref_key)
                    eps = int(alweiss_sign(jnp.float32(dot), jnp.float32(30.0),
                                           sub))
                ref_s = ref_s + np.float32(eps) * z
                ref_signs.append(eps)
            assert signs == ref_signs, (epoch, k)
            got = flat(grab_running_sum(state.grab, cfg))
            assert np.linalg.norm(got - ref_s) <= 1e-5 * np.linalg.norm(ref_s)
            np.testing.assert_allclose(got, ref_s, rtol=1e-5,
                                       atol=1e-5 * np.abs(ref_s).max())
            assert float(state.grab.e) == sum(signs)
        state = state._replace(grab=rollover(state.grab, grab_cfg=cfg))
        ref_m = vecs.mean(0).astype(np.float32)
        np.testing.assert_allclose(flat(state.grab.m_prev), ref_m, rtol=1e-5)
        assert float(state.grab.e) == 0.0
        assert not flat(state.grab.s).any()


def test_grab_state_none_for_rr():
    ds, params, loss_fn = _setup()
    step = jax.jit(build_train_step(loss_fn, sgdm(0.9), constant(0.05),
                                    None, n_micro_per_epoch=16))
    state = init_train_state(params, sgdm(0.9), None)
    assert state.grab is None
    batch = {"x": ds.x[:32].reshape(8, 4, -1), "y": ds.y[:32].reshape(8, 4)}
    state, metrics = step(state, batch)
    assert np.all(np.asarray(metrics["signs"]) == 0)


@pytest.mark.parametrize("ordering", ["grab", "rr"])
def test_loop_converges(ordering):
    ds, params, loss_fn = _setup()
    cfg = LoopConfig(epochs=4, n_micro=8, ordering=ordering, log_every=0)
    state, hist = run_training(loss_fn, params, sgdm(0.9), constant(0.05),
                               ds, 4, cfg)
    assert hist[-1]["loss"] < 0.5 * hist[0]["loss"]


@pytest.mark.parametrize("ordering", ["grab", "rr"])
def test_loop_step_donates_state(ordering):
    """The single-device step donates its TrainState: the state a hook saw
    at the end of epoch 0 goes into epoch 1's first step, after which every
    one of its buffers is deleted. The caller's params are copied in, so
    donation leaves them alive."""
    ds, params, loss_fn = _setup()
    seen = []
    cfg = LoopConfig(epochs=2, n_micro=8, ordering=ordering, log_every=0)
    state, _ = run_training(loss_fn, params, sgdm(0.9), constant(0.05), ds, 4,
                            cfg, hooks=lambda ep, st, h: seen.append(st))
    # the sign buffer is left out: on the CPU backend the epoch-end
    # device_get hands back a zero-copy view, and a buffer the host still
    # references is not donated
    donated = jax.tree.leaves(seen[0]._replace(signs=None))
    assert donated and all(x.is_deleted() for x in donated)
    assert not any(x.is_deleted() for x in jax.tree.leaves(state))
    assert not any(x.is_deleted() for x in jax.tree.leaves(params))


def test_mesh_loop_state_sharded_and_caller_params_kept():
    """On the mesh path the initial state is built by one jitted init with
    the state's shardings as outputs: every leaf comes back on the mesh, in
    fresh buffers, so donating the state leaves the caller's params alive."""
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_elastic_mesh

    ds, params, loss_fn = _setup()
    mesh = make_elastic_mesh(model_parallel=1)
    seen = []
    cfg = LoopConfig(epochs=2, n_micro=8, ordering="cd-grab", workers=2,
                     mesh=mesh, log_every=0)
    state, _ = run_training(loss_fn, params, adamw(), constant(0.01), ds, 4,
                            cfg, grab_cfg=GrabConfig(sketch_dim=8),
                            hooks=lambda ep, st, h: seen.append(st))
    assert all(isinstance(x.sharding, NamedSharding)
               and x.sharding.mesh == mesh for x in jax.tree.leaves(state))
    assert all(x.is_deleted() for x in jax.tree.leaves(seen[0].params))
    assert not any(x.is_deleted() for x in jax.tree.leaves(params))


def test_loop_records_compiled_step_bytes():
    """The loop records the compiled step's device bytes as gauges, and the
    first call reuses that compile: the step is traced once."""
    ds, params, loss_fn = _setup()
    traces = []

    def counting_loss(p, mb):
        traces.append(1)
        return loss_fn(p, mb)

    reg = MetricsRegistry(print_events=False)
    cfg = LoopConfig(epochs=2, n_micro=8, ordering="grab", log_every=0,
                     metrics=reg)
    run_training(counting_loss, params, sgdm(0.9), constant(0.05), ds, 4, cfg)
    gauges = reg.summary()["gauges"]
    state_bytes = 4 * sum(x.size for x in jax.tree.leaves(params))
    assert gauges["step.argument_bytes"]["last"] > state_bytes
    assert gauges["step.alias_bytes"]["last"] > 0          # donated
    assert all(gauges[f"step.{p}_bytes"]["n"] == 1
               for p in ("argument", "output", "alias", "temp"))
    assert len(traces) == 1


@pytest.mark.parametrize("ordering", ["grab", "rr"])
def test_loop_records_grab_state_bytes(ordering):
    """The loop records the GraB state's bytes on one chip as the
    ``grab.state_bytes`` gauge, once, from shapes alone, and the epoch's
    sign imbalance (|sum of its signs| over their number, from the sign
    buffer it fetches anyway) as ``grab.sign_imbalance``, once an epoch; rr
    has no GraB state and neither gauge."""
    ds, params, loss_fn = _setup()
    reg = MetricsRegistry(print_events=False)
    cfg = LoopConfig(epochs=2, n_micro=8, ordering=ordering, log_every=0,
                     metrics=reg)
    state, _ = run_training(loss_fn, params, sgdm(0.9), constant(0.05), ds,
                            4, cfg)
    gauges = reg.summary()["gauges"]
    if ordering == "rr":
        assert "grab.state_bytes" not in gauges
        assert "grab.sign_imbalance" not in gauges
        return
    assert gauges["grab.state_bytes"]["n"] == 1
    assert gauges["grab.state_bytes"]["last"] == sum(
        x.nbytes for x in jax.tree.leaves(state.grab))
    last_epoch = np.asarray(state.signs, np.int64)     # epoch 1's signs
    assert gauges["grab.sign_imbalance"]["n"] == 2
    assert gauges["grab.sign_imbalance"]["last"] == \
        abs(last_epoch.sum()) / last_epoch.size


def test_checkpoint_roundtrip_and_resume():
    ds, params, loss_fn = _setup()
    with tempfile.TemporaryDirectory() as d:
        cfg = LoopConfig(epochs=2, n_micro=8, ordering="grab",
                         ckpt_dir=d, log_every=0)
        state, hist = run_training(loss_fn, params, sgdm(0.9), constant(0.05),
                                   ds, 4, cfg)
        # restore equality
        mgr = CheckpointManager(d)
        restored, step, extra = mgr.restore(state)
        assert step == int(state.step)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), rtol=1e-6)
        assert extra["epoch"] == 2
        assert "sigma" in extra["order"]
        # resume continues (epoch 2 -> 3) without re-running earlier epochs
        cfg2 = LoopConfig(epochs=3, n_micro=8, ordering="grab",
                          ckpt_dir=d, log_every=0)
        state2, hist2 = run_training(loss_fn, params, sgdm(0.9),
                                     constant(0.05), ds, 4, cfg2)
        assert {h["epoch"] for h in hist2} == {2}


def test_checkpoint_atomicity_keeps_latest():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        tree = {"a": jnp.arange(4.0)}
        for s in (1, 2, 3):
            mgr.save(s, tree, blocking=True)
        from repro.train.checkpoint import list_checkpoints
        assert [s for s, _ in list_checkpoints(d)] == [2, 3]


def test_adamw_and_sgdm_reduce_quadratic():
    for opt in (adamw(weight_decay=0.0), sgdm(0.9)):
        params = {"w": jnp.asarray([3.0, -2.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            state, params = opt.update(state, grads, params, 0.05)
        assert float(jnp.abs(params["w"]).max()) < 0.05


def test_int8_error_feedback_compression():
    from repro.optim.compression import ef_int8_compress, ef_int8_decompress
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=256), jnp.float32)}
    residual = {"w": jnp.zeros(256, jnp.float32)}
    # accumulated error over steps stays bounded (error feedback works)
    acc_true = np.zeros(256)
    acc_q = np.zeros(256)
    for i in range(20):
        q, scales, residual = ef_int8_compress(g, residual)
        deq = ef_int8_decompress(q, scales)
        acc_true += np.asarray(g["w"])
        acc_q += np.asarray(deq["w"])
    resid = np.abs(np.asarray(residual["w"])).max()
    scale = float(scales["w"])
    assert resid <= 2 * scale * 127  # residual bounded by quantization range
    np.testing.assert_allclose(acc_q + np.asarray(residual["w"]), acc_true,
                               rtol=1e-4, atol=1e-4)


def test_int8_compress_psum_decompress_with_shared_scales():
    """The documented cross-rank recipe: compress with max-reduced scales,
    integer-psum, decompress by the shared scale / n_ranks. Ranks see wildly
    different magnitudes — exactly the case rank-local scales corrupt (the
    sum of integers quantized in different units has no unit)."""
    from repro.optim.compression import ef_int8_compress, ef_int8_decompress

    R = 4
    rng = np.random.default_rng(3)
    mags = np.array([0.01, 1.0, 10.0, 100.0])[:, None]
    gs = {"w": jnp.asarray(rng.normal(size=(R, 64)) * mags, jnp.float32)}
    res = {"w": jnp.zeros((R, 64), jnp.float32)}

    def rank(g, r):
        q, s, new_r = ef_int8_compress(g, r, axis_name="pod")
        q_sum = jax.tree.map(lambda x: jax.lax.psum(x, "pod"), q)
        return ef_int8_decompress(q_sum, s, R), s, new_r

    recon, scales, _ = jax.vmap(rank, axis_name="pod")(gs, res)
    recon, scales = np.asarray(recon["w"]), np.asarray(scales["w"])
    # the pmax made every rank quantize in the same unit ...
    assert np.all(scales == scales[0])
    # ... so every rank reconstructs the same mean, within the quantization
    # bound: per-rank elementwise error <= scale/2, averaged over R ranks
    assert np.all(recon == recon[0])
    true_mean = np.mean(np.asarray(gs["w"]), axis=0)
    np.testing.assert_allclose(recon[0], true_mean,
                               atol=float(scales[0]) / 2 + 1e-6)


def test_int8_compress_preserves_tuple_bearing_pytrees():
    """Gradient pytrees with interior tuple nodes must round-trip with their
    structure intact — the per-leaf (q, scale, residual) unzip goes through
    the treedef, not a tuple-type leaf predicate (which would stop descent
    at the interior tuple and corrupt all three outputs)."""
    from repro.optim.compression import ef_int8_compress, ef_int8_decompress

    g = {"a": (jnp.linspace(-1.0, 1.0, 8), jnp.full((4,), 2.0)),
         "b": {"c": jnp.full((3,), -3.0)}}
    r = jax.tree.map(jnp.zeros_like, g)
    q, s, new_r = ef_int8_compress(g, r)
    want = jax.tree_util.tree_structure(g)
    for out in (q, s, new_r):
        assert jax.tree_util.tree_structure(out) == want
    assert all(x.dtype == jnp.int8 for x in jax.tree.leaves(q))
    deq = ef_int8_decompress(q, s)
    for d, orig, scale in zip(jax.tree.leaves(deq), jax.tree.leaves(g),
                              jax.tree.leaves(s)):
        np.testing.assert_allclose(np.asarray(d), np.asarray(orig),
                                   atol=float(scale) / 2 + 1e-7)


def test_sign_wire_pack_unpack_roundtrip():
    """The [k] -> [k+4] packed row format: dequantization error <= scale/2
    per element, all-zero rows survive exactly, and the scale rides in-band
    as its own raw bytes (pure function of the wire -> replicated consumers
    derive identical values)."""
    from repro.optim.compression import (SCALE_BYTES, pack_rows_int8,
                                         quantize_rows_int8, unpack_rows_int8)

    rng = np.random.default_rng(7)
    rows = np.asarray(rng.normal(size=(6, 33)) * 50, np.float32)
    rows[2] = 0.0                              # stash row: must stay zero
    packed = pack_rows_int8(jnp.asarray(rows))
    assert packed.shape == (6, 33 + SCALE_BYTES) and packed.dtype == jnp.int8
    out = np.asarray(unpack_rows_int8(packed))
    _, scale = quantize_rows_int8(jnp.asarray(rows))
    err = np.abs(out - rows)
    assert np.all(err <= np.asarray(scale)[:, None] / 2 + 1e-7)
    assert np.all(out[2] == 0.0)
    # unpack is deterministic in the bytes alone
    again = np.asarray(unpack_rows_int8(jnp.asarray(np.asarray(packed))))
    assert np.array_equal(out, again)
