"""Subprocess entry point for the device-count-parameterized mesh tests.

JAX locks the device count at first init, so a *real* multi-device CPU mesh
needs ``--xla_force_host_platform_device_count`` set before the process ever
imports jax — hence this worker: ``tests/test_mesh_cd_grab.py`` spawns
``python _mesh_worker.py <n_devices>`` with a clean environment, and the
worker prints one JSON object on its last stdout line.

The constants (W, K, SEED, ...) live at module top so the parent test can
import them and compute the identical host-side reference on its single
device — everything here is seeded numpy, bit-reproducible across processes.
Keep all jax imports inside :func:`main` (importing this module from the
parent must not initialize jax with the forced flags).
"""
import json
import os
import sys

W = 8           # worker rows; divisible by every tested device count
K = 96          # sketch width; deliberately not a lane multiple
SEED = 1234
ALWEISS_C = 5.0
ALWEISS_KEY = 7
STEP_DIM = 16   # full-gradient dim for the grab_step_workers check
STEP_SKETCH = 8
STEP_T = 4      # timesteps (2 pair steps)
# the live mesh step (launch.live.build_live_step, as the training loop
# builds it): phi3-mini's SMOKE config, W workers over the data axis, T = 2
# timesteps a step. No SMOKE param is [W, K]-shaped, so the sign rows'
# all-gathers are told apart by shape.
LIVE_ARCH = "phi3-mini-3.8b"
LIVE_T = 2
LIVE_MICRO = 2
LIVE_SEQ = 32

def _inputs():
    import numpy as np
    rng = np.random.default_rng(SEED)
    zs = rng.normal(size=(W, K)).astype(np.float32)
    s0 = rng.normal(size=(K,)).astype(np.float32)
    gs = rng.normal(size=(STEP_T, W, STEP_DIM)).astype(np.float32)
    return zs, s0, gs


def main(n_dev: int) -> dict:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import coordinated_pair_signs, mesh_pair_signs
    from repro.core.grab import (GrabConfig, grab_step_workers,
                                 init_parallel_grab_state, make_sketch)

    assert jax.device_count() == n_dev, (jax.device_count(), n_dev)
    zs_np, s0_np, gs_np = _inputs()
    zs, s0 = jnp.asarray(zs_np), jnp.asarray(s0_np)

    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    z_sh = jax.device_put(zs, NamedSharding(mesh, P("data", None)))
    s_rep = jax.device_put(s0, NamedSharding(mesh, P()))
    out = {"n_dev": n_dev}

    def replicated_identically(x):
        shards = [np.asarray(s.data) for s in x.addressable_shards]
        return all(np.array_equal(shards[0], s) for s in shards[1:])

    # --- deterministic: mesh all-gather + replicated scan vs host scan ----
    s_mesh, signs_mesh = mesh_pair_signs(s_rep, z_sh, mesh)
    s_host, signs_host = coordinated_pair_signs(s0, zs, impl="xla")
    out["det_bitmatch"] = bool(
        np.array_equal(np.asarray(signs_mesh), np.asarray(signs_host))
        and np.array_equal(np.asarray(s_mesh), np.asarray(s_host)))
    out["det_replicated"] = bool(replicated_identically(signs_mesh)
                                 and replicated_identically(s_mesh))
    out["det_signs"] = np.asarray(signs_mesh).tolist()
    # f32 -> python float (f64) is exact, so JSON round-trips the bits
    out["det_s"] = [float(x) for x in np.asarray(s_mesh)]

    # --- Pallas kernel parity on the same inputs --------------------------
    # (the kernel compiles for the chip; on the CPU it runs interpreted)
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        s_pal, signs_pal = coordinated_pair_signs(s0, zs, impl="pallas")
    out["pallas_sign_bitmatch"] = bool(
        np.array_equal(np.asarray(signs_pal), np.asarray(signs_host)))
    out["pallas_s_close"] = bool(np.allclose(
        np.asarray(s_pal), np.asarray(s_host), rtol=1e-5, atol=1e-5))

    # --- Alweiss replicated-key invariant ---------------------------------
    key = jax.random.PRNGKey(ALWEISS_KEY)
    s_al, signs_al = mesh_pair_signs(s_rep, z_sh, mesh, kind="alweiss",
                                     c=ALWEISS_C, key=key)
    s_al_h, signs_al_h = coordinated_pair_signs(s0, zs, kind="alweiss",
                                                c=ALWEISS_C, key=key,
                                                impl="xla")
    out["alweiss_bitmatch"] = bool(
        np.array_equal(np.asarray(signs_al), np.asarray(signs_al_h))
        and np.array_equal(np.asarray(s_al), np.asarray(s_al_h)))
    out["alweiss_replicated"] = bool(replicated_identically(signs_al)
                                     and replicated_identically(s_al))
    out["alweiss_signs"] = np.asarray(signs_al).tolist()

    # --- int8 compressed wire: quantize-before-gather determinism ---------
    # the packed bytes are computed on the owning shard *before* the gather,
    # so every replica scans identical dequantized rows — bit-identity vs
    # the host scan on the same quantized wire is the whole contract.
    s_i8, signs_i8 = mesh_pair_signs(s_rep, z_sh, mesh, wire="int8")
    s_i8_h, signs_i8_h = coordinated_pair_signs(s0, zs, impl="xla",
                                                wire="int8")
    out["int8_bitmatch"] = bool(
        np.array_equal(np.asarray(signs_i8), np.asarray(signs_i8_h))
        and np.array_equal(np.asarray(s_i8), np.asarray(s_i8_h)))
    out["int8_replicated"] = bool(replicated_identically(signs_i8)
                                  and replicated_identically(s_i8))
    out["int8_signs"] = np.asarray(signs_i8).tolist()
    out["int8_s"] = [float(x) for x in np.asarray(s_i8)]

    # --- hierarchical two-stage gather == flat gather, both wires ---------
    hier_ok = True
    for hg in (h for h in (2, 4) if n_dev % h == 0 and h <= n_dev):
        s_hf, signs_hf = mesh_pair_signs(s_rep, z_sh, mesh, hier_group=hg)
        s_h8, signs_h8 = mesh_pair_signs(s_rep, z_sh, mesh, wire="int8",
                                         hier_group=hg)
        hier_ok = hier_ok and bool(
            np.array_equal(np.asarray(signs_hf), np.asarray(signs_mesh))
            and np.array_equal(np.asarray(s_hf), np.asarray(s_mesh))
            and np.array_equal(np.asarray(signs_h8), np.asarray(signs_i8))
            and np.array_equal(np.asarray(s_h8), np.asarray(s_i8)))
    out["hier_bitmatch"] = hier_ok

    # --- full device step: grab_step_workers(mesh=...) vs host path -------
    cfg = GrabConfig(pair_balance=True, sketch_dim=STEP_SKETCH)
    tmpl = {"g": jnp.zeros((STEP_DIM,), jnp.float32)}
    sketch = make_sketch(tmpl, STEP_SKETCH)
    st_m = init_parallel_grab_state(tmpl, cfg, W)
    st_h = init_parallel_grab_state(tmpl, cfg, W)
    step_eps = []
    ok = True
    for t in range(STEP_T):
        g = {"g": jnp.asarray(gs_np[t])}
        st_m, em = grab_step_workers(st_m, g, cfg, sketch, mesh=mesh)
        st_h, eh = grab_step_workers(st_h, g, cfg, sketch)
        ok = ok and bool(np.array_equal(np.asarray(em), np.asarray(eh)))
        step_eps.append(np.asarray(em).tolist())
    ok = ok and bool(np.array_equal(np.asarray(st_m.s), np.asarray(st_h.s)))
    out["step_bitmatch"] = ok
    out["step_signs"] = step_eps

    # --- deferred exchange == per-step exchange on the int8 wire ----------
    # grab_step_workers_collect stashes packed rows per microbatch; ONE
    # gather + replicated scan afterwards must reproduce the per-step
    # exchange bit-for-bit (same quantized rows, same scan order).
    from repro.core.distributed import mesh_deferred_pair_signs
    from repro.core.grab import grab_step_workers_collect

    cfg8 = GrabConfig(pair_balance=True, sketch_dim=STEP_SKETCH,
                      sign_wire="int8")
    st_p = init_parallel_grab_state(tmpl, cfg8, W)
    st_d = init_parallel_grab_state(tmpl, cfg8, W)
    s0_run = jnp.asarray(np.asarray(st_d.s))
    eps_ps, packed = [], []
    for t in range(STEP_T):
        g = {"g": jnp.asarray(gs_np[t])}
        st_p, ep = grab_step_workers(st_p, g, cfg8, sketch)
        eps_ps.append(np.asarray(ep))
        st_d, pk = grab_step_workers_collect(st_d, g, cfg8, sketch)
        packed.append(pk)
    s_def, eps_def = mesh_deferred_pair_signs(s0_run, jnp.stack(packed),
                                              jnp.int32(0), mesh)
    out["deferred_bitmatch"] = bool(
        np.array_equal(np.asarray(eps_def), np.stack(eps_ps))
        and np.array_equal(np.asarray(s_def), np.asarray(st_p.s)))
    out["deferred_replicated"] = bool(replicated_identically(eps_def)
                                      and replicated_identically(s_def))

    # --- the live mesh step's collectives, from its compiled HLO -------
    import _hlo
    out["live"] = {
        name: _hlo.collectives(_live_step_hlo(wire, slab))
        for name, wire, slab in (("int8", "int8", True), ("f32", "f32", True),
                                 ("int8_unpinned", "int8", False))}
    return out


def _live_step_hlo(wire: str, slab: bool) -> str:
    """The optimized HLO text of ``build_live_step``'s jitted step on a
    mesh of every device over ``data``, with the ``wire`` sign wire, the
    deterministic balancer and sketch width K. ``slab=False`` builds it
    with no explicit constraint (``CdGrabConstraints()``: XLA's own
    propagation) in place of ``make_cd_constraints``'s slab set."""
    import contextlib
    from unittest import mock

    import numpy as np
    import jax

    from repro.configs import get_config
    from repro.core.grab import GrabConfig, make_sketch
    from repro.launch import live
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.optim import adamw, constant
    from repro.train.step import CdGrabConstraints

    model = get_config(LIVE_ARCH)[1]
    params = lm.init_lm(jax.random.PRNGKey(0), model)
    grab_cfg = GrabConfig(pair_balance=True, sketch_dim=K, sign_wire=wire)
    n_micro = LIVE_T * W
    shape = (n_micro, LIVE_MICRO, LIVE_SEQ)
    batch = {"tokens": np.zeros(shape, np.int32),
             "labels": np.zeros(shape, np.int32)}
    with (contextlib.nullcontext() if slab else mock.patch.object(
            live, "make_cd_constraints",
            lambda *a, **kw: CdGrabConstraints())):
        step, state = live.build_live_step(
            lambda p, mb: lm.loss_fn(p, model, mb, remat=True), adamw(),
            constant(1e-3), grab_cfg, mesh=make_elastic_mesh(1),
            params=params, batch_template=batch, n_micro=n_micro,
            n_micro_total=4 * n_micro, n_workers=W,
            sketch=make_sketch(params, K))
    return step.lower(state, batch).compile().as_text()


if __name__ == "__main__":
    print(json.dumps(main(int(sys.argv[1]))))
