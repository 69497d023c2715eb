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
# cd-grab dry-run cell (SMOKE config on this worker's real n_dev x 1 mesh):
# the sharding hillclimb + the analytic-vs-HLO sign-collective cross-check.
DRYRUN_ARCH = "minicpm-2b"
DRYRUN_SHAPE = "train_smoke"
DRYRUN_SKETCH = 96   # no SMOKE param slab is [W, 96]-shaped -> unambiguous
#                      fingerprint for the [W, k] sign all-gather isolation


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

    # --- cd-grab dry-run cell: constraint hillclimb + analytic-vs-HLO ----
    # Imported only now: jax is already initialized, so the module-level
    # forced-device-count flag append in launch.dryrun is inert.
    from jax.sharding import Mesh
    from repro.launch.dryrun import run_cell

    cell_mesh = Mesh(np.asarray(jax.devices()).reshape(n_dev, 1),
                     ("data", "model"))
    rec = run_cell(DRYRUN_ARCH, DRYRUN_SHAPE, cell_mesh, ordering="cd-grab",
                   sketch_dim=DRYRUN_SKETCH, smoke=True, verbose=False)
    out["dryrun"] = {k: rec.get(k) for k in (
        "status", "reason",
        "sign_collective_bytes_per_dev", "sign_collective_count",
        "sign_collective_s",
        "sign_collective_bytes_per_dev_hlo", "sign_collective_count_hlo",
        "sign_collective_s_hlo", "sign_collective_delta")}
    out["dryrun"]["cd_grab"] = rec.get("cd_grab")

    # --- int8 dry-run cell: compressed-wire collective attribution --------
    # constraints pinned to "slab" (skips the hillclimb re-run; the sign
    # collective bytes don't depend on the constraint set anyway) so the
    # parent can check bytes ratio vs the f32 cell + analytic-vs-HLO delta.
    rec8 = run_cell(DRYRUN_ARCH, DRYRUN_SHAPE, cell_mesh, ordering="cd-grab",
                    sketch_dim=DRYRUN_SKETCH, smoke=True, verbose=False,
                    cd_constraints="slab", sign_wire="int8")
    out["dryrun_int8"] = {k: rec8.get(k) for k in (
        "status", "reason",
        "sign_collective_bytes_per_dev", "sign_collective_count",
        "sign_collective_bytes_per_dev_hlo", "sign_collective_count_hlo",
        "sign_collective_delta")}
    return out


if __name__ == "__main__":
    print(json.dumps(main(int(sys.argv[1]))))
