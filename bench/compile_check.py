"""Compile each cell's training step for a described TPU v5e chip, on a
host without one, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [--workload NAME ...]
        [--layers N ...]

The step is built as the training loop builds it for one chip (the jitted,
state-donating ``build_train_step``) at the cell's sizes; nothing runs. A
step that does not fit the chip fails here as it would on the chip. A cell
whose configuration has a ``mesh`` is compiled for that mesh over the
described v5e 2x2, as ``repro.launch.live.build_live_step`` builds it for
the loop (its shardings, constraint set and donated state), and the bytes
printed are per chip.
``--layers`` compiles the cell's configuration at other depths, to find the
deepest that fits. Compiling a whole step takes a minute or so.
"""
from __future__ import annotations

import argparse
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = float(2 ** 30)


def compile_step(cell: dict, cfg: dict, traffic: dict, layers: int, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core.grab import GrabConfig
    from repro.models import lm
    from repro.optim import adamw, constant
    from repro.train.step import build_train_step, init_train_state

    over = dict(cfg["program"]["overrides"], n_layers=layers)
    model = get_config(cfg["program"]["arch"])[0].with_(**over)
    grab_cfg = (GrabConfig(**traffic["grab"])
                if traffic["ordering"] == "grab" else None)
    n_units = traffic["steps_per_epoch"] * traffic["n_micro"]
    hp = traffic["optimizer"]
    opt = adamw(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], clip_norm=hp["clip_norm"])
    remat = traffic["remat"]
    step = jax.jit(build_train_step(
        lambda p, mb: lm.loss_fn(p, model, mb, remat=remat), opt,
        constant(hp["lr"]), grab_cfg, n_micro_per_epoch=n_units),
        donate_argnums=(0,))
    one = SingleDeviceSharding(device)
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    state = place(jax.eval_shape(lambda: init_train_state(
        lm.init_lm(jax.random.PRNGKey(0), model), opt, grab_cfg,
        n_micro_per_epoch=n_units)))
    shape = (traffic["n_micro"], traffic["micro"], traffic["seq_len"])
    batch = place({"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
                   "labels": jax.ShapeDtypeStruct(shape, jnp.int32)})
    return step.lower(state, batch).compile().memory_analysis()


def compile_mesh_step(cfg: dict, traffic: dict, layers: int, devices):
    """``build_live_step``'s jitted step for ``cfg["mesh"]`` over
    ``devices``, lowered from shapes alone (``build_live_step`` itself
    places a state, which a described chip cannot hold)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from harness import make_mesh
    from repro.configs import get_config
    from repro.core.grab import GrabConfig, make_sketch
    from repro.launch.mesh import data_axes
    from repro.launch.sharding import (ShardPolicy, cd_grab_state_specs,
                                       make_cd_constraints, make_grad_pinner,
                                       named)
    from repro.models import lm
    from repro.optim import adamw, constant
    from repro.train.step import build_train_step, init_train_state

    over = dict(cfg["program"]["overrides"], n_layers=layers)
    model = get_config(cfg["program"]["arch"])[0].with_(**over)
    grab_cfg = GrabConfig(**traffic["grab"])
    workers = traffic["workers"]
    n_units = traffic["steps_per_epoch"] * traffic["n_micro"]
    hp = traffic["optimizer"]
    opt = adamw(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], clip_norm=hp["clip_norm"])
    mesh = make_mesh(devices[:math.prod(cfg["mesh"].values())], cfg["mesh"])
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), model))
    bshape = (traffic["n_micro"], traffic["micro"], traffic["seq_len"])
    batch = {k: jax.ShapeDtypeStruct(bshape, jnp.int32)
             for k in ("tokens", "labels")}
    policy = ShardPolicy()
    remat = traffic["remat"]
    step = build_train_step(
        lambda p, mb: lm.loss_fn(p, model, mb, remat=remat), opt,
        constant(hp["lr"]), grab_cfg, n_micro_per_epoch=n_units,
        sketch=make_sketch(params, grab_cfg.sketch_dim),
        constrain_grads=make_grad_pinner(params, policy, mesh),
        n_workers=workers, mesh=mesh,
        cd_constraints=make_cd_constraints(None, params, batch, policy,
                                           mesh))
    state = jax.eval_shape(lambda p: init_train_state(
        p, opt, grab_cfg, n_workers=workers, n_micro_per_epoch=n_units),
        params)
    s_sh = named(mesh, cd_grab_state_specs(state, policy))
    b_sh = named(mesh, P(data_axes(mesh)))
    jitted = jax.jit(step, in_shardings=(s_sh, {k: b_sh for k in batch}),
                     out_shardings=(s_sh, named(mesh, P())),
                     donate_argnums=(0,))
    return jitted.lower(state, batch).compile().memory_analysis()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", nargs="*")
    ap.add_argument("--layers", type=int, nargs="*")
    args = ap.parse_args(argv)

    from jax.experimental import topologies

    from layout import Layout

    lay = Layout(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in lay.spec["workloads"]]
    for name in names:
        cell = lay.cell(name)
        cfg = lay.config(cell["config"])
        traffic = lay.traffic(cell["traffic"])
        for layers in args.layers or [cfg["num_hidden_layers"]]:
            try:
                ma = (compile_mesh_step(cfg, traffic, layers, topo.devices)
                      if cfg.get("mesh") else
                      compile_step(cell, cfg, traffic, layers,
                                   topo.devices[0]))
            except Exception as e:  # noqa: BLE001 — report and go on
                print(f"{name} layers={layers}: does not compile: "
                      f"{str(e).splitlines()[0][:300]}")
                continue
            need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(f"{name} layers={layers}: arguments "
                  f"{ma.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
                  f"{ma.temp_size_in_bytes / GIB:.2f} GiB, outputs "
                  f"{ma.output_size_in_bytes / GIB:.2f} GiB (aliased "
                  f"{ma.alias_size_in_bytes / GIB:.2f}); needs "
                  f"{need / GIB:.2f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
