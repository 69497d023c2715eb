"""Launcher glue for the *live* training loop (``train.loop.run_training``
with ``LoopConfig.mesh``).

PRs 2/4 built the mesh-native CD-GraB machinery for the dry-run launcher:
``cd_grab_state_specs`` in_shardings, ``constrain_grads`` from the param
specs, the ``micro_workers`` constraint hillclimb. This module folds exactly
that configuration into the default launch path — same spec functions, same
``make_cd_constraints`` resolver as ``launch.specs.make_cell``, so what the
dry-run measured is what training runs. The live loop defaults the
constraint set to the hillclimb winner (``CD_GRAB_DEFAULT_CONSTRAINT``)
instead of sweeping.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.core.grab import GrabConfig, Sketch
from repro.launch.mesh import data_axes
from repro.launch.sharding import (ShardPolicy, cd_grab_state_specs,
                                   make_cd_constraints, make_grad_pinner,
                                   named, state_specs)
from repro.train.step import build_train_step, init_train_state


def build_live_step(loss_fn: Callable, optimizer, lr_schedule,
                    grab_cfg: Optional[GrabConfig], *, mesh, params,
                    batch_template, n_micro: int, n_micro_total: int,
                    n_workers: int = 1, sketch: Optional[Sketch] = None,
                    shard_policy: Optional[ShardPolicy] = None,
                    cd_constraints: Optional[str] = None,
                    data_axis: str = "data"):
    """Build the mesh-aware, donation-enabled jitted train step and the
    sharded initial :class:`TrainState` for the live loop.

    Returns ``(step_fn, state)``:

    * ``step_fn`` — ``jax.jit`` of :func:`train.step.build_train_step` with
      ``in_shardings`` from ``cd_grab_state_specs`` (W > 1) / ``state_specs``
      and the batch's leading microbatch-stream axis on the data axes;
      the state argument is donated, so the device-resident sign buffer and
      GraB state update in place across steps.
    * ``state`` — the initial TrainState (incl. the ``[T, W]`` sign buffer
      sized for ``n_micro_total``) placed onto the mesh with the same specs
      the step was compiled against. Checkpoint restore re-places into this
      template, inheriting the shardings.

    ``batch_template``: a host pytree with the per-step batch structure
    (leaves ``[n_micro, micro, ...]``) — only shapes/structure are read.
    ``cd_constraints`` names a ``CD_GRAB_CANDIDATES`` entry; None applies
    the hillclimb-winning default.
    """
    policy = shard_policy or ShardPolicy()
    cd_grab = n_workers > 1
    axes = data_axes(mesh)
    dp_total = 1
    for a in axes:
        dp_total *= mesh.shape[a]

    constrain_grads = make_grad_pinner(params, policy, mesh)
    cd_cons = None
    if cd_grab:
        assert grab_cfg is not None and grab_cfg.pair_balance
        assert n_workers % mesh.shape[data_axis] == 0, \
            (n_workers, dict(mesh.shape))
        cd_cons = make_cd_constraints(cd_constraints, params, batch_template,
                                      policy, mesh, data_axis=data_axis)

    step_fn = build_train_step(
        loss_fn, optimizer, lr_schedule, grab_cfg,
        n_micro_per_epoch=n_micro_total, sketch=sketch,
        constrain_grads=constrain_grads, n_workers=n_workers,
        mesh=mesh if cd_grab else None, data_axis=data_axis,
        cd_constraints=cd_cons)

    def init(p):
        return init_train_state(p, optimizer, grab_cfg, n_workers=n_workers,
                                n_micro_per_epoch=n_micro_total)

    abstract = jax.eval_shape(init, params)
    s_specs = (cd_grab_state_specs(abstract, policy, data_axis=data_axis)
               if cd_grab else state_specs(abstract, policy))
    state_shardings = named(mesh, s_specs)
    # the state is built in its sharded layout: made on one device first,
    # the whole W-stacked f32 stash would have to fit there. Its params are
    # fresh buffers, so donating the state never deletes the caller's.
    state = jax.jit(init, in_shardings=(state_shardings.params,),
                    out_shardings=state_shardings)(params)

    # batch leaves are [n_micro, micro, ...]: cd-grab shards the
    # microbatch-stream axis (it regroups to [T, W, ...] in-step, worker
    # rows over the data axes); single-stream shards the example axis.
    # PartitionSpecs apply as prefixes, so one spec per layout covers every
    # leaf rank.
    micro_bs = jax.tree.leaves(batch_template)[0].shape[1]
    if cd_grab and n_micro % dp_total == 0:
        b_spec = P(axes)
    elif not cd_grab and micro_bs % dp_total == 0:
        b_spec = P(None, axes)
    else:
        b_spec = P()
    # out_shardings pins the new state to the same specs as the input: the
    # donated state round-trips through the step with a stable layout (no
    # propagation drift, no resharding error when the committed output is
    # fed straight back in), and metrics come out replicated so the host
    # fetch at log/epoch boundaries is a plain copy.
    jitted = jax.jit(step_fn,
                     in_shardings=(state_shardings,
                                   jax.tree.map(lambda _: named(mesh, b_spec),
                                                batch_template)),
                     out_shardings=(state_shardings, named(mesh, P())),
                     donate_argnums=(0,))
    return jitted, state
