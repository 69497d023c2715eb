"""Train-step builder: gradient-accumulation scan with **fused GraB**.

The step consumes one *global batch* laid out as ``[n_micro, micro_bs, ...]``
and scans over the microbatch axis:

    for t in range(n_micro):                       # lax.scan
        g_t   = grad(loss)(params, micro_t)        # needed for accumulation anyway
        state, eps_t = grab_step(state, g_t)       # O(d) dot + sign + axpy
        acc  += g_t

so GraB's ordering signal costs **zero extra gradient computations** — the
paper's §6 gradient-accumulation workaround as a first-class systems feature.
The per-microbatch signs come back to the host, which reorders the global
microbatch permutation for the next epoch (Algorithm 3 two-pointer).

Under pjit the gradients inside the scan are already sharded; GraB's three
state pytrees inherit the same specs, its inner product is a per-shard
partial + scalar psum, and the single optimizer update happens *outside*
the scan (one fused grad all-reduce per step, overlappable with the last
microbatch's backward).

The step's layers carry ``jax.named_scope`` names, which reach every HLO
instruction's ``op_name`` metadata and so a device trace: ``fwd_bwd`` (the
gradient call, itself a jitted call of that name, the remat recompute
included), ``grab_balance`` (the balance step, the deferred sign exchange
and the sign-buffer write), ``grad_accum`` (the f32 accumulator's zeros,
adds and per-worker mean) and ``optimizer`` (the mean over microbatches,
the lr schedule and the update). The loop's epoch-end rollover is
``grab_rollover`` (``train/loop.py``). The names are metadata: the
compiled step has the same instructions without them.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.grab import (GrabConfig, Sketch, grab_step, grab_step_workers,
                             grab_step_workers_collect, init_grab_state,
                             init_parallel_grab_state, init_sign_buffer)
from repro.optim.optimizers import Optimizer
from repro.train.state import TrainState
from repro.utils.tree import tree_zeros_like


class CdGrabConstraints(NamedTuple):
    """Explicit sharding constraints for the [W, ...]-leading intermediates
    inside ``micro_workers`` (the CD-GraB scan body). Each field is an
    optional tree->tree callable (with_sharding_constraint under the hood);
    None leaves that intermediate to XLA's propagation. The launcher builds
    these from ``launch.sharding`` (``cd_grab_slab_specs`` /
    ``cd_grab_stacked_grad_specs``) so the constraint set and the
    ``cd_grab_state_specs`` in_shardings come from one source of truth, and
    the dry-run hillclimbs over ``launch.sharding.CD_GRAB_CANDIDATES`` to
    pick the measured-best set."""
    slab: Optional[Callable] = None     # [W, micro, ...] per-timestep batch
    grads: Optional[Callable] = None    # vmapped per-worker grads [W, ...]
    stash: Optional[Callable] = None    # worker-stacked pair stash [W, ...]


def build_train_step(loss_fn: Callable, optimizer: Optimizer,
                     lr_schedule: Callable,
                     grab_cfg: Optional[GrabConfig] = None,
                     n_micro_per_epoch: int = 1,
                     sketch: Optional[Sketch] = None,
                     constrain_grads: Optional[Callable] = None,
                     n_workers: int = 1, mesh=None, data_axis: str = "data",
                     cd_constraints: Optional[CdGrabConstraints] = None):
    """Returns train_step(state, batch) -> (state, metrics).

    loss_fn(params, micro_batch) -> (loss, metrics_dict).
    batch: pytree with a leading ``[n_micro, ...]`` axis on every leaf.
    If ``grab_cfg`` is None the step is a plain accumulate-and-apply (used
    for RR/SO/FlipFlop — identical compute, no balancing).
    Output metrics include ``signs: [n_micro]`` (+1/-1; zeros when GraB off).
    When ``state.signs`` carries the device-resident ``[T, W]`` buffer
    (``init_train_state(..., n_micro_per_epoch=N)``), the step also appends
    its sign rows there at offset ``grab.t`` — the loop then never reads
    ``metrics["signs"]``, fetching the whole buffer once per epoch.

    ``n_workers > 1`` is the CD-GraB path: the ``n_micro`` microbatches are
    regrouped as [T, W, ...] (T timesteps of W per-worker microbatches, the
    time-major layout ``ParallelGrabOrder`` schedules), per-worker gradients
    come from a vmap over the worker axis, and the pair signs are
    coordinated through the shared running sum in
    ``grab.grab_step_workers``. ``signs`` then has shape [T, W]. Requires
    ``grab_cfg.pair_balance`` and ``n_micro % n_workers == 0``.

    ``mesh``: the launcher's mesh-native CD-GraB path — forwarded to
    ``grab.grab_step_workers`` so the sketch-mode sign dataflow runs as the
    ``mesh_pair_signs`` all-gather + replicated scan instead of the
    host-simulated gathered scan (bit-identical results; the mesh form is
    what the SPMD partitioner lowers onto the hardware). Only meaningful
    with ``n_workers > 1``; ``data_axis`` names the mesh axis the worker
    rows shard over. With ``grab_cfg.sign_wire == "int8"`` and the
    deterministic balancer, the mesh path defers the exchange: the scan
    stashes packed int8 rows and ONE gather + replicated scan per optimizer
    step runs outside it (``distributed.mesh_deferred_pair_signs``),
    overlapping the wire with the epilogue — same signs, bit-identical.

    ``constrain_grads``: optional tree->tree applying param PartitionSpecs
    (with_sharding_constraint) to gradient-shaped pytrees. Without it, XLA's
    propagation can keep the f32 grad accumulator and GraB state *unsharded*
    through the microbatch scan — observed as 7 GiB-per-tensor temps on the
    256-chip dry-run. The launcher always passes this under pjit. (The
    worker-stacked stash of the CD-GraB path is pinned by the launcher via
    ``launch.sharding.cd_grab_state_specs`` instead — its leading axis is
    not gradient-shaped.)

    ``cd_constraints``: optional :class:`CdGrabConstraints` applying
    explicit in-scan constraints to the CD-GraB intermediates (batch slab /
    per-worker grads / stash). Without them XLA picks the stash-vs-gradient
    resharding itself, which the dry-run observed as unattributed extra
    all-gather bytes; the launcher hillclimbs over candidate sets and passes
    the measured-best one.
    """
    pin = constrain_grads or (lambda t: t)
    cdc = cd_constraints or CdGrabConstraints()
    if n_workers > 1:
        assert grab_cfg is not None and grab_cfg.pair_balance, \
            "multi-worker ordering is the CD-GraB pair-balance mode"
    # Deferred compressed exchange (compute overlap): with the int8 wire +
    # deterministic balancer on a mesh, the microbatch scan only *stashes*
    # each timestep's packed rows; ONE gather + replicated scan runs after
    # the scan (mesh_deferred_pair_signs), where XLA overlaps it with the
    # gradient-mean/optimizer epilogue instead of serializing one collective
    # into every scan iteration. Alweiss keeps the per-step compressed
    # exchange (its PRNG stream is per-timestep), as does the host path.
    deferred = (n_workers > 1 and mesh is not None and grab_cfg is not None
                and grab_cfg.sign_wire == "int8"
                and grab_cfg.balancer == "deterministic"
                and grab_cfg.sketch_dim > 0)

    def pin_grab(gs):
        if gs is None or grab_cfg is None:
            return gs
        s = gs.s if grab_cfg.sketch_dim > 0 else pin(gs.s)
        if n_workers > 1:          # stash carries a worker axis; see above
            if cdc.stash is not None:
                return gs._replace(s=s, m_prev=cdc.stash(gs.m_prev),
                                   m_acc=cdc.stash(gs.m_acc))
            return gs._replace(s=s)
        return gs._replace(s=s, m_prev=pin(gs.m_prev), m_acc=pin(gs.m_acc))

    @jax.jit
    def fwd_bwd(p, mb):
        # a call of its own, so that ``fwd_bwd`` also names the forward's
        # loop invariants: differentiating a scan hoists them out of the
        # model's layer loop with the name stack reset, past any scope
        return jax.value_and_grad(loss_fn, has_aux=True)(p, mb)

    def train_step(state: TrainState, batch):
        params = state.params

        def micro(carry, mb):
            acc, grab_state = carry
            with jax.named_scope("fwd_bwd"):
                (loss, metrics), grads = fwd_bwd(params, mb)
                grads = pin(grads)
            if grab_cfg is not None:
                with jax.named_scope("grab_balance"):
                    grab_state, eps = grab_step(grab_state, grads,
                                                n_micro_per_epoch, grab_cfg,
                                                sketch)
                    grab_state = pin_grab(grab_state)
            else:
                eps = jnp.int32(0)
            with jax.named_scope("grad_accum"):
                acc = pin(jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads))
            return (acc, grab_state), (loss, eps)

        def worker_grads(mb_w):
            # mb_w: [W, micro, ...] — one timestep of W per-worker batches
            with jax.named_scope("fwd_bwd"):
                if cdc.slab is not None:
                    mb_w = cdc.slab(mb_w)
                (losses, metrics), grads = jax.vmap(
                    fwd_bwd, in_axes=(None, 0))(params, mb_w)
                if cdc.grads is not None:
                    grads = cdc.grads(grads)
            return losses, grads

        def accumulate_mean(acc, grads):
            with jax.named_scope("grad_accum"):
                gmean = pin(jax.tree.map(
                    lambda g: g.astype(jnp.float32).mean(axis=0), grads))
                return pin(jax.tree.map(jnp.add, acc, gmean))

        def micro_workers(carry, mb_w):
            acc, grab_state = carry
            losses, grads = worker_grads(mb_w)
            with jax.named_scope("grab_balance"):
                grab_state, eps = grab_step_workers(
                    grab_state, grads, grab_cfg, sketch, mesh=mesh,
                    data_axis=data_axis)
                grab_state = pin_grab(grab_state)
            acc = accumulate_mean(acc, grads)
            return (acc, grab_state), (losses.mean(), eps)

        def micro_workers_collect(carry, mb_w):
            # deferred-exchange body: identical compute, but the sign
            # dataflow only stashes this timestep's packed int8 row — no
            # collective inside the scan
            acc, grab_state = carry
            losses, grads = worker_grads(mb_w)
            with jax.named_scope("grab_balance"):
                grab_state, packed = grab_step_workers_collect(
                    grab_state, grads, grab_cfg, sketch)
                grab_state = pin_grab(grab_state)
            acc = accumulate_mean(acc, grads)
            return (acc, grab_state), (losses.mean(), packed)

        with jax.named_scope("grad_accum"):
            acc0 = pin(tree_zeros_like(params, jnp.float32))
        if n_workers > 1 and deferred:
            from repro.core.distributed import mesh_deferred_pair_signs
            batch_w = jax.tree.map(
                lambda x: x.reshape((x.shape[0] // n_workers, n_workers)
                                    + x.shape[1:]), batch)
            (acc, grab_state), (losses, packed) = jax.lax.scan(
                micro_workers_collect, (acc0, pin_grab(state.grab)), batch_w)
            # one batched exchange for the whole step's [T, W, k+4] stash;
            # independent of the grad-mean/optimizer chain below, so the
            # compiler overlaps the gather with the epilogue
            with jax.named_scope("grab_balance"):
                new_s, signs = mesh_deferred_pair_signs(
                    grab_state.s, packed, state.grab.t, mesh, data_axis,
                    hier_group=grab_cfg.sign_hier)
            grab_state = grab_state._replace(s=new_s)
        elif n_workers > 1:
            batch_w = jax.tree.map(
                lambda x: x.reshape((x.shape[0] // n_workers, n_workers)
                                    + x.shape[1:]), batch)
            (acc, grab_state), (losses, signs) = jax.lax.scan(
                micro_workers, (acc0, pin_grab(state.grab)), batch_w)
        else:
            (acc, grab_state), (losses, signs) = jax.lax.scan(
                micro, (acc0, pin_grab(state.grab)), batch)

        with jax.named_scope("optimizer"):
            n_steps = losses.shape[0]
            grads = jax.tree.map(lambda a: a / n_steps, acc)
            lr = lr_schedule(state.step)
            opt_state, params = optimizer.update(state.opt, grads, params, lr)
        new_signs = state.signs
        if state.signs is not None and grab_cfg is not None:
            # device-resident sign buffer: append this step's rows at the
            # GraB clock (grab.t before the scan = timesteps already done
            # this epoch), so the buffer is epoch-positional and a resumed
            # step overwrites exactly the rows it would have produced
            with jax.named_scope("grab_balance"):
                rows = signs if n_workers > 1 else signs[:, None]
                new_signs = jax.lax.dynamic_update_slice(
                    state.signs, rows.astype(jnp.int8),
                    (state.grab.t, jnp.int32(0)))
        new_state = TrainState(params=params, opt=opt_state, grab=grab_state,
                               step=state.step + 1, signs=new_signs)
        metrics = {"loss": losses.mean(), "signs": signs, "lr": lr}
        return new_state, metrics

    return train_step


def init_train_state(params, optimizer: Optimizer,
                     grab_cfg: Optional[GrabConfig] = None,
                     n_workers: int = 1,
                     n_micro_per_epoch: int = 0) -> TrainState:
    """``n_micro_per_epoch > 0`` (and a grab_cfg) allocates the
    device-resident ``[T, W]`` int8 sign buffer in ``state.signs`` — the live
    loop's once-per-epoch sign fetch path. Dry-run cells and unit steps that
    read ``metrics["signs"]`` directly leave it at 0 (``signs=None``)."""
    if grab_cfg is None:
        grab = None
    elif n_workers > 1:
        grab = init_parallel_grab_state(params, grab_cfg, n_workers)
    else:
        grab = init_grab_state(params, grab_cfg)
    signs = (init_sign_buffer(n_micro_per_epoch, n_workers)
             if grab_cfg is not None and n_micro_per_epoch else None)
    return TrainState(params=params, opt=optimizer.init(params), grab=grab,
                      step=jnp.int32(0), signs=signs)
