"""The training loop: ordering policy + permuted loader + fused-GraB step +
fault-tolerant checkpointing, assembled.

This is the loop ``examples/train_lm.py`` and the convergence benchmarks
drive. It is **dispatch-asynchronous**: the steady-state step loop performs
zero device→host transfers. The per-step balance signs accumulate in the
device-resident ``[T, W]`` int8 buffer inside ``TrainState`` (written by the
step at the GraB clock, donated across steps) and come back to the host
exactly once per epoch, right before the Algorithm-3 reorder; losses stay on
the device and are fetched in one batched transfer every ``log_every`` steps
(and at the epoch boundary). ``LoopConfig.sync_transfers=True`` restores the
legacy host-synchronous behavior — one loss + sign fetch per step — kept
only as the A/B baseline for ``benchmarks/cd_grab_scaling.py
--wallclock-loop``.

Passing ``LoopConfig.mesh`` runs the launcher path on real hardware: the
step is jitted with ``in_shardings`` from ``launch.sharding`` (the
``cd_grab_state_specs`` worker-stacked stash rules for cd-grab,
``constrain_grads`` from the param specs) and the hillclimb-winning
``CdGrabConstraints`` from the dry-run sweeps — one source of truth with
``launch.dryrun`` (see ``launch.live``).

Resume is **exact**: a checkpoint (mid-epoch or boundary) carries the sign
buffer and GraB state inside ``TrainState``, so the loop continues from the
exact step it stopped at — no epoch replay, no stale running sum.

Telemetry (``repro.obs``) rides the same contract: phase timers are
``perf_counter`` spans with profiler annotations (``obs.trace.phase``):
``loader_wait``, ``dispatch`` and ``ckpt_save`` per step, and at a GraB
epoch boundary ``epoch_reorder`` holding ``sign_fetch`` (the one sign
fetch, which first waits for the epoch's queued steps), ``reorder`` (the
Algorithm-3 reorder on the host) and ``rollover`` (the dispatch of the
epoch-end rollover), then ``epoch_hook`` around the caller's ``hooks``.
``phase.step`` times a whole loop iteration on the host; on the async path
that is the enqueue, not the device's step. On the device the step's layers
are named scopes (``train/step.py``) and the rollover runs under
``grab_rollover``. Per-epoch ordering-quality metrics are computed from the
sign buffer's existing once-per-epoch fetch, the compiled step's device
bytes are ``step.*_bytes`` gauges, the GraB state's bytes per chip the
``grab.state_bytes`` gauge, the epoch's |sum of signs| over its number of
signs the ``grab.sign_imbalance`` gauge (set once an epoch from the fetched
buffer; in full-pytree mode it bounds the f32 cancellation in the running
sum ``s - e * m_prev``), and everything lands in one schema-validated
JSONL run log (``LoopConfig.metrics_out``) — recording never adds a
device→host sync (enforced by the transfer-guarded
``tests/test_async_loop.py``).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grab import GrabConfig, grab_epoch_end, make_sketch
from repro.core.orderings import OrderPolicy, make_policy
from repro.data.prefetch import WindowPrefetcher
from repro.obs import MetricsRegistry, ProfileWindow, ordering_quality, phase
from repro.train.checkpoint import CheckpointManager
from repro.train.state import TrainState
from repro.train.step import build_train_step, init_train_state


@dataclasses.dataclass
class LoopConfig:
    epochs: int = 5
    n_micro: int = 8              # microbatches per optimizer step
    ordering: str = "grab"        # grab | cd-grab | rr | so | flipflop
    workers: int = 1              # cd-grab only: W logical DP workers
    sign_wire: str = "f32"        # cd-grab coordination wire: "f32" | "int8"
    #                               (int8 packs the [W, k] rows to [W, k+4]
    #                               int8 before the gather — ~4x fewer bytes,
    #                               same signs on every shard; on the mesh
    #                               path it also defers the exchange to one
    #                               overlappable gather per step)
    sign_hier: int = 0            # two-stage gather group size (0 = flat)
    ckpt_dir: Optional[str] = None
    ckpt_every_steps: int = 0     # 0 = once per epoch
    keep_ckpts: int = 3
    log_every: int = 50
    seed: int = 0
    # --- portable permutation artifacts ------------------------------------
    export_order: Optional[str] = None   # after training, save the final
    #                               learned order (the permutation the next
    #                               epoch would use) as a .npy artifact —
    #                               replay it with fixed_order for the
    #                               paper's retrain-from-GraB ablation
    fixed_order: Optional[str] = None    # path to a save_order .npy: replay
    #                               that frozen permutation every epoch
    #                               (overrides `ordering`; GraB reordering
    #                               is disabled — the artifact IS the order)
    # --- launcher path (see launch.live) -----------------------------------
    mesh: Any = None              # jax Mesh: jit with explicit in_shardings,
    #                               donate the state, apply the cd-grab
    #                               constraint set below
    shard_policy: Any = None      # launch.sharding.ShardPolicy (mesh only)
    cd_constraints: Optional[str] = None  # CD_GRAB_CANDIDATES name; None =
    #                               the measured hillclimb winner
    # --- data pipeline (repro.data.prefetch) -------------------------------
    loader_workers: int = 2       # window-prefetch assembly pool size
    loader_window: int = 4        # order_slice horizon, in optimizer steps
    loader_buffer: int = 2        # bounded delivery-queue depth (step batches)
    # --- telemetry (repro.obs) ---------------------------------------------
    metrics_out: Optional[str] = None     # JSONL run-log path (None = no sink;
    #                               metrics still accumulate in-process)
    metrics: Any = None           # inject a MetricsRegistry (tests/benchmarks
    #                               sharing one registry across runs); when
    #                               set, metrics_out is ignored
    profile_steps: Optional[str] = None   # "A:B": capture a JAX profiler
    #                               trace for global steps [A, B)
    profile_dir: str = "profile_trace"    # where the captured trace lands
    # --- legacy host-synchronous dispatch (benchmark A/B only) -------------
    sync_transfers: bool = False  # fetch loss + signs every step (blocking)


def _record_step_bytes(step_fn, state, batch, reg: MetricsRegistry) -> None:
    """Compile the jitted step ahead of its first call and record the
    compiled program's device bytes as ``step.*_bytes`` gauges. The call
    that follows reuses this executable (the jit cache is shared), so the
    step is compiled once."""
    ma = step_fn.lower(state, batch).compile().memory_analysis()
    if ma is None:                   # backends that do not report it
        return
    for part in ("argument", "output", "alias", "temp"):
        reg.gauge(f"step.{part}_bytes").set(
            getattr(ma, f"{part}_size_in_bytes"))


def _record_grab_bytes(grab, reg: MetricsRegistry) -> None:
    """Record the GraB state's bytes on one chip as the
    ``grab.state_bytes`` gauge, from each leaf's shard shape: metadata only,
    no transfer."""
    reg.gauge("grab.state_bytes").set(sum(
        math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(grab)))


def grab_rollover(grab, grab_cfg: GrabConfig):
    """The epoch-end rollover of the GraB state (``grab_epoch_end``), under
    the ``grab_rollover`` scope. Jitted under this name, so that its program
    is ``jit_grab_rollover``: most of its work is zero fills and copies that
    the compiler makes, and those carry no ``op_name``."""
    with jax.named_scope("grab_rollover"):
        return grab_epoch_end(grab, grab_cfg)


def run_training(loss_fn: Callable, params, optimizer, lr_schedule, dataset,
                 micro_size: int, loop_cfg: LoopConfig,
                 grab_cfg: Optional[GrabConfig] = None,
                 hooks: Optional[Callable] = None):
    """Train for loop_cfg.epochs over ``dataset``; returns (state, history).

    ``loss_fn(params, micro_batch) -> (loss, metrics)``.
    One optimizer step consumes ``n_micro`` microbatches; GraB orders the
    *microbatch* stream (n = len(dataset) / micro_size units per epoch).
    """
    n_micro_total = len(dataset) // micro_size
    assert n_micro_total % loop_cfg.n_micro == 0, \
        (n_micro_total, loop_cfg.n_micro)
    steps_per_epoch = n_micro_total // loop_cfg.n_micro

    fixed = loop_cfg.fixed_order is not None
    cd_grab = (loop_cfg.ordering in ("cd-grab", "cd_grab", "cdgrab")
               and not fixed)
    use_grab = (loop_cfg.ordering == "grab" or cd_grab) and not fixed
    n_workers = loop_cfg.workers if cd_grab else 1
    if use_grab and grab_cfg is None:
        grab_cfg = GrabConfig(pair_balance=cd_grab)
    if not use_grab:
        grab_cfg = None
    if cd_grab:
        if not grab_cfg.pair_balance:
            grab_cfg = dataclasses.replace(grab_cfg, pair_balance=True)
        # loop-level sign-wire knobs override the GrabConfig defaults only
        # when explicitly set, so callers passing a pre-configured grab_cfg
        # keep their choice
        if loop_cfg.sign_wire != "f32":
            grab_cfg = dataclasses.replace(grab_cfg,
                                           sign_wire=loop_cfg.sign_wire)
        if loop_cfg.sign_hier:
            grab_cfg = dataclasses.replace(grab_cfg,
                                           sign_hier=loop_cfg.sign_hier)
        assert loop_cfg.n_micro % n_workers == 0, \
            (loop_cfg.n_micro, n_workers)
        assert (n_micro_total // n_workers) % 2 == 0, \
            "pair balancing needs an even per-worker stream"

    if fixed:
        # replay a frozen permutation artifact: validates the file is a real
        # permutation and sized for THIS run's microbatch stream
        policy: OrderPolicy = make_policy("fixed", n_micro_total,
                                          path=loop_cfg.fixed_order)
    else:
        policy_kw = {}
        if cd_grab:
            policy_kw["workers"] = n_workers
        elif use_grab:
            policy_kw["pair"] = grab_cfg.pair_balance
        policy = make_policy(loop_cfg.ordering, n_micro_total,
                             seed=loop_cfg.seed, **policy_kw)

    # --- telemetry: registry + run metadata + profiler window --------------
    own_reg = loop_cfg.metrics is None
    reg: MetricsRegistry = (loop_cfg.metrics if loop_cfg.metrics is not None
                            else MetricsRegistry(loop_cfg.metrics_out))
    profiler = ProfileWindow(loop_cfg.profile_steps, loop_cfg.profile_dir,
                             reg=reg)
    run_meta = {
        "ordering": "fixed" if fixed else loop_cfg.ordering,
        "fixed_order": loop_cfg.fixed_order,
        "export_order": loop_cfg.export_order,
        "workers": n_workers,
        "epochs": loop_cfg.epochs, "steps_per_epoch": steps_per_epoch,
        "n_micro": loop_cfg.n_micro, "micro_size": micro_size,
        "n_examples": len(dataset), "seed": loop_cfg.seed,
        "sync_transfers": loop_cfg.sync_transfers,
        "loader": {"workers": loop_cfg.loader_workers,
                   "window": loop_cfg.loader_window,
                   "buffer": loop_cfg.loader_buffer},
        "mesh": dict(loop_cfg.mesh.shape) if loop_cfg.mesh is not None else None,
        "devices": jax.device_count(),
    }
    if grab_cfg is not None:
        run_meta.update(balancer=grab_cfg.balancer,
                        sketch_dim=grab_cfg.sketch_dim,
                        pair_balance=grab_cfg.pair_balance,
                        sign_wire=grab_cfg.sign_wire,
                        sign_hier=grab_cfg.sign_hier)
    meta_kw = {}
    if cd_grab and n_workers > 1 and grab_cfg.sketch_dim > 0:
        # analytic sign-collective roofline terms as run metadata, so the
        # modeled wire bytes sit in the same record stream as the measured
        # step times (group = W: one gathered row per logical worker —
        # matches the live mesh path where W == the data-axis size)
        from repro.launch.roofline import sign_collective_terms
        deferred = (loop_cfg.mesh is not None
                    and grab_cfg.sign_wire == "int8"
                    and grab_cfg.balancer == "deterministic")
        meta_kw["sign_collective"] = sign_collective_terms(
            n_workers, grab_cfg.sketch_dim,
            pair_steps=(n_micro_total // n_workers) // 2, group=n_workers,
            wire=grab_cfg.sign_wire, hier_group=grab_cfg.sign_hier,
            deferred=deferred)
    reg.emit("run_meta", run="train.loop", config=run_meta, **meta_kw)

    # the shard-aware window-prefetching pipeline: whole [n_micro, ...]
    # step batches are order_slice'd, gathered, and stacked OFF this
    # thread — the loop's loader_wait phase is one next() per step
    loader = WindowPrefetcher(
        dataset, policy, micro_size, n_micro=loop_cfg.n_micro,
        window=loop_cfg.loader_window, workers=loop_cfg.loader_workers,
        buffer=loop_cfg.loader_buffer, metrics=reg)

    sketch = None
    if grab_cfg is not None and grab_cfg.sketch_dim > 0:
        sketch = make_sketch(params, grab_cfg.sketch_dim)

    if loop_cfg.mesh is not None:
        # launcher path: explicit in_shardings + constraint set from
        # launch.sharding (one source of truth with the dry-run), donated
        # state, initial placement onto the mesh
        from repro.launch.live import build_live_step
        tmpl_micro = dataset.batch(np.arange(micro_size))
        batch_template = {k: np.stack([v] * loop_cfg.n_micro)
                          for k, v in tmpl_micro.items()}
        step_fn, state = build_live_step(
            loss_fn, optimizer, lr_schedule, grab_cfg, mesh=loop_cfg.mesh,
            params=params, batch_template=batch_template,
            n_micro=loop_cfg.n_micro, n_micro_total=n_micro_total,
            n_workers=n_workers, sketch=sketch,
            shard_policy=loop_cfg.shard_policy,
            cd_constraints=loop_cfg.cd_constraints)
    else:
        # donated, so old and new state are never both live across a step;
        # the params are copied in so that donation never deletes the
        # caller's arrays
        step_fn = jax.jit(build_train_step(
            loss_fn, optimizer, lr_schedule, grab_cfg,
            n_micro_per_epoch=n_micro_total, sketch=sketch,
            n_workers=n_workers), donate_argnums=(0,))
        state = init_train_state(jax.tree.map(jnp.copy, params), optimizer,
                                 grab_cfg, n_workers=n_workers,
                                 n_micro_per_epoch=n_micro_total)

    start_epoch = 0
    resume_step = 0
    manager = None
    if loop_cfg.ckpt_dir:
        manager = CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep_ckpts)
        restored, step, extra = manager.restore(state)
        if restored is not None:
            state = restored
            start_epoch = int(extra.get("epoch", 0))
            policy.load_state_dict(extra.get("order", {}))
            # resume is exact: the checkpointed TrainState carries the GraB
            # running state *and* the partial device sign buffer for the
            # interrupted epoch, so we continue from the very next step —
            # nothing is replayed against a stale running sum, and any
            # host-side pending records are superseded by the buffer
            policy.discard_pending()
            resume_step = int(step) - start_epoch * steps_per_epoch
            assert 0 <= resume_step <= steps_per_epoch, \
                (step, start_epoch, steps_per_epoch)
            reg.event(f"[loop] resumed from step {step}: epoch {start_epoch}, "
                      f"in-epoch step {resume_step}",
                      epoch=start_epoch, step=int(step))

    # built once — rebuilding jax.jit(lambda ...) at each boundary retraced
    # (and recompiled) the epoch-end rollover every epoch. On the mesh path
    # the rollover's fresh zero trees would come back with
    # propagation-chosen (replicated) shardings and poison the donated
    # step's committed in_shardings, so pin the outputs to the state's own
    # layout (restore preserves it, so this holds across resumes too).
    epoch_end_kw = {}
    if use_grab and loop_cfg.mesh is not None:
        epoch_end_kw["out_shardings"] = jax.tree.map(lambda x: x.sharding,
                                                     state.grab)
    epoch_end_fn = jax.jit(grab_rollover, static_argnames="grab_cfg",
                           **epoch_end_kw)
    if use_grab:
        _record_grab_bytes(state.grab, reg)

    history = []
    pending = []      # (epoch, global_step, device loss) not yet fetched

    def flush_losses():
        """One batched device→host transfer for all pending loss scalars."""
        if not pending:
            return None
        vals = jax.device_get([loss for _, _, loss in pending])
        for (ep, st, _), v in zip(pending, vals):
            history.append({"epoch": ep, "step": st, "loss": float(v)})
        pending.clear()
        return history[-1]["loss"]

    step_timer = reg.timer("phase.step")
    step_bytes_recorded = False
    for epoch in range(start_epoch, loop_cfg.epochs):
        t0 = time.perf_counter()
        start_s = resume_step if epoch == start_epoch else 0
        step_iter = loader.iter_epoch(epoch, start_step=start_s)
        for step_i in range(start_s, steps_per_epoch):
            ts0 = time.perf_counter()
            global_step = epoch * steps_per_epoch + step_i + 1
            profiler.on_step(global_step - 1)
            with phase("loader_wait", reg):
                # the stacked [n_micro, ...] batch was assembled off-thread
                # by the prefetch pool — this is delivery wait only
                _, batch = next(step_iter)
            with phase("dispatch", reg):
                if not step_bytes_recorded:
                    _record_step_bytes(step_fn, state, batch, reg)
                    step_bytes_recorded = True
                state, metrics = step_fn(state, batch)
            pending.append((epoch, global_step, metrics["loss"]))
            if loop_cfg.sync_transfers:
                # legacy host-synchronous dispatch: block on the loss and the
                # step's signs right here (the per-step sync the async loop
                # exists to avoid; ordering still consumes the device buffer)
                np.asarray(metrics["signs"])  # repro: allow[host-sync]
                loss = flush_losses()
            elif loop_cfg.log_every and step_i % loop_cfg.log_every == 0:
                loss = flush_losses()
            else:
                loss = None
            if (loss is not None and loop_cfg.log_every
                    and step_i % loop_cfg.log_every == 0):
                reg.event(f"[loop] epoch {epoch} step {step_i}/"
                          f"{steps_per_epoch} loss {loss:.4f}",
                          epoch=epoch, step=global_step, loss=loss)
            if (manager and loop_cfg.ckpt_every_steps
                    and global_step % loop_cfg.ckpt_every_steps == 0):
                with phase("ckpt_save", reg):
                    manager.save(global_step, state,
                                 extra={"epoch": epoch,
                                        "order": policy.state_dict()})
            # host wall time of the iteration (perf_counter, no sync): on
            # the async path this is the enqueue, not the device's step;
            # sync_transfers=True makes it the true blocking step time
            step_timer.record(time.perf_counter() - ts0)
        # epoch boundary: ONE sign fetch for the whole epoch, then commit the
        # Alg.3 reorder (cd-grab: the coordinated global two-pointer pass)
        # and roll the GraB means
        if use_grab:
            with phase("epoch_reorder", reg):
                with phase("sign_fetch", reg):
                    # THE sanctioned sign chokepoint: one fetch per epoch
                    # repro: allow[host-sync]
                    raw_signs = jax.device_get(state.signs)
                with phase("reorder", reg):
                    policy.apply_epoch_signs(epoch, raw_signs)
                with phase("rollover", reg):
                    state = state._replace(grab=epoch_end_fn(
                        state.grab, grab_cfg=grab_cfg))
            # zero-sync ordering quality: numpy over the buffer the reorder
            # already fetched — never an extra transfer
            quality = ordering_quality(raw_signs, grab_cfg.pair_balance)
            reg.emit("quality", epoch=epoch, **quality)
            reg.gauge("grab.sign_imbalance").set(quality["imbalance"])
        flush_losses()
        if manager:
            with phase("ckpt_save", reg):
                manager.save((epoch + 1) * steps_per_epoch, state,
                             extra={"epoch": epoch + 1,
                                    "order": policy.state_dict()})
        if hooks:
            with phase("epoch_hook", reg):
                hooks(epoch, state, history)
        dt = time.perf_counter() - t0
        ep_losses = [h["loss"] for h in history if h["epoch"] == epoch]
        # host floats from flush_losses, no device value  repro: allow[host-sync]
        mean_loss = float(np.mean(ep_losses)) if ep_losses else None
        reg.emit("epoch", epoch=epoch, duration_s=dt, mean_loss=mean_loss,
                 **reg.summary())
        if loop_cfg.log_every:
            loss_txt = "nan" if mean_loss is None else f"{mean_loss:.4f}"
            reg.event(f"[loop] epoch {epoch} done in {dt:.1f}s "
                      f"mean loss {loss_txt}", epoch=epoch)
    flush_losses()
    if loop_cfg.export_order:
        # the order the NEXT epoch would use: for GraB-family policies this
        # is the final learned sigma — the portable artifact the
        # retrain-from-GraB ablation replays via fixed_order
        policy.save_order(loop_cfg.export_order, epoch=loop_cfg.epochs)
        reg.event(f"[loop] exported order artifact "
                  f"({policy.n} units) to {loop_cfg.export_order}")
    if manager:
        manager.wait()
    profiler.close()
    if own_reg:
        reg.close()
    return state, history
