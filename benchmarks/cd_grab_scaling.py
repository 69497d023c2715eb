"""CD-GraB scaling sweep: W ∈ {1, 2, 4, 8} simulated data-parallel workers.

Two measurements, both CPU-friendly:

1. **Herding prefix bound** (default): a fixed-gradient harness feeds the
   coordinated order through the real device path
   (``grab_step_workers`` + ``ParallelGrabOrder``) for several epochs and
   reports the herding objective (max prefix l2 norm of the centered
   stream) of the resulting *global* order per epoch, next to the RR
   median/min over random permutations. This is the quantity CD-GraB's
   theory bounds: the coordinated order should drop below the RR median
   after a couple of epochs at every W.

2. **End-to-end convergence** (``--train``): the full training loop
   (`ordering="cd-grab"`) on the logistic-regression task of the
   convergence benchmark, mean train loss per epoch vs. RR.

3. **Wall-clock of the sign dataflow** (``--wallclock``): per W, the time of
   one ``mesh_pair_signs`` invocation (the all-gather + replicated scan that
   is CD-GraB's only extra collective) next to the full
   ``grab_step_workers(mesh=...)`` device step it rides on, and their ratio
   — the fraction of the ordering step the sign traffic could occupy if it
   overlapped nothing. Runs on however many devices the process has
   (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to force a real
   multi-device CPU mesh; the W rows shard over it, so only W that are
   multiples of N run — others are emitted as ``wallclock_skipped``).

4. **Live-loop dispatch wall-clock** (``--wallclock-loop``): whole epochs of
   the real training loop on the mesh path, legacy host-synchronous dispatch
   (``LoopConfig.sync_transfers=True``: one loss + sign fetch per step) vs
   the async loop (device-resident sign buffer, ≤1 fetch per epoch) — the
   per-epoch win of ISSUE 5's dispatch-asynchronous refactor.

5. **Compressed sign wire** (``--sign-wire``): herding bound with the exact
   f32 sign wire vs the quantized int8 wire (sketch-mode dataflow) and the
   relative ordering-quality drift per epoch — the quality price of the
   int8 packed exchange.

CSV rows: kind,W,epoch,value. Every run also emits ``BENCH_cd_grab.json``
(``--json`` to relocate) with the same rows plus run metadata, so the perf
trajectory is recorded per commit.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.grab import (GrabConfig, grab_epoch_end, grab_step_workers,
                             init_parallel_grab_state)
from repro.core.herding import herding_objective
from repro.core.orderings import ParallelGrabOrder


def coordinated_bounds(zs: np.ndarray, n_workers: int, epochs: int,
                       seed: int = 0, sketch_dim: int = 0,
                       sign_wire: str = "f32") -> list:
    """Herding bound of the CD-GraB coordinated global order per epoch.

    ``sketch_dim``/``sign_wire`` route the balancing through the sketch-mode
    sign dataflow (the path the wire format exists on) — the int8-vs-f32
    comparison measures the ordering-quality drift the quantized wire buys
    its ~4x byte saving with."""
    n, d = zs.shape
    policy = ParallelGrabOrder(n, workers=n_workers, seed=seed)
    cfg = GrabConfig(pair_balance=True, sketch_dim=sketch_dim,
                     sign_wire=sign_wire)
    sketch = None
    if sketch_dim > 0:
        from repro.core.grab import make_sketch
        sketch = make_sketch({"g": jnp.zeros((d,), jnp.float32)}, sketch_dim)
    tmpl = {"g": jnp.zeros((d,), jnp.float32)}
    state = init_parallel_grab_state(tmpl, cfg, n_workers)
    step = jax.jit(lambda st, g: grab_step_workers(st, g, cfg, sketch))
    zs_j = jnp.asarray(zs, jnp.float32)

    bounds = []
    for epoch in range(epochs):
        order = policy.epoch_order(epoch)
        bounds.append(float(herding_objective(zs_j, jnp.asarray(order),
                                              ord=2)))
        seq = zs[order].reshape(n // n_workers, n_workers, d)
        for t in range(n // n_workers):
            state, eps = step(state, {"g": jnp.asarray(seq[t])})
            policy.record_step_signs(np.asarray(eps))
        policy.end_epoch(epoch)
        state = grab_epoch_end(state, cfg)
    return bounds


def rr_bounds(zs: np.ndarray, seeds: int = 20) -> tuple:
    """(median, min) herding bound over random permutations."""
    zs_j = jnp.asarray(zs, jnp.float32)
    vals = []
    for s in range(seeds):
        perm = np.random.default_rng((1234, s)).permutation(len(zs))
        vals.append(float(herding_objective(zs_j, jnp.asarray(perm), ord=2)))
    return float(np.median(vals)), float(np.min(vals))


def run_herding(n: int, d: int, epochs: int, workers: tuple, seed: int):
    rng = np.random.default_rng(seed)
    zs = rng.normal(size=(n, d)).astype(np.float32)
    med, best = rr_bounds(zs)
    rows = [("rr_median", 0, 0, med), ("rr_min", 0, 0, best)]
    for w in workers:
        for epoch, b in enumerate(coordinated_bounds(zs, w, epochs, seed)):
            rows.append(("herding", w, epoch, b))
    return rows


def run_sign_wire(n: int, d: int, epochs: int, workers: tuple, seed: int,
                  k: int):
    """Compressed-wire axis (``--sign-wire``): what the int8 sign wire costs
    in ordering quality, per W.

    The herding harness runs twice through the *sketch-mode* sign dataflow
    (the path the wire format lives on) — once exact (``sign_wire="f32"``),
    once quantized (``"int8"``) — and reports both bounds plus their
    relative drift per epoch. The drift is the entire quality price of the
    compression: signs are still exact ±1, only the sketched
    pair-difference rows the scan dots against are rounded. (What the wire
    saves, 4k / (k + 4) bytes per row, is pinned on the compiled mesh step
    by ``tests/test_mesh_cd_grab.py``.)
    """
    rng = np.random.default_rng(seed)
    zs = rng.normal(size=(n, d)).astype(np.float32)
    rows = []
    for w in workers:
        b_f32 = coordinated_bounds(zs, w, epochs, seed, sketch_dim=k,
                                   sign_wire="f32")
        b_int8 = coordinated_bounds(zs, w, epochs, seed, sketch_dim=k,
                                    sign_wire="int8")
        for epoch, (bf, b8) in enumerate(zip(b_f32, b_int8)):
            rows += [("herding_f32", w, epoch, bf),
                     ("herding_int8", w, epoch, b8),
                     ("herding_wire_drift", w, epoch, (b8 - bf) / bf)]
    return rows


def _time_us(fn, reps: int) -> float:
    out = jax.block_until_ready(fn())          # warmup + compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run_wallclock(workers: tuple, d: int = 65_536, k: int = 256,
                  reps: int = 30, seed: int = 0):
    """Sign all-gather + replicated scan vs the full CD-GraB device step.

    ``wallclock_sign_us``  — one ``mesh_pair_signs`` call ([W, k] gather +
                             W-row scan), the only coordination collective;
    ``wallclock_step_us``  — one full ``grab_step_workers(mesh=...)`` on
                             [W, d] synthetic gradients (stash/diff/sketch +
                             the sign dataflow);
    ``wallclock_sign_frac``— their ratio: how much of the ordering step the
                             sign traffic could occupy with zero overlap.
    """
    from repro.core.distributed import mesh_pair_signs
    from repro.core.grab import make_sketch

    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(seed)
    rows = [("wallclock_devices", 0, 0, float(n_dev))]
    for w in workers:
        if w % n_dev:
            # None -> JSON null (a NaN literal would make the file invalid)
            rows.append(("wallclock_skipped", w, 0, None))
            continue
        cfg = GrabConfig(pair_balance=True, sketch_dim=k)
        tmpl = {"g": jnp.zeros((d,), jnp.float32)}
        sketch = make_sketch(tmpl, k)
        state = init_parallel_grab_state(tmpl, cfg, w)
        g = {"g": jnp.asarray(rng.normal(size=(w, d)), jnp.float32)}
        zs = jnp.asarray(rng.normal(size=(w, k)), jnp.float32)
        s0 = jnp.zeros((k,), jnp.float32)
        sign = jax.jit(lambda s, z: mesh_pair_signs(s, z, mesh))
        step = jax.jit(lambda st, gg: grab_step_workers(st, gg, cfg, sketch,
                                                        mesh=mesh))
        sign_us = _time_us(lambda: sign(s0, zs), reps)
        step_us = _time_us(lambda: step(state, g), max(reps // 3, 3))
        rows += [("wallclock_sign_us", w, 0, sign_us),
                 ("wallclock_step_us", w, 0, step_us),
                 ("wallclock_sign_frac", w, 0, sign_us / step_us)]
    return rows


def run_loop_wallclock(epochs: int, n: int = 512, d: int = 64,
                       micro: int = 2, k: int = 64, seed: int = 0):
    """Per-epoch wall-clock of the *live* training loop, host-synchronous
    vs dispatch-asynchronous, on this process's real device mesh.

    Both runs take the identical launcher path (``LoopConfig.mesh``: jitted
    step with explicit in_shardings, donated state, the cd-grab slab
    constraint, W = device count workers); the only difference is
    ``sync_transfers`` — the legacy loop blocks on a loss + sign fetch
    every step, the async loop keeps signs in the device-resident buffer
    and fetches once per epoch. Rows:

    ``wallclock_loop_sync_s``  — median steady-state epoch, legacy dispatch;
    ``wallclock_loop_async_s`` — same, async dispatch (≤1 sign fetch/epoch);
    ``wallclock_loop_speedup`` — sync / async.

    The two modes run in *interleaved rounds* (sync, async, sync, async, …)
    and the medians pool the steady-state epochs of every round — on a
    shared CI box, load drift between two monolithic runs otherwise swamps
    the dispatch delta. Each round's epoch 0 (compile) is dropped; run with
    epochs >= 3 for a stable median. Force a multi-device mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    from benchmarks.common import ClsDataset
    from repro.data.synthetic import synthetic_classification
    from repro.launch.mesh import make_elastic_mesh
    from repro.models.paper_models import logreg_init, logreg_loss
    from repro.optim import constant, sgdm
    from repro.train import LoopConfig, run_training

    n_dev = jax.device_count()
    mesh = make_elastic_mesh(model_parallel=1)
    w = n_dev
    n_micro_total = n // micro
    n_micro = max(8, w)
    assert n_micro_total % n_micro == 0 and n_micro % w == 0, \
        (n_micro_total, n_micro, w)
    x, y = synthetic_classification(n, d, seed=1, noise=2.0)
    ds = ClsDataset(x, y)
    loss_fn = lambda p, mb: (logreg_loss(p, mb), {})

    rows = [("wallclock_loop_devices", 0, 0, float(n_dev))]
    samples = {True: [], False: []}
    for _round in range(3):
        for sync in (True, False):
            params = logreg_init(jax.random.PRNGKey(seed), d, 10)
            marks = [time.perf_counter()]

            def hook(epoch, state, history):
                marks.append(time.perf_counter())

            cfg = LoopConfig(epochs=epochs, n_micro=n_micro,
                             ordering="cd-grab", workers=w, log_every=0,
                             seed=seed, mesh=mesh, sync_transfers=sync)
            run_training(loss_fn, params, sgdm(0.9), constant(0.05), ds,
                         micro, cfg,
                         grab_cfg=GrabConfig(pair_balance=True,
                                             sketch_dim=k),
                         hooks=hook)
            per_epoch = np.diff(marks)
            steady = per_epoch[1:] if len(per_epoch) > 1 else per_epoch
            samples[sync].extend(float(t) for t in steady)
    med = {s: float(np.median(v)) for s, v in samples.items()}
    rows += [("wallclock_loop_sync_s", w, 0, med[True]),
             ("wallclock_loop_async_s", w, 0, med[False]),
             ("wallclock_loop_speedup", w, 0, med[True] / med[False])]
    return rows


def run_train(epochs: int, workers: tuple, seed: int):
    from benchmarks.common import ClsDataset
    from repro.data.synthetic import synthetic_classification
    from repro.models.paper_models import logreg_init, logreg_loss
    from repro.optim import constant, sgdm
    from repro.train import LoopConfig, run_training

    x, y = synthetic_classification(256, 32, seed=1, noise=2.0)
    ds = ClsDataset(x, y)
    loss_fn = lambda p, mb: (logreg_loss(p, mb), {})

    def sweep(ordering, w):
        params = logreg_init(jax.random.PRNGKey(seed), 32, 10)
        cfg = LoopConfig(epochs=epochs, n_micro=8, ordering=ordering,
                         workers=w, log_every=0, seed=seed)
        _, hist = run_training(loss_fn, params, sgdm(0.9), constant(0.05),
                               ds, 4, cfg)
        per_epoch = {}
        for h in hist:
            per_epoch.setdefault(h["epoch"], []).append(h["loss"])
        return [float(np.mean(v)) for _, v in sorted(per_epoch.items())]

    rows = [("train_rr", 1, epoch, l)
            for epoch, l in enumerate(sweep("rr", 1))]
    for w in workers:
        rows += [("train_cdgrab", w, epoch, l)
                 for epoch, l in enumerate(sweep("cd-grab", w))]
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--workers", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train", action="store_true",
                    help="also run the end-to-end loop sweep")
    ap.add_argument("--sign-wire", action="store_true",
                    help="also run the compressed-wire axis: herding bound "
                         "f32 vs int8 sign wire (sketch mode; see "
                         "run_sign_wire)")
    ap.add_argument("--wire-k", type=int, default=32,
                    help="sketch dim for --sign-wire")
    ap.add_argument("--wallclock", action="store_true",
                    help="also time the sign dataflow vs the device step")
    ap.add_argument("--wallclock-d", type=int, default=65_536,
                    help="synthetic gradient dim for --wallclock")
    ap.add_argument("--wallclock-loop", action="store_true",
                    help="also time whole live-loop epochs: legacy "
                         "host-synchronous dispatch vs the async loop "
                         "(W = device count, mesh path, see run_loop_wallclock)")
    ap.add_argument("--loop-epochs", type=int, default=4,
                    help="epochs for --wallclock-loop (first is dropped "
                         "as compile)")
    ap.add_argument("--json", default="BENCH_cd_grab.json",
                    help="where to write the JSON record ('' disables)")
    args = ap.parse_args(argv)

    rows = run_herding(args.n, args.d, args.epochs, tuple(args.workers),
                       args.seed)
    if args.train:
        rows += run_train(args.epochs, tuple(args.workers), args.seed)
    if args.sign_wire:
        rows += run_sign_wire(args.n, args.d, args.epochs,
                              tuple(args.workers), args.seed, args.wire_k)
    if args.wallclock:
        rows += run_wallclock(tuple(args.workers), d=args.wallclock_d,
                              seed=args.seed)
    if args.wallclock_loop:
        rows += run_loop_wallclock(args.loop_epochs, seed=args.seed)

    print("kind,W,epoch,value")
    for kind, w, epoch, v in rows:
        print(f"{kind},{w},{epoch},{'' if v is None else f'{v:.5f}'}")

    if args.json:
        from benchmarks.common import make_bench_record, write_bench_json
        rec = make_bench_record(
            "cd_grab_scaling",
            {"n": args.n, "d": args.d, "epochs": args.epochs,
             "workers": list(args.workers), "seed": args.seed,
             "wallclock_d": args.wallclock_d,
             "loop_epochs": args.loop_epochs,
             "wire_k": args.wire_k,
             "devices": jax.device_count()},
            rows)
        rec["unix_time"] = rec["time_unix"]      # pre-schema field, kept for
        #                                          old trend-table tooling
        write_bench_json(args.json, rec)
        print(f"[bench] wrote {args.json} (schema {rec['schema']})")


if __name__ == "__main__":
    main()
