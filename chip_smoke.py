"""Smoke run of GraB training on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: phi3 training + kernels
    python chip_smoke.py --four-chip   # four chips: cd-grab on the mesh

The default run trains ``phi3-mini-3.8b`` at its published widths, cut in
depth only, through ``train.loop.run_training`` (the path
``examples/train_lm.py`` drives): GraB with full-pytree balancing (the
paper's Algorithm 4) for two epochs, so the once-per-epoch sign fetch and
the Algorithm-3 reorder both run on the chip, then the random-reshuffling
control arm for one epoch. A second phase runs every Pallas kernel compiled
for the chip at real widths and compares it with its ``kernels/ref.py``
oracle.

``--four-chip`` runs only the multi-chip path: cd-grab with W=4 workers on a
data-parallel mesh over four chips (sketch k=1024, int8 sign wire, the
deferred exchange). It first compares the mesh run with the same job on the
host-simulated path on one device of the same process, over a few seeds, at
phi3's smoke configuration; then it trains the mesh path alone at phi3's
published widths, cut in depth only.

The script refuses to run anywhere but on a TPU: it exits non-zero and
prints no result line. Any failed check exits non-zero. On success the last
line of standard output is one JSON object naming the device, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``; the other lines are informational. A step time printed here
is a smoke observation on the host clock, not a benchmark metric.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "phi3-mini-3.8b"
# Depth is the only cut: 2 of 32 layers. The compiled grab step then needs
# 8.70 GiB of arguments (bf16 params, f32 AdamW m/v and GraB s/m_prev/m_acc,
# ~22 B/param) plus 5.62 GiB of temporaries, of the chip's 15.75 GiB; three
# layers need 17.05 GiB (memory_analysis of the step compiled for v5e).
N_LAYERS = 2
SEQ_LEN = 4096                  # the train_4k shape
MICRO, N_MICRO = 1, 4           # one 4096-token row per microbatch
STEPS_PER_EPOCH = 3
EPOCHS = 2
SEED = 0
LR = 3e-4

# Four-chip phase: cd-grab with W=4, one worker row per chip.
FOUR_W = 4
FOUR_SKETCH = 1024
FOUR_N_MICRO = 8                # T = 2 pair timesteps of W rows per step
FOUR_STEPS_PER_EPOCH = 3
FOUR_EPOCHS = 2
# The mesh-vs-host comparison runs phi3's smoke configuration: the
# host-simulated path keeps the W-stacked f32 pair stash of the whole model
# on one chip, which at phi3 widths does not fit even at one layer (20.90G
# of 15.75G HBM, compiled for v5e). This configuration serves only that
# comparison; the widths are tested by the mesh run below.
FOUR_SEEDS = (0, 1, 2)
FOUR_SEQ_LEN = 1024
# Bounds of the comparison, mesh vs host, set from readings (PERF.md).
# Unfaulted, on four v5e chips over seeds 0-2, the losses differed by at
# most 2.81e-05 relative and AdamW's first moment by 5.33e-03 relative L2
# (f32 matmuls take single bf16 passes at the default precision, and the
# two programs round in different places). Faults planted in the mesh
# path, on four CPU devices: one worker's gradient rows left out of the
# mean moved the losses by 2.9e-03 and 3.5e-03 and the moment by 0.39; one
# worker's rows left out of the sign exchange moved them by 2.3e-03 and
# 0.041, or broke the first-step sign identity; no exchange at all broke
# it on every seed. Summing the workers' gradients instead of averaging
# them moves nothing, because AdamW's global-norm clip (1.0) gives the
# same update.
LOSS_RTOL = 1e-3
MOMENT_RTOL = 1.5e-2
# The mesh path alone at phi3's published widths, cut in depth only. With
# one worker row of the f32 pair stash per chip, FSDP-sharded block params
# and replicated embeddings, the compiled step needs per chip (arguments +
# temporaries, outputs aliased; memory_analysis compiled for a v5e 2x2
# mesh) 10.75 GiB at 2 layers, 12.95 GiB at 3 and 15.05 GiB at 4, of
# 15.75 GiB: 3 is the deepest that leaves room for the params' copy-in.
FOUR_WIDE_LAYERS = 3
FOUR_WIDE_SEQ_LEN = SEQ_LEN


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


GIB = 2.0 ** 30


def _run(loss_fn, params, ds, loop_cfg, grab_cfg, hook):
    """``run_training`` with AdamW and a cosine schedule; returns the final
    state, the loss history and the loop's metrics summary (timers and the
    compiled step's ``step.*_bytes`` gauges)."""
    from repro.obs import MetricsRegistry
    from repro.optim import adamw, cosine
    from repro.train import run_training

    total = loop_cfg.epochs * len(ds) // (MICRO * loop_cfg.n_micro)
    loop_cfg.metrics = MetricsRegistry()
    state, hist = run_training(loss_fn, params, adamw(),
                               cosine(LR, total, warmup=1), ds, MICRO,
                               loop_cfg, grab_cfg=grab_cfg, hooks=hook)
    return state, hist, loop_cfg.metrics.summary()


def _step_bytes(tag, summary, limit) -> None:
    """Print the device bytes of the step the loop compiled."""
    b = {p: summary["gauges"][f"step.{p}_bytes"]["last"]
         for p in ("argument", "temp", "output", "alias")}
    need = b["argument"] + b["temp"] + b["output"] - b["alias"]
    print(f"[{tag}] compiled step per device: arguments "
          f"{b['argument'] / GIB:.2f} GiB, temporaries {b['temp'] / GIB:.2f} "
          f"GiB, outputs {b['output'] / GIB:.2f} GiB of which aliased "
          f"{b['alias'] / GIB:.2f} GiB; {need / GIB:.2f} GiB of the device's "
          f"{limit / GIB:.2f} GiB")


def _step_time(tag, arm, summary) -> None:
    t = summary["timers"]["phase.step"]
    print(f"[{tag}] {arm} host step time p50 {t['p50_s']:.3f}s (the loop's "
          f"phase.step timer, a P2 estimate) over {t['count']} steps (smoke "
          f"observation, first step includes compilation)")


def _first_loss_band(vocab):
    # lm.init_lm draws lm_head ~ N(0, 1/d_model) and the final RMS norm gives
    # unit-RMS features, so the initial logits are ~N(0, 1) per entry and
    # E[loss] = E[logsumexp] - E[gold logit] ~ ln(V) + 1/2: the band is
    # [ln V, ln V + 1].
    return math.log(vocab), math.log(vocab) + 1.0


def _host_params(jax, cfg, seed):
    """Random params on the host: run_training copies them onto the
    device(s) itself, so the script holds no second copy on the chip."""
    from repro.models import lm
    return jax.device_get(lm.init_lm(jax.random.PRNGKey(seed), cfg))


def train_phase(jax, dev) -> None:
    from repro.configs import get_config
    from repro.core.grab import GrabConfig
    from repro.core.orderings import make_policy
    from repro.data.synthetic import SyntheticTextDataset
    from repro.models import lm
    from repro.train import LoopConfig

    full, _ = get_config(ARCH)
    cfg = full.with_(n_layers=N_LAYERS)
    print(f"[train] {ARCH}: d_model {cfg.d_model}, {cfg.n_heads} heads x "
          f"{cfg.hd} (kv {cfg.n_kv_heads}), d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab} padded to {cfg.padded_vocab}, {cfg.param_dtype} "
          f"params, n_layers {cfg.n_layers} of {full.n_layers}")
    params = _host_params(jax, cfg, SEED)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_total = N_MICRO * STEPS_PER_EPOCH
    ds = SyntheticTextDataset(MICRO * n_total, SEQ_LEN, cfg.vocab, seed=SEED)
    print(f"[train] {n_params / 1e6:.1f}M params; {len(ds)} rows of "
          f"{SEQ_LEN} tokens; micro {MICRO} x n_micro {N_MICRO} -> "
          f"{STEPS_PER_EPOCH} steps/epoch, {EPOCHS} epochs; remat on")
    loss_fn = lambda p, mb: lm.loss_fn(p, cfg, mb, remat=True)
    grab_cfg = GrabConfig(sketch_dim=0)      # full-pytree Algorithm 4

    signs = {}

    def keep_signs(epoch, state, _hist):
        signs[epoch] = np.asarray(jax.device_get(state.signs))

    band = _first_loss_band(cfg.vocab)
    with tempfile.TemporaryDirectory() as tmp:
        order_path = os.path.join(tmp, "order.npy")
        loop_cfg = LoopConfig(epochs=EPOCHS, n_micro=N_MICRO, ordering="grab",
                              log_every=1, seed=SEED, export_order=order_path)
        state, hist, summary = _run(loss_fn, params, ds, loop_cfg, grab_cfg,
                                    keep_signs)
        exported = np.load(order_path)
    del state
    stats = dev.memory_stats()
    _step_bytes("train", summary, stats["bytes_limit"])
    losses = [h["loss"] for h in hist]
    print(f"[train] grab losses: {losses}")
    _step_time("train", "grab", summary)
    if "peak_bytes_in_use" in stats:
        print(f"[train] device peak bytes in use "
              f"{stats['peak_bytes_in_use'] / GIB:.2f} GiB")
    check(len(losses) == EPOCHS * STEPS_PER_EPOCH,
          f"grab ran {EPOCHS * STEPS_PER_EPOCH} steps")
    check(all(math.isfinite(x) for x in losses), "every grab loss is finite")
    check(band[0] <= losses[0] <= band[1],
          f"first grab loss {losses[0]:.4f} in [ln {cfg.vocab}, "
          f"ln {cfg.vocab} + 1] = [{band[0]:.3f}, {band[1]:.3f}]")
    for ep in range(EPOCHS):
        check(signs[ep].shape == (n_total, 1)
              and set(np.unique(signs[ep]).tolist()) <= {-1, 1},
              f"epoch {ep} sign buffer {signs[ep].shape} holds only +-1")
    # replay the host policy with the chip's signs: the Algorithm-3 reorder
    # must move the order, and the replay must land on the order the loop
    # itself exported after the last epoch
    policy = make_policy("grab", n_total, seed=SEED, pair=False)
    orders = [np.array(policy.epoch_order(0))]
    for ep in range(EPOCHS):
        policy.apply_epoch_signs(ep, signs[ep])
        orders.append(np.array(policy.epoch_order(ep + 1)))
    print(f"[train] epoch orders: {[o.tolist() for o in orders]}")
    check(not np.array_equal(orders[0], orders[1]),
          "the epoch-1 order differs from the epoch-0 order")
    check(np.array_equal(orders[-1], exported),
          "the replayed order equals the loop's exported order")

    rr_cfg = LoopConfig(epochs=1, n_micro=N_MICRO, ordering="rr",
                        log_every=1, seed=SEED)
    state, hist, summary = _run(loss_fn, params, ds, rr_cfg, None, None)
    del state
    losses = [h["loss"] for h in hist]
    print(f"[train] rr losses: {losses}")
    _step_time("train", "rr", summary)
    check(len(losses) == STEPS_PER_EPOCH and all(math.isfinite(x)
                                                 for x in losses),
          f"rr ran {STEPS_PER_EPOCH} steps with finite losses")
    check(band[0] <= losses[0] <= band[1],
          f"first rr loss {losses[0]:.4f} in [{band[0]:.3f}, {band[1]:.3f}]")


def kernel_phase(jax) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def compare(name, got, want):
        sg, s_out = got
        sw, s_want = want
        check(np.array_equal(np.asarray(sg), np.asarray(sw).astype(np.int32)),
              f"{name}: signs equal the oracle's")
        # each step adds +-z exactly and rounds the add as the oracle does,
        # so once the signs agree the sums agree to f32 rounding
        np.testing.assert_allclose(np.asarray(s_out), np.asarray(s_want),
                                   rtol=1e-6, atol=1e-6)
        print(f"  ok: {name}: running sum equals the oracle's (rtol 1e-6)")

    w, k = 8, 1024
    s0, g = normal(k), normal(w, k)
    compare(f"balance_scan m={w} k={k}",
            jax.jit(ops.balance_scan)(s0, g),
            jax.jit(ref.balance_scan_ref)(s0, g))
    zp, zc = normal(w, k), normal(w, k)
    check(ops.select_coord_impl(w, k) == ("plain", None),
          f"coord_balance W={w} k={k} takes the plain kernel")
    compare(f"coord_balance W={w} k={k}",
            jax.jit(ops.coord_balance)(s0, zp, zc),
            jax.jit(ref.coord_balance_ref)(s0, zp, zc))
    kc = 131072
    impl = ops.select_coord_impl(w, kc)
    check(impl[0] == "chunked", f"coord_balance W={w} k={kc} takes the "
          f"chunked kernel (chunk_k {impl[1]})")
    s0c, zpc, zcc = normal(kc), normal(w, kc), normal(w, kc)
    compare(f"coord_balance chunked W={w} k={kc}",
            jax.jit(ops.coord_balance)(s0c, zpc, zcc),
            jax.jit(ref.coord_balance_ref)(s0c, zpc, zcc))

    B, H, T, D = 2, 16, 512, 64                 # B*H = 32
    q, kk, v = normal(B, H, T, D), normal(B, H, T, D), normal(B, H, T, D)
    wd = jnp.asarray(rng.uniform(0.4, 1.0, size=(B, H, T, D)), jnp.float32)
    u = normal(H, D)
    for post in (False, True):
        got = jax.jit(lambda *a: ops.gla_scan(*a, post_update=post))(
            q, kk, v, wd, u)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: ref.gla_scan_ref(*a, post_update=post))(
                q, kk, v, wd, u)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        # both sides accumulate in f32 (the oracle at "highest" matmul
        # precision, so no bf16 pass): they differ only in summation order,
        # ~T * 2^-24 relative over a T=512 scan, far below 1e-4
        check(err <= 1e-4, f"gla_scan B*H={B * H} T={T} dk=dv={D} "
              f"post_update={post}: max error {err:.2e} of max |o| <= 1e-4")


def cd_grab_run(jax, cfg, seq_len, seed, mesh, *, keep_moment=True):
    """One cd-grab job (W=4, sketch k=1024, int8 wire) through
    ``run_training``, on ``mesh`` or, with ``mesh=None``, on the
    host-simulated path. Returns its losses, per-epoch sign buffers, the
    stash layout, the metrics summary and (``keep_moment``) AdamW's first
    moment on the host."""
    from repro.core.grab import GrabConfig
    from repro.data.synthetic import SyntheticTextDataset
    from repro.models import lm
    from repro.train import LoopConfig

    n_total = FOUR_N_MICRO * FOUR_STEPS_PER_EPOCH
    ds = SyntheticTextDataset(MICRO * n_total, seq_len, cfg.vocab, seed=seed)
    loss_fn = lambda p, mb: lm.loss_fn(p, cfg, mb, remat=True)
    grab_cfg = GrabConfig(pair_balance=True, sketch_dim=FOUR_SKETCH,
                          sign_wire="int8")
    signs = {}

    def keep_signs(epoch, state, _hist):
        signs[epoch] = np.asarray(jax.device_get(state.signs))

    loop_cfg = LoopConfig(epochs=FOUR_EPOCHS, n_micro=FOUR_N_MICRO,
                          ordering="cd-grab", workers=FOUR_W,
                          mesh=mesh, log_every=1, seed=seed)
    state, hist, summary = _run(loss_fn, _host_params(jax, cfg, seed), ds,
                                loop_cfg, grab_cfg, keep_signs)
    run = {"losses": np.array([h["loss"] for h in hist]), "signs": signs,
           "summary": summary,
           "stash": [(x.sharding, x.addressable_shards[0].data.shape, x.shape)
                     for x in jax.tree.leaves(state.grab.m_acc)]}
    if keep_moment:
        run["moment"] = jax.device_get(state.opt.m)
    del state
    return run


def _check_cd_signs(tag, signs) -> None:
    """cd-grab's [T, W] buffer: stash timesteps (even t) hold zeros, the
    balancing timesteps (odd t) only +-1."""
    for ep, buf in signs.items():
        check(buf.shape == (FOUR_N_MICRO * FOUR_STEPS_PER_EPOCH // FOUR_W,
                            FOUR_W)
              and not buf[0::2].any()
              and set(np.unique(buf[1::2]).tolist()) <= {-1, 1},
              f"{tag} epoch {ep} sign buffer {buf.shape}: zero stash rows, "
              f"+-1 balance rows")


def _check_stash(tag, stash) -> None:
    for sharding, shard_shape, shape in stash:
        spec = getattr(sharding, "spec", None)
        if not (spec and spec[0] == "data"
                and shard_shape[0] == shape[0] // FOUR_W):
            raise SmokeFailure(f"{tag} stash leaf {shape} is not sharded over "
                               f"'data': {sharding}")
    print(f"  ok: {tag}: the compiled step returns the [{FOUR_W}, ...] worker "
          f"stash sharded over 'data' ({len(stash)} leaves, one worker row "
          f"per chip)")


def _rel_l2(a_tree, b_tree, jax) -> float:
    a = np.concatenate([np.ravel(x).astype(np.float64)
                        for x in jax.tree.leaves(a_tree)])
    b = np.concatenate([np.ravel(x).astype(np.float64)
                        for x in jax.tree.leaves(b_tree)])
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare_mesh_host(jax, cfg, seq_len, seed, mesh) -> dict:
    """The same cd-grab job on the mesh and on the host-simulated path;
    checks the comparison and returns its readings."""
    mesh_run = cd_grab_run(jax, cfg, seq_len, seed, mesh)
    host_run = cd_grab_run(jax, cfg, seq_len, seed, None)
    tag = f"seed {seed}"
    print(f"[four-chip] {tag} losses mesh {mesh_run['losses'].tolist()} "
          f"host {host_run['losses'].tolist()}")
    _check_cd_signs(f"{tag} mesh", mesh_run["signs"])
    t_step = FOUR_N_MICRO // FOUR_W
    first = [r["signs"][0][:t_step] for r in (mesh_run, host_run)]
    print(f"[four-chip] {tag} first-step signs mesh {first[0].tolist()} "
          f"host {first[1].tolist()}")
    # the first step starts from the same params and data on both paths,
    # so its balance decisions must match bit for bit
    check(np.array_equal(first[0], first[1]),
          f"{tag}: the first step's signs are bit-identical, mesh vs host")
    # after that the params differ by rounding (the two programs partition
    # and fuse the math differently), so a decision that sits within
    # rounding of a tie may legitimately flip: the later signs are counted,
    # and the losses and AdamW's moment are held to measured bounds instead
    both = [np.concatenate([r["signs"][e][1::2].ravel()
                            for e in range(FOUR_EPOCHS)])
            for r in (mesh_run, host_run)]
    agree = float(np.mean(both[0] == both[1]))
    rel_loss = float(np.max(np.abs(mesh_run["losses"] - host_run["losses"])
                            / np.abs(host_run["losses"])))
    rel_m = _rel_l2(mesh_run["moment"], host_run["moment"], jax)
    print(f"[four-chip] {tag} readings: balance decisions equal in "
          f"{agree:.4f} of {both[0].size}; losses max relative difference "
          f"{rel_loss:.3e}; AdamW first moment relative L2 difference "
          f"{rel_m:.3e}")
    check(np.all(np.isfinite(mesh_run["losses"])) and rel_loss <= LOSS_RTOL,
          f"{tag}: losses agree within {LOSS_RTOL:g} relative")
    check(rel_m <= MOMENT_RTOL,
          f"{tag}: AdamW first moments agree within {MOMENT_RTOL:g} relative")
    _check_stash(f"{tag} mesh", mesh_run["stash"])
    return {"agree": agree, "loss": rel_loss, "moment": rel_m}


def wide_mesh_run(jax, cfg, seq_len, mesh) -> None:
    """The mesh path alone at ``cfg``'s widths."""
    from repro.models import lm

    n_params = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: lm.init_lm(jax.random.PRNGKey(SEED), cfg))))
    print(f"[four-chip] mesh at published widths: {cfg.name} d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads x {cfg.hd}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab} padded to {cfg.padded_vocab}, "
          f"{cfg.param_dtype} params, n_layers {cfg.n_layers}; "
          f"{n_params / 1e6:.1f}M params; {seq_len}-token rows, micro "
          f"{MICRO} x n_micro {FOUR_N_MICRO}, {FOUR_STEPS_PER_EPOCH} "
          f"steps/epoch, {FOUR_EPOCHS} epochs")
    run = cd_grab_run(jax, cfg, seq_len, SEED, mesh, keep_moment=False)
    devs = jax.devices()
    _step_bytes("four-chip", run["summary"],
                devs[0].memory_stats()["bytes_limit"])
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devs]
    if None not in peaks:
        print(f"[four-chip] device peak bytes in use "
              f"{[round(p / GIB, 2) for p in peaks]} GiB")
    losses = run["losses"]
    print(f"[four-chip] mesh losses: {losses.tolist()}")
    _step_time("four-chip", "cd-grab mesh", run["summary"])
    band = _first_loss_band(cfg.vocab)
    check(len(losses) == FOUR_EPOCHS * FOUR_STEPS_PER_EPOCH
          and np.all(np.isfinite(losses)),
          f"the mesh run's {len(losses)} losses are finite")
    check(band[0] <= losses[0] <= band[1],
          f"first loss {losses[0]:.4f} in [ln {cfg.vocab}, ln {cfg.vocab} "
          f"+ 1] = [{band[0]:.3f}, {band[1]:.3f}]")
    _check_cd_signs("wide mesh", run["signs"])
    _check_stash("wide mesh", run["stash"])


def four_chip_phase(jax) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_elastic_mesh

    check(jax.device_count() == FOUR_W, f"{jax.device_count()} devices present")
    mesh = make_elastic_mesh(model_parallel=1)
    full, smoke = get_config(ARCH)
    print(f"[four-chip] mesh vs host: {smoke.name} smoke config, d_model "
          f"{smoke.d_model}, n_layers {smoke.n_layers}, vocab {smoke.vocab}; "
          f"cd-grab W={FOUR_W}, sketch k={FOUR_SKETCH}, int8 wire; "
          f"{FOUR_SEQ_LEN}-token rows, {FOUR_STEPS_PER_EPOCH} steps/epoch, "
          f"{FOUR_EPOCHS} epochs; seeds {list(FOUR_SEEDS)}")
    readings = [compare_mesh_host(jax, smoke, FOUR_SEQ_LEN, seed, mesh)
                for seed in FOUR_SEEDS]
    print(f"[four-chip] largest over seeds: losses "
          f"{max(r['loss'] for r in readings):.3e} (bound {LOSS_RTOL:g}), "
          f"AdamW moment {max(r['moment'] for r in readings):.3e} (bound "
          f"{MOMENT_RTOL:g}); balance decisions equal in at least "
          f"{min(r['agree'] for r in readings):.4f}")
    wide_mesh_run(jax, full.with_(n_layers=FOUR_WIDE_LAYERS),
                  FOUR_WIDE_SEQ_LEN, mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip cd-grab mesh path and its "
                         "host-simulated comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 1
    from repro.utils.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache(REPO)
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {jax.device_count()}")
    print(f"jax: {jax.__version__}")
    print(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    if args.four_chip:
        four_chip_phase(jax)
    else:
        train_phase(jax, dev)
        kernel_phase(jax)
    print(f"phases done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
