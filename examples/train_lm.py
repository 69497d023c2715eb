"""End-to-end LM training driver with GraB ordering.

    PYTHONPATH=src python examples/train_lm.py --preset cpu-smoke
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

``cpu-smoke`` (default) trains a ~2M-param decoder for a few epochs on this
box; ``100m`` is the deliverable configuration (~100M params, a few hundred
steps) sized for a real accelerator. Both run the full production path:
synthetic corpus -> permuted loader -> fused-GraB microbatch train step ->
checkpointing -> (optional) resume.
"""
import argparse
import os

import jax
import numpy as np

from repro.core.grab import GrabConfig
from repro.data.sources import MemmapShardDataset, write_shards
from repro.data.synthetic import SyntheticTextDataset
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import adamw, cosine
from repro.train import LoopConfig, run_training
from repro.utils.compile_cache import setup_compile_cache

PRESETS = {
    "cpu-smoke": dict(
        model=ModelConfig(name="smoke-lm", n_layers=2, d_model=128, n_heads=4,
                          n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
                          param_dtype="float32"),
        n_examples=64, seq_len=64, micro=2, n_micro=4, epochs=3, lr=3e-3),
    "100m": dict(
        model=ModelConfig(name="lm-100m", n_layers=12, d_model=768, n_heads=12,
                          n_kv_heads=12, head_dim=64, d_ff=3072, vocab=32768,
                          param_dtype="bfloat16"),
        n_examples=2048, seq_len=1024, micro=8, n_micro=8, epochs=2, lr=3e-4),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="cpu-smoke")
    ap.add_argument("--ordering", default="grab",
                    choices=["grab", "cd-grab", "rr", "so", "flipflop"])
    ap.add_argument("--workers", type=int, default=1,
                    help="cd-grab: W logical data-parallel workers")
    ap.add_argument("--mesh", action="store_true",
                    help="run the launcher path: an elastic data-parallel "
                         "mesh over all local devices (force several CPU "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N), "
                         "explicit in_shardings + the hillclimb-winning "
                         "cd-grab constraint set, donated device state")
    ap.add_argument("--sketch-dim", type=int, default=0,
                    help="GraB sketch width k (0 = full-pytree balance; "
                         "cd-grab on a mesh uses k for the sign all-gather)")
    ap.add_argument("--sign-wire", default="f32", choices=["f32", "int8"],
                    help="cd-grab coordination wire: int8 packs the [W, k] "
                         "sketched rows to [W, k+4] int8 before the gather "
                         "(~4x fewer bytes, bit-identical signs on every "
                         "shard) and defers the exchange to one "
                         "overlappable gather per step on the mesh path")
    ap.add_argument("--sign-hier", type=int, default=0,
                    help="two-stage sign gather: group size L for the "
                         "intra-host stage (0 = flat single-stage gather)")
    ap.add_argument("--data", default="synthetic",
                    help="data source: 'synthetic' (the preset's in-memory "
                         "counter-based corpus) or 'shards:<dir>' (on-disk "
                         "memmap .npy shards written by --write-shards; "
                         "manifest checksums are validated on open)")
    ap.add_argument("--write-shards", default=None, metavar="DIR",
                    help="materialize the preset's synthetic corpus to "
                         "on-disk .npy shards + manifest in DIR, then exit "
                         "— train from them with --data shards:DIR")
    ap.add_argument("--shard-size", type=int, default=None,
                    help="examples per shard for --write-shards "
                         "(default: one quarter of the corpus)")
    ap.add_argument("--loader-workers", type=int, default=2,
                    help="window-prefetch assembly pool size")
    ap.add_argument("--loader-window", type=int, default=4,
                    help="order_slice prefetch horizon, in optimizer steps")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--export-order", default=None, metavar="PATH.npy",
                    help="after training, save the final learned order "
                         "(e.g. GraB's last sigma) as a portable .npy "
                         "permutation artifact")
    ap.add_argument("--fixed-order", default=None, metavar="PATH.npy",
                    help="replay a frozen permutation artifact (written by "
                         "--export-order) every epoch — overrides "
                         "--ordering; the retrain-from-GraB ablation path")
    ap.add_argument("--metrics-out", default=None,
                    help="write the structured run log (schema-validated "
                         "JSONL: run_meta + per-epoch timers/quality "
                         "metrics + events) to this path")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="capture a JAX profiler trace for global steps "
                         "[A, B) (after compile/warm-up; view with "
                         "tensorboard or perfetto)")
    ap.add_argument("--profile-dir", default="profile_trace",
                    help="directory for the --profile-steps trace")
    args = ap.parse_args()
    setup_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

    p = PRESETS[args.preset]
    cfg = p["model"]
    if args.write_shards:
        src = SyntheticTextDataset(p["n_examples"], p["seq_len"], cfg.vocab,
                                   seed=0)
        shard = args.shard_size or max(1, len(src) // 4)
        manifest = write_shards(src, args.write_shards, shard_size=shard)
        print(f"wrote {len(src)} examples as shards of {shard} to "
              f"{manifest} — train from them with "
              f"--data shards:{args.write_shards}")
        return
    if args.data.startswith("shards:"):
        ds = MemmapShardDataset(args.data[len("shards:"):])
    elif args.data == "synthetic":
        ds = SyntheticTextDataset(p["n_examples"], p["seq_len"], cfg.vocab,
                                  seed=0)
    else:
        raise SystemExit(f"unknown --data {args.data!r}: expected "
                         f"'synthetic' or 'shards:<dir>'")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_elastic_mesh
        mesh = make_elastic_mesh(model_parallel=1)
    print(f"model {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"{len(ds)} examples of {p['seq_len']} tokens, "
          f"ordering={args.ordering}"
          + (f", mesh={dict(mesh.shape)}" if mesh is not None else ""))

    loss_fn = lambda prm, mb: lm.loss_fn(prm, cfg, mb, remat=True)
    steps_per_epoch = len(ds) // (p["micro"] * p["n_micro"])
    total = (args.epochs or p["epochs"]) * steps_per_epoch
    loop = LoopConfig(epochs=args.epochs or p["epochs"], n_micro=p["n_micro"],
                      ordering=args.ordering, workers=args.workers,
                      sign_wire=args.sign_wire, sign_hier=args.sign_hier,
                      ckpt_dir=args.ckpt_dir, log_every=10, mesh=mesh,
                      loader_workers=args.loader_workers,
                      loader_window=args.loader_window,
                      export_order=args.export_order,
                      fixed_order=args.fixed_order,
                      metrics_out=args.metrics_out,
                      profile_steps=args.profile_steps,
                      profile_dir=args.profile_dir)
    grab_cfg = None
    if args.ordering in ("grab", "cd-grab") and not args.fixed_order:
        grab_cfg = GrabConfig(pair_balance=args.ordering == "cd-grab",
                              sketch_dim=min(args.sketch_dim, n_params),
                              sign_wire=args.sign_wire,
                              sign_hier=args.sign_hier)
    state, hist = run_training(loss_fn, params, adamw(),
                               cosine(p["lr"], total, warmup=total // 20),
                               ds, p["micro"], loop, grab_cfg=grab_cfg)
    per_epoch = {}
    for h in hist:
        per_epoch.setdefault(h["epoch"], []).append(h["loss"])
    for ep, v in sorted(per_epoch.items()):
        print(f"epoch {ep}: mean loss {np.mean(v):.4f}")


if __name__ == "__main__":
    main()
