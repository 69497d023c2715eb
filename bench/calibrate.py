"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload phi3.grab.s512 --mode program \
        --seeds 11 12 13 ...
    python3 bench/calibrate.py --workload phi3.grab.s512 --mode control \
        --seeds 11 12 13

``program``: whole runs of the cell (set-up, a window of one epoch, the
comparison) for each seed, in one process: the lower readings.
``control``: the reference put in the program's place, computed in float8
(``reference.py``, ``precision="fp8"``), against the float32 reference.
``half``: the reference in the program's place with half of each step's
microbatches left out and the mean taken over the rest (a planted fault).
A step that returns its state unchanged reads 1 on ``update_gap`` by that
number's definition and needs no run.

Each seed prints one JSON line: the numbers compared, and ``correct`` as the
harness decides it from the cell's own limits (``bench/limits/<cell>.json``).
The control and the fault have to come out not correct. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)


def reference_in_place(root: str, workload: str, seed: int, mode: str) -> dict:
    """The numbers compared when ``mode`` (``control`` or ``half``) stands
    in for the program."""
    import jax

    import data as bench_data
    import harness
    import reference
    from layout import Layout

    lay = Layout(root)
    cell = lay.cell(workload)
    cfg = lay.config(cell["config"])
    traffic = lay.traffic(cell["traffic"])
    grab = traffic["ordering"] == "grab"
    micro, n_micro = traffic["micro"], traffic["n_micro"]
    n_units = traffic["steps_per_epoch"] * n_micro
    seed_np = seed % (2 ** 63)
    ds = bench_data.TokenRows(n_units * micro, traffic["seq_len"],
                              cfg["vocab_size"], seed_np)
    steps = harness._steps(ds, reference.first_grab_order(n_units, seed_np),
                           traffic["reference_steps"], n_micro, micro)
    key = reference.make_key(seed)
    init = jax.jit(lambda k: reference.init_params(k, cfg))
    hp = traffic["optimizer"]
    ref = reference.train_steps(lambda: init(key), steps, cfg, hp, grab=grab)
    kw = {"prec": "fp8"} if mode == "control" else {"keep": 0.5}
    alt = reference.train_steps(lambda: init(key), steps, cfg, hp, grab=grab,
                                **kw)
    return harness.compare(alt, ref, grab)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "control", "half"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: JAX found no TPU", file=sys.stderr)
        return 2
    from layout import Layout

    limits = Layout(ROOT).limits(args.workload)
    rows, verdicts = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        if args.mode == "program":
            out = harness.run(ROOT, args.workload, seed, 0.0, False, t0)
            nums, nonfinite = out["numbers"], out["window"]["nonfinite"]
        else:
            nums = reference_in_place(ROOT, args.workload, seed, args.mode)
            nonfinite = 0
        checks, correct = harness.decide(nums, limits, nonfinite)
        rows.append(nums)
        verdicts.append(correct)
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "numbers": nums, "correct": correct,
                          "failed_checks": sorted(
                              k for k, c in checks.items()
                              if not c["value"] <= c["limit"]),
                          "seconds": time.perf_counter() - t0}), flush=True)
    summary = {k: {"max": max(r[k] for r in rows),
                   "min": min(r[k] for r in rows)} for k in rows[0]}
    print(json.dumps({"workload": args.workload, "mode": args.mode,
                      "seeds": args.seeds, "summary": summary,
                      "correct": verdicts, "limits": limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
