"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload phi3.grab.s512 --mode program \
        --seeds 11 12 13 ...
    python3 bench/calibrate.py --workload phi3-dp4.cdgrab.s1k \
        --mode control half sign flip --seeds 11 12 13

``program``: whole runs of the cell (set-up, a window of one epoch, the
comparison) for each seed, in one process: the lower readings.
``control``: the reference put in the program's place, computed in float8
(``reference.py``, ``precision="fp8"``), against the float32 reference.
Every stand-in and the reference that judges it run the configuration's
model module (``Layout.model``).
``half``: the reference in the program's place with half of each step's
microbatches left out and the mean taken over the rest (a planted fault;
in a CD-GraB cell the last half of the workers of every timestep).
``sign`` (CD-GraB cells): the reference in the program's place with worker
0's row left out of every sign scan (a planted fault of the exchange).
``flip`` (CD-GraB cells): the reference in the program's place with the
sign rule inverted, -1 where <s, z> <= 0 (a planted fault of the balance).
A step that returns its state unchanged reads 1 on ``update_gap`` by that
number's definition and needs no run. In a CD-GraB cell the reference that
judges a stand-in takes the stand-in's signs into its running sum, as it
takes the program's; it runs once a seed for all the stand-ins.

Each seed prints one JSON line a mode: the numbers compared, and
``correct`` as the harness decides it from the cell's own limits
(``bench/limits/<cell>.json``). The control and the faults have to come out
not correct. The benchmark's own runs never run this.
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


STAND_INS = {"control": {"prec": "fp8"}, "half": {"keep": 0.5},
             "sign": {"drop_row": 0}, "flip": {"flip": True}}


def reference_in_place(root: str, workload: str, seed: int, modes,
                       devices=None) -> dict:
    """For each of ``modes`` (``control``, ``half``, ``sign``, ``flip``),
    the numbers compared when it stands in for the program. The reference
    that judges them runs once: in a CD-GraB cell its rows are fixed by the
    gradients alone, and each stand-in's signs are forced into its sum by
    :func:`reference.cd_scan` of them, as the program's are."""
    import jax
    import numpy as np

    import data as bench_data
    import harness
    import reference
    from layout import Layout

    lay = Layout(root)
    cell = lay.cell(workload)
    cfg = lay.config(cell["config"])
    model = lay.model(cfg)
    traffic = lay.traffic(cell["traffic"])
    grab = traffic["ordering"] != "rr"
    micro, n_micro = traffic["micro"], traffic["n_micro"]
    n_units = traffic["steps_per_epoch"] * n_micro
    seed_np = seed % (2 ** 63)
    ds = bench_data.TokenRows(n_units * micro, traffic["seq_len"],
                              cfg["vocab_size"], seed_np)
    key = reference.make_key(seed)
    init = jax.jit(lambda k: model.init_params(k, cfg))
    hp = traffic["optimizer"]
    out = {}
    if traffic["ordering"] == "cd-grab":
        workers = traffic["workers"]
        order = reference.cd_first_order(n_units, workers, seed_np)
        steps = harness._steps(ds, order, traffic["reference_steps"],
                               n_micro, micro)
        run = lambda **a: reference.train_steps_cd(
            lambda: init(key), steps, cfg, hp, workers=workers,
            sketch_dim=traffic["grab"]["sketch_dim"],
            devices=devices or jax.devices(), model=model, **a)
        ref = run()
        for mode in modes:
            alt = run(**STAND_INS[mode])
            s, signs = reference.cd_scan(ref["rows"], alt["signs"])
            judge = dict(ref, sum=np.asarray([np.linalg.norm(s)]),
                         signs=signs)
            out[mode] = harness.compare(alt, judge, grab)[0]
        return out
    if {"sign", "flip"} & set(modes):
        raise ValueError("the sign faults are CD-GraB cells' alone")
    steps = harness._steps(ds, reference.first_grab_order(n_units, seed_np),
                           traffic["reference_steps"], n_micro, micro)
    ref = reference.train_steps(lambda: init(key), steps, cfg, hp, grab=grab,
                                model=model)
    for mode in modes:
        alt = reference.train_steps(lambda: init(key), steps, cfg, hp,
                                    grab=grab, model=model, **STAND_INS[mode])
        out[mode] = harness.compare(alt, ref, grab)[0]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", nargs="+", required=True,
                    choices=("program",) + tuple(STAND_INS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: JAX found no TPU", file=sys.stderr)
        return 2
    from layout import Layout

    limits = Layout(ROOT).limits(args.workload)
    stand_ins = [m for m in args.mode if m != "program"]
    rows = {m: [] for m in args.mode}
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = {}
        if "program" in args.mode:
            out = harness.run(ROOT, args.workload, seed, 0.0, False, t0)
            got["program"] = (out["numbers"], out["window"]["nonfinite"])
        if stand_ins:
            got.update((m, (n, 0)) for m, n in reference_in_place(
                ROOT, args.workload, seed, stand_ins).items())
        for mode, (nums, nonfinite) in got.items():
            checks, correct = harness.decide(nums, limits, nonfinite)
            rows[mode].append((nums, correct))
            print(json.dumps({"workload": args.workload, "mode": mode,
                              "seed": seed, "numbers": nums,
                              "correct": correct,
                              "failed_checks": sorted(
                                  k for k, c in checks.items()
                                  if not c["value"] <= c["limit"]),
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    for mode, got in rows.items():
        summary = {k: {"max": max(n[k] for n, _ in got),
                       "min": min(n[k] for n, _ in got)} for k in got[0][0]}
        print(json.dumps({"workload": args.workload, "mode": mode,
                          "seeds": args.seeds, "summary": summary,
                          "correct": [c for _, c in got],
                          "limits": limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
