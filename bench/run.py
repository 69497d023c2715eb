"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload phi3.grab.s512 --seed 7 --seconds 10 \
        --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared with
the reference beside its limit. The same numbers end standard error. Without
a TPU, or with fewer chips than the cell needs, it exits 2 and prints no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(out["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
