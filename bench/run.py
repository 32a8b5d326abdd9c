#!/usr/bin/env python3
"""On-chip benchmark of the stencil-solve system: one run of one cell.

    python3 bench/run.py --workload jacobi2d-paper-f32.fixed5000 \\
        --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for. ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` traces the window with the profiler and prints its
per-layer metrics, the device's busy time and a breakdown. Both compare
what the window produced with the plain reference and say whether it was
``correct``. The last line of standard output is one JSON object. Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
