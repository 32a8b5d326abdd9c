#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

For one cell, in one process: build the system and warm it once, then for
each seed make that seed's inputs, run a short window at the cell's own
load, and read the comparison's numbers twice on the same sampled
answers:

* ``program``: what the program produced (the lower readings);
* ``control``: the configuration's plain reference computed in the
  precision one step below (``control_dtype``) put in the program's
  place (the upper readings). The control must come out not correct.

    python3 bench/control.py --workload jacobi2d-paper-f32.fixed5000 \\
        --seeds 1,2,3 --seconds 3

Needs the cell's chips. Prints one JSON line per seed, and a summary as
the last line: the largest program reading and the smallest control
reading of each number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench import loads, harness, verify
    cell = harness.cell_from_files(args.workload)
    try:
        devs = harness.use_chips(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, harness.SRC)
    system = loads.System(cell.config, devs)
    d = loads.LOADS[cell.traffic["kind"]](system, cell.traffic)
    seeds = [int(s) for s in args.seeds.split(",")]
    d.setup(seeds[0], args.seconds)
    lower: dict = {}
    upper: dict = {}
    for seed in seeds:
        d.reseed(seed, args.seconds)
        d.window(args.seconds)
        prog = verify.numbers(d.answers, cell.config, system.devices[0],
                              missing=d.failed)
        ctl = verify.numbers(
            verify.control_answers(d.answers, cell.config,
                                   system.devices[0]),
            cell.config, system.devices[0], missing=0)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in ctl.items():
            upper[k] = min(upper.get(k, v), v)
        ok, _ = verify.judge(prog, cell.config["limits"])
        ctl_ok, _ = verify.judge(ctl, cell.config["limits"])
        print(json.dumps({"seed": seed, "answers": len(d.answers),
                          "sweeps": [a["sweeps"] for a in d.answers],
                          "program": prog, "program_correct": ok,
                          "control": ctl, "control_correct": ctl_ok}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "lower": lower, "upper": upper,
                      "limits": cell.config["limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
