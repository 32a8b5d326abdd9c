#!/usr/bin/env python3
"""Find the served cell's knee: the highest arrival rate it sustains.

One process builds the served cell's system once (set-up and warm-up as a
benchmark run makes them), then measures the time to solution of one lone
request of the tolerance cell (``--solo-traffic``), the base of the
latency limit, and then offers the cell's traffic at each rate of
``--rates`` for ``--seconds`` each. For each rate it prints the 50th and
90th percentile latency (missing requests count as infinite), the
requests still in the system at half the window and at its close (a
backlog that grows between the two is not sustained), and whether both
limits hold. The knee is the highest rate at which they hold and hold at
every lower rate swept; the served cell runs at ``--fraction`` of it.

    python3 bench/knee_sweep.py --rates 1,2,3,4,5 --seconds 20

Needs a TPU. The last line of its output is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def in_system(arrivals, finish, at: float) -> int:
    return sum(1 for a, f in zip(arrivals, finish) if a <= at < f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="jacobi2d-paper-f32.serve")
    ap.add_argument("--solo-traffic", default="tol")
    ap.add_argument("--rates", default="1,2,3,4,5")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--limit-multiple", type=float, default=10.0)
    ap.add_argument("--fraction", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=20261017)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench import loads, harness
    cell = harness.cell_from_files(args.workload)
    try:
        devs = harness.use_chips(cell.chips)
    except harness.NoChip as e:
        print(f"knee_sweep: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, harness.SRC)
    system = loads.System(cell.config, devs)
    solo = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                          args.solo_traffic + ".json"))
    grids = system.pool(args.seed, 3)
    system.to_tol(grids[0], solo["tol"], solo["max_iters"])
    walls = []
    for g in grids:
        t0 = time.perf_counter()
        system.to_tol(g, solo["tol"], solo["max_iters"])
        walls.append(time.perf_counter() - t0)
    solo_s = statistics.median(walls)
    limit = args.limit_multiple * solo_s
    print(f"solo time to solution {solo_s:.4f} s; latency limit "
          f"{limit:.4f} s", flush=True)

    d = loads.OpenServe(system, cell.traffic)
    d.setup(args.seed, args.seconds)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        d.schedule(args.seed, args.seconds, rate)
        d.window(args.seconds, grace_s=args.seconds)
        finish = [a + lat for a, lat in zip(d.arrivals, d.latencies)]
        half = in_system(d.arrivals, finish, args.seconds / 2)
        end = in_system(d.arrivals, finish, args.seconds)
        p90 = loads.percentile(d.latencies, 90)
        row = {"rate": rate, "requests": len(d.arrivals),
               "p50_s": loads.percentile(d.latencies, 50), "p90_s": p90,
               "in_system_half": half, "in_system_close": end,
               "unfinished": d.failed,
               "launches_per_request": d.counters["launches"] / max(
                   1, d.counters["completed"]),
               "sustained": bool(p90 <= limit) and end <= max(2 * half, 8)
               and d.failed == 0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = None
    for r in sorted(rows, key=lambda r: r["rate"]):
        if not r["sustained"]:
            break
        knee = r["rate"]
    print(json.dumps({"solo_s": solo_s, "limit_s": limit, "knee": knee,
                      "cell_rate": knee and args.fraction * knee,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
