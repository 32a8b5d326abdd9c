#!/usr/bin/env python3
"""Measure the chip's float32 vector peak for ``bench/peaks.json``.

The vector unit's float32 rate is not published for every chip, so it is
measured: a Pallas kernel keeps a tile of float32 values in vector
registers and updates it ``x = x * a + b`` many times (two operations per
element per step: one multiply, one add), over a grid of tiles sized so
that every variant does the same 2.7e11 operations per call (about 45 ms
at 6e12 operations a second). Several tile heights,
numbers of independent chains and unroll factors are tried; the best is
the peak. Each variant is timed ``--repeats`` times after a warm-up call;
the best variant's spread is reported as the distance between its first
and third quartile over its median.

    python3 bench/calibrate_peak.py [--repeats 7]

Needs a TPU; prints one JSON object, the last line of its output.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time


def variant(rows: int, chains: int, unroll: int, steps: int, tiles: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(ab_ref, x_ref, o_ref):
        a, b = ab_ref[0], ab_ref[1]
        x = x_ref[...]

        def body(_, cs):
            for _ in range(unroll):  # Mosaic's loops do not unroll
                cs = tuple(c * a + b for c in cs)
            return cs

        cs = jax.lax.fori_loop(0, steps // unroll, body,
                               tuple(x + float(j) for j in range(chains)))
        o_ref[...] = functools.reduce(lambda p, q: p + q, cs)

    call = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows * tiles, 128), jnp.float32),
    )
    ops = 2 * steps * chains * rows * 128 * tiles
    return jax.jit(call), ops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print(f"calibrate_peak: needs a TPU; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    ab = jnp.asarray([0.999, 0.001], jnp.float32)
    steps = 8192
    rows_of = {}
    for rows in (8, 32, 64):
        for chains in (1, 2, 4, 8):
            if rows * chains > 256:
                continue  # more live values than the vector registers hold
            for unroll in (1, 8):
                tiles = 512 * 256 // (rows * chains)  # same ops per call
                fn, ops = variant(rows, chains, unroll, steps, tiles)
                x = jnp.ones((rows * tiles, 128), jnp.float32)
                fn(ab, x).block_until_ready()
                walls = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    fn(ab, x).block_until_ready()
                    walls.append(time.perf_counter() - t0)
                rates = sorted(ops / w for w in walls)
                q1, q2, q3 = statistics.quantiles(rates, n=4)
                key = f"rows{rows}_chains{chains}_unroll{unroll}"
                rows_of[key] = {"best_ops_per_s": rates[-1],
                                "median_ops_per_s": q2,
                                "iqr_over_median": (q3 - q1) / q2,
                                "wall_s_median": statistics.median(walls)}
                print(key, json.dumps(rows_of[key]), flush=True)
    best = max(rows_of, key=lambda k: rows_of[k]["median_ops_per_s"])
    print(json.dumps({"device_kind": dev.device_kind,
                      "vector_f32_ops_per_s": rows_of[best][
                          "median_ops_per_s"],
                      "best_variant": best,
                      "spread": rows_of[best]["iqr_over_median"],
                      "repeats": args.repeats, "variants": rows_of}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
