"""Work a stencil solve must do, counted from its shapes alone.

These counts are the yardstick of every roofline share the benchmark
reports. They depend only on what the solve computes (the grid, the taps,
the sweeps), never on how a kernel does it: no kernel's own byte model,
block size or fusion depth enters here, so a kernel that is renamed,
fused deeper or replaced is measured against the same floor.

* ops: one f32 vector operation per tap, per interior point, per sweep.
  A radius-1 5-point Jacobi update with equal weights needs three adds and
  one multiply, which is its tap count; no implementation of the spec can
  do less arithmetic.
* compulsory bytes: the grid read once and written once per solve, the
  least any implementation must move through HBM.
* least time: the larger of ops over the vector peak and bytes over the
  HBM bandwidth, both over every chip the solve uses.
"""
from __future__ import annotations

import math


def interior_points(interior: tuple[int, ...]) -> int:
    return math.prod(interior)


def ringed_shape(interior: tuple[int, ...], radius: int) -> tuple[int, ...]:
    """The interior with a ring of ``radius`` points on both sides of every
    axis."""
    return tuple(n + 2 * radius for n in interior)


def sweep_ops(points: int, sweeps: int, taps: int) -> int:
    """Vector operations of ``sweeps`` sweeps over ``points`` points."""
    return points * sweeps * taps


def compulsory_bytes(ringed: tuple[int, ...], dtype_bytes: int,
                     solves: int) -> int:
    """Read the ringed grid once and write it once, per solve."""
    return 2 * math.prod(ringed) * dtype_bytes * solves


def least_time_s(ops: float, nbytes: float, *, vector_ops_per_s: float,
                 hbm_bytes_per_s: float, chips: int) -> tuple[float, str]:
    """(least seconds on ``chips`` chips, which bound sets it)."""
    t_ops = ops / (vector_ops_per_s * chips)
    t_mem = nbytes / (hbm_bytes_per_s * chips)
    return (t_ops, "vector") if t_ops >= t_mem else (t_mem, "hbm")
