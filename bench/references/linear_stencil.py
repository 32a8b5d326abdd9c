"""Plain reference for a linear stencil on a ringed grid.

``out[p] = sum_k w[k] * u[p + off[k]]`` over the interior, the ring of
width ``radius`` held fixed (Dirichlet), on a grid of any number of axes
(one offset component per axis). Written in straightforward
``jax.numpy`` from the configuration's own offsets and weights: it imports
nothing of the program under test and takes nothing the program made.
Taps accumulate in ``compute`` precision in the configuration's tap order.

The reference runs in float32. The control the benchmark must reject is
the same code in bfloat16, the precision one step below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Taps = tuple[tuple[tuple[int, ...], ...], tuple[float, ...]]


def taps_of(config: dict) -> Taps:
    return (tuple(tuple(int(c) for c in o) for o in config["offsets"]),
            tuple(float(w) for w in config["weights"]))


def radius(taps: Taps) -> int:
    return max(abs(c) for off in taps[0] for c in off)


def _interior(shape: tuple[int, ...], r: int, off=None) -> tuple:
    """One slice per axis: the interior, shifted by ``off``."""
    off = off or (0,) * len(shape)
    return tuple(slice(r + d, n - r + d) for n, d in zip(shape, off))


def sweep(u: jax.Array, taps: Taps) -> jax.Array:
    """One sweep in ``u.dtype`` arithmetic."""
    r = radius(taps)
    acc = None
    for off, wt in zip(*taps):
        term = u[_interior(u.shape, r, off)] * jnp.asarray(wt, u.dtype)
        acc = term if acc is None else acc + term
    return u.at[_interior(u.shape, r)].set(acc)


def _residual(u: jax.Array, taps: Taps) -> jax.Array:
    inner = _interior(u.shape, radius(taps))
    d = sweep(u, taps)[inner].astype(jnp.float32) - u[inner].astype(
        jnp.float32)
    return jnp.max(jnp.abs(d))


@functools.partial(jax.jit, static_argnames=("taps", "compute"))
def sweeps(u: jax.Array, n, *, taps: Taps, compute: str = "float32"
           ) -> jax.Array:
    """``n`` sweeps (``n`` is traced: one program serves every count),
    computed in ``compute``; returned in float32."""
    v = u.astype(compute)
    v = jax.lax.fori_loop(0, n, lambda _, x: sweep(x, taps), v)
    return v.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("taps",))
def residual(u: jax.Array, *, taps: Taps) -> jax.Array:
    """Max-norm update of one more float32 sweep over the interior."""
    return _residual(u.astype(jnp.float32), taps)


@functools.partial(jax.jit, static_argnames=("taps", "compute", "cadence"))
def solve_to_tol(u: jax.Array, tol, max_blocks, *, taps: Taps,
                 cadence: int, compute: str = "float32"):
    """Blocks of ``cadence`` sweeps until the residual after a block is
    <= ``tol`` or ``max_blocks`` blocks ran. Returns (grid in float32,
    sweeps done, residual)."""
    def cond(c):
        _, n, res = c
        return (n < max_blocks) & (res > tol)

    def body(c):
        v, n, _ = c
        v = jax.lax.fori_loop(0, cadence, lambda _, x: sweep(x, taps), v)
        return v, n + 1, _residual(v, taps)

    v, n, res = jax.lax.while_loop(
        cond, body, (u.astype(compute), jnp.int32(0), jnp.float32(jnp.inf)))
    return v.astype(jnp.float32), n * cadence, res
