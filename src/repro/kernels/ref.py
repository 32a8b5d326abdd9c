"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel in kernels/ is validated against these references with
``np.testing.assert_allclose`` across shape/dtype sweeps (see tests/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilSpec, apply_stencil, jacobi_2d_5pt


def jacobi_step(u: jax.Array) -> jax.Array:
    """One 5-point Jacobi sweep on a ringed grid (boundary fixed)."""
    return apply_stencil(u, jacobi_2d_5pt())


def jacobi_multi(u: jax.Array, t: int) -> jax.Array:
    """t consecutive Jacobi sweeps (oracle for the temporal-blocked kernel)."""
    for _ in range(t):
        u = jacobi_step(u)
    return u


@functools.partial(jax.jit, static_argnames=("spec", "fuse"))
def sweeps(u: jax.Array, n, spec: StencilSpec | None = None, *,
           fuse: int = 1) -> jax.Array:
    """``n`` oracle sweeps in one jitted loop (``n`` may be traced).

    Each group of ``fuse`` sweeps runs in f32 and rounds to ``u.dtype``
    once at its end, which is where a fused policy (``temporal`` with
    ``t=fuse``) rounds; leftover ``n % fuse`` sweeps round every sweep,
    like the engine's non-fused remainder. ``fuse=1`` is the plain
    per-sweep oracle. For f32 grids the rounding points change nothing.
    """
    spec = spec if spec is not None else jacobi_2d_5pt()

    def group(k):
        def body(_, v):
            w = v.astype(jnp.float32)
            for _ in range(k):
                w = apply_stencil(w, spec)
            return w.astype(v.dtype)
        return body

    u = jax.lax.fori_loop(0, n // fuse, group(fuse), u)
    return jax.lax.fori_loop(0, n % fuse, group(1), u)


def stencil_step(u: jax.Array, spec: StencilSpec) -> jax.Array:
    """Generic weighted-stencil sweep (oracle for the general kernel)."""
    return apply_stencil(u, spec)


def conv1d_depthwise_causal(x: jax.Array, w: jax.Array,
                            b: jax.Array | None = None) -> jax.Array:
    """Depthwise causal 1-D convolution (Mamba2's conv frontend).

    x: (B, L, D), w: (K, D), b: (D,) or None. Output (B, L, D) where
    ``out[:, l, d] = sum_k w[k, d] * x[:, l - (K-1) + k, d]`` (zero padded).
    """
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def stream_copy(x: jax.Array) -> jax.Array:
    """Identity copy (oracle for the streaming/data-access benchmark)."""
    return x


def stream_replicated(x: jax.Array, factor: int) -> jax.Array:
    """Oracle for the replicated-read benchmark: sum of `factor` reads."""
    return (x.astype(jnp.float32) * jnp.float32(factor)).astype(x.dtype)
