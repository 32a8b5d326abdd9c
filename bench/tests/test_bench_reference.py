"""The benchmark's plain reference against the program, at a small size on
the CPU: a wrong reference would pass or fail every run unnoticed."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.references import linear_stencil as ref  # noqa: E402

CONFIG = harness.load_json(os.path.join(
    harness.BENCH, "configs", "jacobi2d-paper-f32.json"))
TAPS = ref.taps_of(CONFIG)


def _grid(seed, ny=32, nx=256):
    u = jnp.zeros((ny + 2, nx + 2), jnp.float32).at[:, 0].set(1.0)
    noise = jax.random.uniform(jax.random.PRNGKey(seed), (ny, nx))
    return u.at[1:-1, 1:-1].set(noise)


def _spec():
    from repro.core.stencil import StencilSpec
    return StencilSpec(offsets=TAPS[0], weights=TAPS[1])


@pytest.mark.parametrize("policy", ["auto", "rowchunk", "temporal"])
def test_reference_equals_engine_run_in_f32(policy):
    from repro import engine
    u = _grid(3)
    got = engine.run(u, _spec(), policy=policy, iters=40)
    want = ref.sweeps(u, 40, taps=TAPS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_reference_solve_to_tol_matches_run_converged():
    from repro import engine
    u = _grid(4)
    got, n, res = engine.run_converged(u, _spec(), tol=0.05, max_iters=400)
    want, m, r = ref.solve_to_tol(u, 0.05, 400 // 8, taps=TAPS, cadence=8)
    assert n == int(m)
    assert res == pytest.approx(float(r), rel=1e-6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(ref.residual(want, taps=TAPS)) == pytest.approx(
        float(r), rel=1e-6)


def test_the_control_departs_from_the_reference():
    u = _grid(5)
    want = ref.sweeps(u, 400, taps=TAPS)
    low = ref.sweeps(u, 400, taps=TAPS, compute="bfloat16")
    assert float(jnp.max(jnp.abs(low - want))) > 10 * CONFIG["limits"][
        "grid_gap"]


def _parent_sweep(u, taps):
    """The 2-D sweep as it stood before the reference took any number of
    axes."""
    r = ref.radius(taps)
    h, w = u.shape
    acc = None
    for (dy, dx), wt in zip(*taps):
        term = u[r + dy:h - r + dy, r + dx:w - r + dx] * jnp.asarray(
            wt, u.dtype)
        acc = term if acc is None else acc + term
    return u.at[r:h - r, r:w - r].set(acc)


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_2d_reference_is_the_parents(compute):
    u = _grid(6)
    want = jax.jit(lambda v: jax.lax.fori_loop(
        0, 40, lambda _, x: _parent_sweep(x, TAPS), v.astype(compute)))(u)
    got = ref.sweeps(u, 40, taps=TAPS, compute=compute)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.astype(jnp.float32)))


HEAT3D_TAPS = (((0, 0, 0), (-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0),
                (0, 0, -1), (0, 0, 1)),
               (0.25, 0.125, 0.125, 0.125, 0.125, 0.125, 0.125))


def _heat3d_loop(a):
    """PolyBench heat-3d's update of B from A, as its kernel writes it:
    a second difference along each axis, in float64."""
    b = a.copy()
    n0, n1, n2 = a.shape
    for i in range(1, n0 - 1):
        for j in range(1, n1 - 1):
            for k in range(1, n2 - 1):
                b[i, j, k] = (
                    0.125 * (a[i + 1, j, k] - 2.0 * a[i, j, k]
                             + a[i - 1, j, k])
                    + 0.125 * (a[i, j + 1, k] - 2.0 * a[i, j, k]
                               + a[i, j - 1, k])
                    + 0.125 * (a[i, j, k + 1] - 2.0 * a[i, j, k]
                               + a[i, j, k - 1])
                    + a[i, j, k])
    return b


def test_3d_sweep_matches_the_polybench_loop():
    """Tolerance: the weights are powers of two, so each float32 product
    is exact; they are non-negative and sum to 1, so every partial sum is
    at most max|u| in magnitude and each of the six float32 additions
    rounds by at most eps/2 of it: 3 eps max|u| in all. The float64 loop
    is exact to well inside that."""
    a = jax.random.uniform(jax.random.PRNGKey(8), (6, 7, 9),
                           jnp.float32) * 20.0 - 5.0
    got = np.asarray(ref.sweep(a, HEAT3D_TAPS))
    want = _heat3d_loop(np.asarray(a, np.float64))
    tol = 3 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(a)))
    assert np.max(np.abs(got - want)) <= tol
    np.testing.assert_array_equal(got[0], np.asarray(a)[0])  # ring kept

