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
