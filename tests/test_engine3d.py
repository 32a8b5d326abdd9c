"""The 3-D temporal kernel through the engine's normal path.

PolyBench heat-3d's stencil (the centre and six face neighbours) on small
ringed grids with an affine fixed ring and a seeded random interior, in
interpret mode: ``engine.run(policy="auto")`` must give what
``core.stencil.apply_stencil`` scanned the same number of sweeps gives,
bit for bit (the kernel sums the taps in the spec's order). The planner,
dispatch, schedule and tracing take the 3-D grid; the 2-D-only parts
refuse it with a ``PlanError`` that says so.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import engine
from repro.core.stencil import StencilSpec, apply_stencil, residual
from repro.engine.plan import PlanError, default_depth
from repro.obs import Tracer, use_tracer

HEAT3D = StencilSpec(
    offsets=((0, 0, 0), (-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0),
             (0, 0, -1), (0, 0, 1)),
    weights=(0.25, 0.125, 0.125, 0.125, 0.125, 0.125, 0.125))


def _grid(shape, seed=0, dtype=jnp.float32):
    """PolyBench's affine boundary ``10 + 0.05 i + 0.05 j - 0.05 k`` around
    an interior uniform in [0, 1) drawn from ``seed``."""
    i, j, k = np.indices(shape)
    u = (10 + 0.05 * i + 0.05 * j - 0.05 * k).astype(np.float32)
    u[1:-1, 1:-1, 1:-1] = np.random.default_rng(seed).random(
        tuple(s - 2 for s in shape), dtype=np.float32)
    return jnp.asarray(u, dtype)


def _oracle(u, n):
    for _ in range(n):
        u = apply_stencil(u, HEAT3D)
    return u


# (ringed shape, t, iters, planes a block): one block; several with a
# ragged last one; odd and even t; a remainder; lanes over 128 and not a
# multiple of it; the default depth.
CASES = {
    "one_block_even_t": ((6, 10, 130), 4, 8, None),
    "ragged_blocks_odd_t": ((10, 9, 200), 3, 6, 3),
    "remainder": ((8, 12, 140), 4, 10, 2),
    "default_t_lanes_200": ((6, 11, 200), None, 9, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_3d_kernel_matches_the_oracle(case):
    shape, t, iters, bm = CASES[case]
    u = _grid(shape, seed=len(case))
    sched = engine.build_schedule(iters, spec=HEAT3D, shape=shape,
                                  dtype=u.dtype, t=t, bm=bm)
    assert sched.policy == "temporal" and sched.remainder_policy == "temporal"
    plan = engine.plan_for(shape, u.dtype, HEAT3D, "temporal", bm=bm,
                           t=sched.t)
    if bm is not None:
        assert plan.nblocks > 1
    got = engine.run(u, HEAT3D, policy="auto", iters=iters, t=t, bm=bm)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_oracle(u, iters)))


def test_3d_kernel_ignores_uninitialized_memory():
    """With NaN in every uninitialized scratch plane and every read past
    the grid (the last block is ragged), the answer still equals the
    oracle bit for bit: no kept cell reads what the kernel did not
    write."""
    u = _grid((9, 10, 130), seed=5)
    nan_memory = pltpu.InterpretParams(out_of_bounds_reads="uninitialized",
                                       uninitialized_memory="nan")
    got = engine.stencil_temporal(u, HEAT3D, t=3, bm=2, interpret=nan_memory)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_oracle(u, 3)))


def test_3d_run_converged_stops_on_the_residual():
    """``run_converged`` on a 3-D grid: whole blocks until the n-D
    residual is under tol, and the residual it reports is that of the
    grid it returns."""
    u = _grid((7, 10, 130), seed=3)
    out, n, res = engine.run_converged(u, HEAT3D, tol=5e-2, max_iters=100,
                                       t=4)
    assert 0 < n < 100 and n % 4 == 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(_oracle(u, n)))
    assert res == pytest.approx(float(residual(out, HEAT3D)), abs=0)
    assert res <= 5e-2 < float(residual(_oracle(u, n - 4), HEAT3D))


def test_3d_step_advances_one_sweep():
    u = _grid((6, 10, 130), seed=4)
    got = engine.step(u, HEAT3D, policy="auto")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_oracle(u, 1)))


def test_resolve_auto_picks_the_3d_kernel():
    shape = (200, 200, 200)
    assert engine.resolve_auto(shape, jnp.float32, HEAT3D, iters=2000,
                               device="tpu_v5e") == "temporal"
    assert engine.resolve_auto(shape, jnp.float32, HEAT3D, iters=1,
                               device="tpu_v5e") == "temporal"
    sched = engine.build_schedule(2001, spec=HEAT3D, shape=shape,
                                  dtype=jnp.float32, device="tpu_v5e")
    assert (sched.policy, sched.t, sched.fused_blocks, sched.remainder,
            sched.remainder_policy) == ("temporal", 6, 333, 3, "temporal")


def test_3d_plan_at_polybench_size():
    """At 200^3 f32 on a v5e: every plane takes 200 x 256 f32 words in
    fast memory, and a block of ``bp`` planes needs its window (``bp + 2t``
    planes) streamed in twice, twice in scratch, and its output twice. The
    planner takes the deepest block that fits 16 MiB, and the default
    depth is the deepest whose sweeps cover at most two planes per plane
    kept: at t=6 five-plane blocks sweep 5 + 5 planes, at t=7 four-plane
    blocks 4 + 6."""
    shape, plane = (200, 200, 200), 200 * 256 * 4
    budget = engine.get_device("tpu_v5e").fast_memory_bytes
    plan = engine.plan_for(shape, jnp.float32, HEAT3D, "temporal", t=4,
                           device="tpu_v5e")
    assert plan.ndim == 3 and plan.interior_shape == (198, 198, 198)
    assert (plan.bm, plan.halo_planes, plan.window_rows) == (8, (3, 5), 16)
    assert plan.halo_rows == (0, 0) and plan.strip_rows == 8
    assert plan.vmem_bytes == (4 * 16 + 2 * 8) * plane <= budget
    assert (4 * 17 + 2 * 9) * plane > budget   # a ninth plane does not fit
    assert plan.nblocks == 25                  # 24 x 8 + a ragged 6
    assert plan.recompute == pytest.approx((8 + 3) / 8 * 200 / 198)
    assert "planes=8 halo_planes=(3, 5)" in plan.describe()
    blocks = {t: engine.plan_for(shape, jnp.float32, HEAT3D, "temporal",
                                 t=t, device="tpu_v5e").bm
              for t in (5, 6, 7, 8)}
    assert blocks == {5: 6, 6: 5, 7: 4, 8: 2}
    assert default_depth(shape, jnp.float32, HEAT3D, "tpu_v5e") == 6


def test_3d_run_span_carries_the_plan():
    u = _grid((8, 10, 130), seed=6)
    tr = Tracer()
    with use_tracer(tr):
        engine.run(u, HEAT3D, policy="auto", iters=6, t=3, bm=2)
        engine.run_converged(u, HEAT3D, tol=None, max_iters=6, t=3, bm=2)
    plan = engine.plan_for(u.shape, u.dtype, HEAT3D, "temporal", t=3, bm=2)
    for name in ("engine.run", "engine.run_converged"):
        attrs = next(e.attrs for e in tr.events if e.name == name)
        assert attrs["policy"] == "temporal"
        assert attrs["block_planes"] == 2
        assert tuple(attrs["halo_planes"]) == plan.halo_planes == (2, 4)
        assert attrs["strip_rows"] == 8
        assert attrs["recompute"] == round(plan.recompute, 4)


def _tiny_budget():
    return dataclasses.replace(engine.get_device("tpu_v5e"),
                               name="tpu_v5e_64k", fast_memory_bytes=64 << 10)


@pytest.mark.parametrize("case", ["4d_spec", "over_budget", "2d_policy",
                                  "masked", "axes_mismatch"])
def test_3d_plan_refusals(case):
    shape = (8, 12, 130)
    if case == "4d_spec":
        spec4 = StencilSpec(offsets=((0, 0, 0, 1), (0, 0, 0, -1)),
                            weights=(0.5, 0.5))
        with pytest.raises(PlanError, match="2-D or 3-D"):
            engine.plan_for((6, 6, 6, 6), jnp.float32, spec4, "temporal")
    elif case == "over_budget":
        with pytest.raises(PlanError, match="fast memory"):
            engine.plan_for(shape, jnp.float32, HEAT3D, "temporal", t=4,
                            device=_tiny_budget())
    elif case == "2d_policy":
        with pytest.raises(PlanError, match="2-D only"):
            engine.plan_for(shape, jnp.float32, HEAT3D, "rowchunk")
    elif case == "masked":
        with pytest.raises(PlanError, match="mesh path is 2-D"):
            engine.plan_for(shape, jnp.float32, HEAT3D, "temporal",
                            masked=True)
    else:
        with pytest.raises(PlanError, match="axes"):
            engine.plan_for((8, 130), jnp.float32, HEAT3D, "temporal")


def test_run_distributed_refuses_3d():
    mesh = jax.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    with pytest.raises(PlanError, match="mesh path is 2-D"):
        engine.run_distributed(_grid((6, 10, 130)), HEAT3D, mesh=mesh,
                               iters=4, t=2)
