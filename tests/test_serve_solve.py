"""Solve server: bucketing, bit-exactness, eviction, diagnostics, warmup.

The serving contract under test: every request that goes through
:class:`repro.serve.SolveServer` — whatever it was batched with, whenever
it was evicted — must be bit-exact (fp32) against a solo ``engine.run``
at the same realized iteration count, and every rejection must be a
structured ``SCHED-*`` diagnostic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.stencil import (
    jacobi_2d_5pt,
    laplace_2d_9pt,
    make_laplace_problem,
)
from repro.serve import SolveRejected, SolveRequest, SolveServer


def _problem(h, w, dtype=np.float32, left=1.0):
    return make_laplace_problem(h, w, dtype=dtype, left=left)


def _solo(req):
    """The reference the server must match: one engine.run at the
    request's realized iteration count, same resolved policy/cadence."""
    fn = jax.jit(lambda u: engine.run(
        u, req.spec, policy=req.key.policy, iters=req.iters_done,
        t=req.key.t, interpret=True))
    return np.asarray(fn(jnp.asarray(req.grid)))


def test_mixed_traffic_bit_exact():
    """N concurrent requests — different shapes, specs, tolerances, some
    fixed-iteration — each bit-exact vs a solo run at iters_done."""
    srv = SolveServer(max_slots=4, interpret=True)
    reqs = [
        SolveRequest(grid=_problem(16, 16), tol=3e-3, max_iters=96,
                     policy="temporal", t=8),
        SolveRequest(grid=_problem(16, 16), tol=1.6e-3, max_iters=96,
                     policy="temporal", t=8),
        SolveRequest(grid=_problem(16, 16), tol=None, max_iters=24,
                     policy="temporal", t=8),
        SolveRequest(grid=_problem(12, 20), tol=2e-3, max_iters=96,
                     policy="rowchunk", t=8),
        SolveRequest(grid=_problem(16, 16), spec=laplace_2d_9pt(),
                     tol=1.5e-3, max_iters=96, policy="rowchunk", t=8),
    ]
    srv.solve(reqs)
    assert len(srv.buckets) == 3  # (16,16) temporal / (12,20) / 9pt spec
    for req in reqs:
        assert req.done
        assert req.iters_done % req.key.t == 0
        assert 0 < req.iters_done <= req.max_iters
        np.testing.assert_array_equal(req.result, _solo(req))
        if req.tol is not None:
            assert req.converged
            assert req.residual <= req.tol
        res_fn = engine.residual_for(req.spec)
        assert req.residual == pytest.approx(
            float(res_fn(jnp.asarray(req.result))), rel=1e-6)


def test_eviction_frees_slot_for_queued_request():
    """More requests than slots: converged solves are evicted mid-flight
    and their slots immediately serve the queue."""
    srv = SolveServer(max_slots=2, interpret=True)
    reqs = [SolveRequest(grid=_problem(16, 16), tol=tol, max_iters=96,
                         policy="temporal", t=8)
            for tol in (5e-3, 3e-3, 2e-3, 1.5e-3, 1e-3)]
    srv.solve(reqs)
    stats = srv.stats()
    assert stats["completed"] == len(reqs)
    assert stats["evicted_early"] >= 1
    (per,) = stats["per_bucket"].values()
    assert per["peak_active"] <= 2
    # Batching + eviction must beat one-block-per-request-per-launch.
    assert stats["launches"] < sum(r.target_blocks for r in reqs)
    for req in reqs:
        np.testing.assert_array_equal(req.result, _solo(req))


def test_bucket_never_mixes_dtypes():
    srv = SolveServer(max_slots=4, interpret=True)
    f32 = SolveRequest(grid=_problem(16, 16, np.float32), tol=None,
                       max_iters=8, policy="rowchunk", t=8)
    bf16 = SolveRequest(grid=_problem(16, 16, jnp.bfloat16), tol=None,
                        max_iters=8, policy="rowchunk", t=8)
    srv.submit(f32)
    srv.submit(bf16)
    assert f32.key != bf16.key
    assert len(srv.buckets) == 2
    srv.drain()
    assert f32.result.dtype == np.float32
    assert np.asarray(bf16.result).dtype == jnp.bfloat16


def test_bucket_mix_is_structured_diagnostic():
    """A request routed to a foreign bucket dies with SCHED-BUCKET-MIX,
    one finding per mismatching static field."""
    srv = SolveServer(max_slots=2, interpret=True)
    req = srv.submit(SolveRequest(grid=_problem(16, 16), tol=None,
                                  max_iters=8, policy="rowchunk", t=8))
    bucket = srv._buckets[req.key]
    foreign = dict(req.key.fields(), dtype="bfloat16", shape=(12, 22))
    with pytest.raises(SolveRejected) as ei:
        bucket.admit(SolveRequest(grid=_problem(10, 20)), foreign)
    msg = str(ei.value)
    assert msg.count("SCHED-BUCKET-MIX") == 2
    assert "bucket.dtype" in msg and "bucket.shape" in msg


def test_infeasible_requests_are_structured_rejections():
    srv = SolveServer(max_slots=2, interpret=True)
    with pytest.raises(SolveRejected, match="SCHED-REQUEST-INFEASIBLE"):
        srv.submit(SolveRequest(grid=np.zeros(16, np.float32)))  # 1-D
    with pytest.raises(SolveRejected, match="SCHED-REQUEST-INFEASIBLE"):
        srv.submit(SolveRequest(grid=_problem(16, 16), max_iters=0))
    with pytest.raises(SolveRejected, match="SCHED-REQUEST-INFEASIBLE"):
        # Unknown policy name dies at schedule build, not deep in launch.
        srv.submit(SolveRequest(grid=_problem(16, 16), max_iters=8,
                                policy="nonesuch"))


def test_streaming_progress_per_block():
    """The stream callback sees every block boundary: monotone iteration
    counts in steps of t, and (with stream_iterates) the true iterate."""
    seen = []

    def cb(req, prog):
        seen.append(prog)

    req = SolveRequest(grid=_problem(16, 16), tol=None, max_iters=32,
                       policy="temporal", t=8, stream=cb,
                       stream_iterates=True)
    SolveServer(max_slots=1, interpret=True).solve([req])
    assert [p.iters_done for p in seen] == [8, 16, 24, 32]
    for prog in seen:
        assert prog.iterate is not None
    np.testing.assert_array_equal(seen[-1].iterate, req.result)
    # Jacobi on a Laplace problem: residual decreases block to block.
    residuals = [p.residual for p in seen]
    assert residuals == sorted(residuals, reverse=True)


def test_server_warm_never_remeasures():
    """Warming the tune cache is idempotent: the second warm (and any
    tuned admission after it) is a pure cache hit — measure_count is
    pinned still."""
    from repro.engine import tune

    srv = SolveServer(max_slots=2, interpret=True)
    shapes = [(18, 18), (14, 22)]
    won = srv.warm(shapes, iters=8, t=4)
    assert set(won) == set(shapes)
    assert set(srv.warmed) == set(shapes)
    before = tune.cache_info()["measure_count"]
    again = srv.warm(shapes, iters=8, t=4)
    assert again == won
    assert tune.cache_info()["measure_count"] == before
    # A tuned request over a warmed shape admits without re-measuring.
    req = srv.submit(SolveRequest(grid=_problem(16, 16), tol=None,
                                  max_iters=8, policy="tuned", t=4))
    assert tune.cache_info()["measure_count"] == before
    assert req.key.policy == won[(18, 18)]


def test_run_batched_strip_kernel_matches_reference():
    """The vmapped launch the served path runs, at a width whose windows
    sweep in strips (the batch becomes a grid axis of the kernel and its
    scratch is reused lane after lane), over 16-row blocks of the 34
    ringed rows, the last one ragged: each lane
    equals the oracle bit for bit, with uninitialized memory read as NaN
    (Pallas's TPU interpret mode)."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import ref
    spec = jacobi_2d_5pt()
    us = jnp.stack([_problem(32, 2300, left=1.0),
                    _problem(32, 2300, left=-2.0)])
    us = us.at[:, 1:-1, 1:-1].set(jax.random.uniform(
        jax.random.PRNGKey(3), (2, 32, 2300)))
    plan = engine.plan_for(us.shape[1:], us.dtype, spec, "temporal", t=8,
                           bm=16)
    assert plan.strip_rows == 8 and plan.nblocks == 3, plan.describe()
    got = engine.run_batched(us, spec, policy="temporal", iters=16, t=8,
                             bm=16, interpret=pltpu.InterpretParams(
                                 out_of_bounds_reads="uninitialized"))
    for i in range(us.shape[0]):
        want = ref.sweeps(us[i], 16, spec, fuse=8)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_run_batched_matches_per_lane_run():
    """The vmapped batch primitive is bit-exact per lane vs solo runs."""
    spec = jacobi_2d_5pt()
    us = jnp.stack([_problem(16, 16, left=1.0),
                    _problem(16, 16, left=-2.0)])
    got = engine.run_batched(us, spec, policy="temporal", iters=8, t=8,
                            interpret=True)
    for i in range(us.shape[0]):
        want = engine.run(us[i], spec, policy="temporal", iters=8, t=8,
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))
    with pytest.raises(Exception):
        engine.run_batched(us[0], spec, iters=1)  # 2-D input: not a batch
