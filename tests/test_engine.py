"""Spec-driven stencil engine: policy equivalence, plan cache, dispatch.

Every registered execution policy must reproduce the pure-jnp
``apply_stencil`` oracle for every stencil shape (5-point Jacobi, 9-point
Laplace, 1-D advection embedded as 2-D) in both f32 and bf16, in interpret
mode — that is the acceptance bar for the engine replacing the hand-written
kernel zoo.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import engine
from repro.core import jacobi as J
from repro.core.stencil import (StencilSpec, advection_2d_3pt, apply_stencil,
                                jacobi_2d_5pt, laplace_2d_9pt,
                                make_laplace_problem)
from repro.engine.plan import PlanError


def _problem(ny, nx, dtype, seed=0):
    u = make_laplace_problem(ny, nx, dtype=dtype)
    noise = jax.random.uniform(jax.random.PRNGKey(seed), (ny, nx), jnp.float32)
    return u.at[1:-1, 1:-1].set(noise.astype(dtype))


def _oracle(u, spec, n=1):
    for _ in range(n):
        u = apply_stencil(u, spec)
    return u


def _tol(dtype):
    return (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
            else dict(rtol=1e-6, atol=1e-6))


SPECS = {
    "jacobi5": jacobi_2d_5pt(),
    "laplace9": laplace_2d_9pt(),
    "advection2d": advection_2d_3pt(),
}
DTYPES = [jnp.float32, jnp.bfloat16]
POLICIES = engine.available_policies()


# ---------------------------------------------------------------------------
# Equivalence: every policy x every spec x every dtype == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("spec_name", list(SPECS))
@pytest.mark.parametrize("dtype", DTYPES)
def test_policy_matches_oracle_single_sweep(policy, spec_name, dtype):
    spec = SPECS[spec_name]
    u = _problem(30, 128, dtype)
    got = engine.run(u, spec, policy=policy, iters=1, bm=8, t=1,
                     interpret=True)
    want = _oracle(u, spec)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("spec_name", list(SPECS))
def test_policy_matches_oracle_multi_sweep(policy, spec_name):
    """iters=5 with t=2 exercises the temporal remainder path (2+2+1)."""
    spec = SPECS[spec_name]
    u = _problem(24, 128, jnp.float32)
    got = engine.run(u, spec, policy=policy, iters=5, bm=8, t=2,
                     interpret=True)
    want = _oracle(u, spec, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("policy", POLICIES)
def test_policy_radius2_spec(policy):
    """Anisotropic radius-2 spec: generality beyond the face-neighbour zoo."""
    spec = StencilSpec(offsets=((-2, 0), (-1, 0), (0, 0), (0, -2), (0, 1)),
                       weights=(0.1, 0.3, 0.2, 0.15, 0.25))
    u = _problem(30, 128, jnp.float32)
    got = engine.run(u, spec, policy=policy, iters=2, bm=7, t=2,
                     interpret=True)
    want = _oracle(u, spec, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("policy", POLICIES)
def test_boundary_ring_is_preserved(policy):
    u = _problem(32, 128, jnp.float32)
    got = engine.run(u, jacobi_2d_5pt(), policy=policy, iters=1, bm=16, t=1,
                     interpret=True)
    for idx in [(0, slice(None)), (-1, slice(None)),
                (slice(None), 0), (slice(None), -1)]:
        np.testing.assert_array_equal(np.asarray(got[idx]), np.asarray(u[idx]))


RADIUS2 = StencilSpec(offsets=((-2, 0), (-1, 0), (0, 0), (0, -2), (0, 1)),
                     weights=(0.1, 0.3, 0.2, 0.15, 0.25))
# (interior rows, cols, dtype, spec, t, bm, masked): the temporal
# kernel's edge cases, at widths whose windows exceed WHOLE_WINDOW_VREGS
# and so sweep in strips. The kernel's blocks tile the ringed rows. A
# single block whose 26 ringed rows are not a whole number of sublane
# tiles; a ragged last block (``("fit", 16)``: bm=None on a device whose
# budget holds 16-row blocks and no more, over 46 ringed rows, which no
# multiple of 8 divides); the masked form a distributed shard runs,
# ragged too; bf16; a radius-2 spec; one sweep. The ``whole`` cases'
# windows are small enough to sweep as one value.
STRIP_CASES = {
    "deep": (32, 2046, jnp.float32, jacobi_2d_5pt(), 8, 24, False),
    "single_ragged": (24, 2302, jnp.float32, jacobi_2d_5pt(), 3, None,
                      False),
    "ragged_blocks": (44, 2302, jnp.float32, laplace_2d_9pt(), 4,
                      ("fit", 16), False),
    "masked_shard": (44, 2046, jnp.float32, jacobi_2d_5pt(), 8, ("fit", 24),
                     True),
    "bf16": (48, 2046, jnp.bfloat16, jacobi_2d_5pt(), 8, 16, False),
    "radius2": (34, 2814, jnp.float32, RADIUS2, 2, 8, False),
    "t1": (32, 2814, jnp.float32, jacobi_2d_5pt(), 1, 8, False),
    "whole": (32, 128, jnp.float32, jacobi_2d_5pt(), 8, 16, False),
    "whole_masked": (44, 140, jnp.float32, jacobi_2d_5pt(), 8, ("fit", 16),
                     True),
}


def _strip_case(case):
    """The case's grid, pin mask, ``bm`` request and device."""
    import dataclasses

    from repro.engine.plan import _window_and_vmem
    ny, nx, dtype, spec, t, bm, masked = STRIP_CASES[case]
    u = _problem(ny, nx, dtype)
    device = None
    if isinstance(bm, tuple):
        budget = _window_and_vmem("temporal", u.shape, dtype, spec, bm[1],
                                  t, masked)[1]
        device = dataclasses.replace(engine.get_device("cpu_ref"),
                                     name=f"fits_bm{bm[1]}",
                                     fast_memory_bytes=budget)
        bm = None
    mask = None
    if masked:
        r = spec.radius
        ring = np.ones(u.shape, bool)
        ring[r:-r, r:-r] = False
        mask = jnp.asarray(ring)
    return u, spec, t, bm, device, mask


@pytest.mark.parametrize("case", list(STRIP_CASES))
def test_temporal_deep_fusion_matches_oracle(case):
    """One fused launch equals ``t`` reference sweeps rounded once, bit
    for bit; with a mask equal to the grid's own ring, the masked form
    does too."""
    from repro.kernels import ref
    u, spec, t, bm, device, mask = _strip_case(case)
    plan = engine.plan_for(u.shape, u.dtype, spec, "temporal", t=t, bm=bm,
                           device=device, masked=mask is not None)
    assert plan.strip_rows == (0 if case.startswith("whole") else 8)
    got = engine.stencil_temporal(u, spec, t=t, bm=bm, interpret=True,
                                  device=device, mask=mask)
    want = ref.sweeps(u, t, spec, fuse=t)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# Mosaic leaves scratch rows and lanes past the window, and block rows
# past the grid, holding whatever VMEM held, which is not what the
# interpreter's zeros are. Pallas's TPU interpret mode fills uninitialized
# memory with NaN, and lets reads past an array return it; it can fuse a
# multiply into an add differently, so it is exact only for dyadic
# weights, as these cases have.
NAN_MEMORY = pltpu.InterpretParams(out_of_bounds_reads="uninitialized",
                                   uninitialized_memory="nan")


@pytest.mark.parametrize("case", ["deep", "single_ragged", "masked_shard",
                                  "bf16", "t1"])
def test_temporal_strips_ignore_uninitialized_memory(case):
    """No kept cell of the strip kernel reads memory the kernel did not
    write: with NaN in every uninitialized scratch and out-of-grid row,
    the answer still equals the oracle bit for bit. The masked case pins
    only the ring's columns, as a middle shard of a row mesh does, so its
    first and last rows evolve like exchanged halo rows; they go stale
    one row per sweep and only the rows ``t`` deep and more are kept."""
    from repro.kernels import ref
    u, spec, t, bm, device, mask = _strip_case(case)
    if mask is not None:
        mask = jnp.asarray(mask).at[:, spec.radius:-spec.radius].set(False)
    got = engine.stencil_temporal(u, spec, t=t, bm=bm, interpret=NAN_MEMORY,
                                  device=device, mask=mask)
    want = ref.sweeps(u, t, spec, fuse=t)
    keep = slice(t, u.shape[0] - t) if mask is not None else slice(None)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[keep],
                                  np.asarray(want, np.float32)[keep])


def test_temporal_mask_defaults_to_ring_mask():
    """An explicit mask equal to the grid's own ring must reproduce the
    unmasked kernel bit-for-bit (the mask only generalizes the pin set)."""
    u = _problem(20, 66, jnp.float32)
    spec = jacobi_2d_5pt()
    mask = np.zeros(u.shape, bool)
    mask[:1, :] = mask[-1:, :] = mask[:, :1] = mask[:, -1:] = True
    got = engine.stencil_temporal(u, spec, t=3, interpret=True,
                                  mask=jnp.asarray(mask))
    want = engine.stencil_temporal(u, spec, t=3, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_temporal_mask_pins_only_global_ring_cells():
    """Distributed-shard semantics: pinned (global-ring) cells hold their
    values through the fused sweeps even when unpinned halo cells are
    perturbed, unpinned cells evolve, and the region far enough from any
    unpinned edge matches the masked-sweep oracle exactly."""
    t, d = 3, 3  # radius-1 spec: halo depth d = t*r
    u = _problem(24, 66, jnp.float32)
    h, w = u.shape
    spec = jacobi_2d_5pt()
    # A corner shard's pin set: the global ring slices it owns (top/left,
    # d deep); bottom/right bands are exchanged halo and stay unpinned.
    mask = np.zeros((h, w), bool)
    mask[:d, :] = mask[:, :d] = True
    jmask = jnp.asarray(mask)

    got = engine.stencil_temporal(u, spec, t=t, interpret=True, mask=jmask)
    # Pinned cells stay pinned...
    np.testing.assert_array_equal(np.asarray(got)[mask], np.asarray(u)[mask])
    # ...and keep staying pinned when the halo cells are perturbed.
    u2 = jnp.where(jmask, u, u + jnp.float32(0.125))
    got2 = engine.stencil_temporal(u2, spec, t=t, interpret=True, mask=jmask)
    np.testing.assert_array_equal(np.asarray(got2)[mask],
                                  np.asarray(u)[mask])
    # The perturbation must actually reach the unpinned valid region —
    # halo cells are real inputs, not decoration.
    assert not np.array_equal(np.asarray(got2)[d:h - d, d:w - d],
                              np.asarray(got)[d:h - d, d:w - d])
    # Valid region (>= d from any unpinned edge) == masked-sweep oracle.
    want = u
    for _ in range(t):
        want = jnp.where(jmask, u, apply_stencil(want, spec))
    np.testing.assert_array_equal(np.asarray(got)[:h - d, :w - d],
                                  np.asarray(want)[:h - d, :w - d])


# (interior rows, cols, masked): each form of the temporal kernel over
# 42 ringed rows in 16-row blocks, the last one ragged (10 rows): strips,
# the whole window as one value, and the masked form pinned at the grid's
# own ring.
RINGED_FORMS = {
    "strip": (40, 2302, False),
    "whole": (40, 258, False),
    "masked": (40, 2302, True),
}


@pytest.mark.parametrize("form", list(RINGED_FORMS))
def test_temporal_writes_the_ringed_grid(form):
    """The kernel's output is the ringed grid itself: the reference
    policy's grid bit for bit, ring rows and columns included, with no
    write-back after the launch."""
    ny, nx, masked = RINGED_FORMS[form]
    spec, t, bm = jacobi_2d_5pt(), 4, 16
    u = make_laplace_problem(ny, nx, left=1.0, right=-2.0, top=0.5,
                             bottom=3.0)
    u = u.at[1:-1, 1:-1].set(jax.random.uniform(
        jax.random.PRNGKey(len(form)), (ny, nx)))
    plan = engine.plan_for(u.shape, u.dtype, spec, "temporal", t=t, bm=bm,
                           masked=masked)
    assert (plan.bm, plan.nblocks, plan.halo_rows) == (16, 3, (8, 8))
    assert plan.strip_rows == (0 if form == "whole" else 8)
    mask = None
    if masked:
        mask = jnp.ones(u.shape, bool).at[1:-1, 1:-1].set(False)
    got = engine.stencil_temporal(u, spec, t=t, bm=bm, interpret=True,
                                  mask=mask)
    want = engine.run(u, spec, policy="reference", iters=t)
    assert got.shape == u.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("policy,writebacks",
                         [("temporal", 0), ("rowchunk", 1)])
def test_writeback_is_traced_only_for_single_sweep_policies(policy,
                                                             writebacks):
    """``engine.kernel.writeback`` counts each trace of the interior's
    write-back: a temporal program has none, a single-sweep policy's one
    (the grid's shape is traced by no other test)."""
    from repro.obs import metrics
    u = _problem(19, 150, jnp.float32)
    count = metrics.counter("engine.kernel.writeback")
    before = count.value
    jax.make_jaxpr(lambda v: engine.run(v, jacobi_2d_5pt(), policy=policy,
                                        iters=4, t=4, interpret=True))(u)
    assert count.value - before == writebacks


def test_auto_policy_matches_oracle():
    u = _problem(24, 128, jnp.float32)
    got = engine.run(u, laplace_2d_9pt(), policy="auto", iters=6, bm=8,
                     interpret=True)
    want = _oracle(u, laplace_2d_9pt(), 6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Planning: cache behaviour and validation
# ---------------------------------------------------------------------------

def test_plan_cache_hits():
    engine.plan_cache_clear()
    p1 = engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(), "rowchunk",
                         bm=16)
    info = engine.plan_cache_info()
    assert info.misses == 1 and info.hits == 0
    p2 = engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(), "rowchunk",
                         bm=16)
    info = engine.plan_cache_info()
    assert info.hits == 1 and info.misses == 1
    assert p1 is p2  # memoized object identity, not just equality
    engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(), "dbuf", bm=16)
    assert engine.plan_cache_info().misses == 2


def test_plan_values():
    plan = engine.plan_for((34, 130), jnp.bfloat16, laplace_2d_9pt(),
                           "temporal", bm=16, t=4)
    assert plan.bm == 16 and plan.t == 4 and plan.radius == 1
    assert plan.nblocks == 3  # the 34 ringed rows: 16 + 16 + a ragged 2
    assert plan.window_rows == 16 + 2 * 4  # bm + 2*t*r
    assert plan.dtype_bytes == 2
    assert "temporal" in plan.describe()
    # bm request snapped to a divisor of the interior height
    plan2 = engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(),
                            "rowchunk", bm=15)
    assert 32 % plan2.bm == 0 and plan2.bm <= 15


def test_temporal_footprint_is_the_strip_kernels_working_set():
    """The planner prices what the strip-mined kernel holds: the streamed
    window (and pin mask) double-buffered, the output block of ringed
    rows, and two f32 ping-pong copies of the window (three with a mask),
    in whole lane tiles, an even number of them in the scratch. The halo
    is the t*r = 8 rows the sweeps need past the rows a block writes. At
    the paper's grid on a v5e that keeps f32 at bm=56 (15.02 MB; bm=64
    would need 16.83 MB), 19 blocks of 72 rows over the 1026 ringed rows,
    and the masked form at bm=32."""
    from repro.engine.plan import _window_and_vmem
    spec, shape = jacobi_2d_5pt(), (1026, 9218)
    budget = engine.get_device("tpu_v5e").fast_memory_bytes

    def vmem(bm, streams):
        win = 8 + bm + 8                  # halo, main, halo
        stream, out = 2 * win * 9344 * 4, 2 * bm * 9344 * 4
        scratch = win * 9472 * 4          # 74 lane tiles
        return streams * stream + out + (streams + 1) * scratch

    assert _window_and_vmem("temporal", shape, jnp.float32, spec, 56, 8) \
        == (56 + 16, vmem(56, 1)) == (72, 15_024_128)
    assert vmem(56, 1) <= budget < vmem(64, 1) == 16_826_368
    assert _window_and_vmem("temporal", shape, jnp.float32, spec, 32, 8,
                            masked=True) == (48, vmem(32, 2))
    assert vmem(32, 2) <= budget < vmem(40, 2)
    plan = engine.plan_for(shape, jnp.float32, spec, "temporal", t=8,
                           device="tpu_v5e")
    assert (plan.bm, plan.kernel_rows, plan.strip_rows) == (56, 72, 8)
    assert plan.halo_rows == (8, 8) and plan.nblocks == 19
    assert plan.recompute == 72 / 56
    assert "strip=8 recompute=1.286" in plan.describe()
    masked = engine.plan_for(shape, jnp.float32, spec, "temporal", t=8,
                             device="tpu_v5e", masked=True)
    assert (masked.bm, masked.recompute) == (32, 1.5)


def _loop_carries(jaxpr):
    """Avals carried by every while/scan loop in ``jaxpr`` and the jaxprs
    nested in its equations (a Pallas kernel's among them)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            n = eqn.params["body_nconsts"]
            yield from eqn.params["body_jaxpr"].in_avals[n:]
        elif eqn.primitive.name == "scan":
            n, k = eqn.params["num_consts"], eqn.params["num_carry"]
            yield from eqn.params["jaxpr"].in_avals[n:n + k]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _loop_carries(sub)


def _kernel_jaxpr(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if isinstance(sub, jax.extend.core.Jaxpr):
                found = _kernel_jaxpr(sub)
                if found is not None:
                    return found
    return None


@pytest.mark.parametrize("shape", [(1026, 9218), (272, 9232)],
                         ids=["paper", "shard"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("masked", [False, True], ids=["ring", "masked"])
def test_temporal_sweep_loops_carry_no_window(shape, dtype, masked):
    """Spill guard: no loop in the temporal kernel carries more than one
    strip. A sweep loop that carries the whole window as one value (584
    vregs at the paper's width) makes Mosaic spill nearly all of it every
    sweep; the strip-mined kernel keeps its window in VMEM scratch and
    its loops carry indices."""
    spec = jacobi_2d_5pt()
    plan = engine.plan_for(shape, dtype, spec, "temporal", t=8,
                           device="tpu_v5e", masked=masked)
    x = jax.ShapeDtypeStruct(shape, dtype)
    m = jax.ShapeDtypeStruct(shape, jnp.bool_)
    outer = jax.make_jaxpr(lambda u, mk: engine.stencil_temporal(
        u, spec, t=8, device="tpu_v5e", interpret=False,
        mask=mk if masked else None))(x, m)
    kernel = _kernel_jaxpr(outer.jaxpr)
    assert kernel is not None
    carries = list(_loop_carries(kernel))
    assert carries, "the sweeps should run as loops"
    strip = plan.strip_rows * shape[1]
    for aval in carries:
        assert int(np.prod(aval.shape)) <= strip, aval


def test_plan_validation_errors():
    from repro.core.stencil import advection_1d_3pt
    with pytest.raises(PlanError):  # 1-D spec must be embedded as 2-D
        engine.plan_for((34, 130), jnp.float32, advection_1d_3pt(), "rowchunk")
    with pytest.raises(PlanError):  # grid smaller than the stencil ring
        engine.plan_for((2, 130), jnp.float32, jacobi_2d_5pt(), "rowchunk")
    with pytest.raises(PlanError):  # t < 1 is meaningless
        engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(), "temporal",
                        t=0)
    with pytest.raises(PlanError):  # unknown policy
        engine.plan_for((34, 130), jnp.float32, jacobi_2d_5pt(), "warp9")
    with pytest.raises(PlanError):  # VMEM budget exceeded
        engine.plan_for((20002, 20002), jnp.float32, jacobi_2d_5pt(),
                        "temporal", bm=20000, t=64)


def test_unknown_policy_lists_registry():
    u = _problem(16, 128, jnp.float32)
    with pytest.raises(ValueError, match="rowchunk"):
        engine.run(u, jacobi_2d_5pt(), policy="nope", interpret=True)


# ---------------------------------------------------------------------------
# Registry-driven dispatch and benchmark enumeration
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert POLICIES == ("shifted", "rowchunk", "dbuf", "temporal")
    fused = [p.name for p in engine.registry() if p.fused]
    assert fused == ["temporal"]
    for p in engine.registry():
        assert p.bytes_per_point(jacobi_2d_5pt(), 2, 8) > 0
        assert p.paper_ref


def test_benchmark_variants_come_from_registry():
    from benchmarks.common import engine_variant_rows
    rows = engine_variant_rows(t=8)
    names = [r[1] for r in rows]
    assert names == ["reference", *POLICIES]
    # the temporal row's traffic model reflects the fusion depth
    by_policy = {r[1]: r[3] for r in rows}
    assert by_policy["temporal"] == pytest.approx(by_policy["rowchunk"] / 8)
    assert by_policy["shifted"] > by_policy["rowchunk"]


def test_resolve_auto_heuristic():
    spec = jacobi_2d_5pt()
    # many sweeps + window fits -> temporal
    assert engine.resolve_auto((130, 130), jnp.float32, spec,
                               iters=100) == "temporal"
    # single sweep, several blocks -> dbuf hides the DMA latency
    assert engine.resolve_auto((1026, 130), jnp.float32, spec,
                               iters=1) == "dbuf"
    # single sweep, single resident block -> nothing to prefetch
    assert engine.resolve_auto((18, 130), jnp.float32, spec, iters=1) \
        == "rowchunk"


# ---------------------------------------------------------------------------
# Driver integration: policy names + temporal remainder regression
# ---------------------------------------------------------------------------

def test_jacobi_run_accepts_policy_name():
    u = _problem(16, 128, jnp.float32)
    got = J.jacobi_run(u, 3, policy="dbuf", bm=8, interpret=True)
    want = _oracle(u, jacobi_2d_5pt(), 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):  # callable + name is ambiguous
        J.jacobi_run(u, 3, lambda v: v, policy="dbuf")


def test_jacobi_run_counts_sweeps_exactly_for_fused_policy():
    """Regression: policy="temporal" must advance exactly ``iters`` sweeps
    (not iters * t), and per-sweep drivers must refuse fused policies."""
    u = _problem(32, 128, jnp.float32)
    want = _oracle(u, jacobi_2d_5pt(), 4)
    got = J.jacobi_run(u, 4, policy="temporal", bm=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="fused"):
        J.jacobi_solve(u, policy="temporal", interpret=True)
    with pytest.raises(ValueError, match="fused"):
        J.jacobi_run_unrolled(u, 4, policy="temporal")


def test_jacobi_run_temporal_non_divisible_iters():
    """Regression: iters % t != 0 used to raise; the remainder now runs
    under a non-fused registry policy."""
    u = _problem(32, 128, jnp.float32)
    want = _oracle(u, jacobi_2d_5pt(), 7)
    got = J.jacobi_run_temporal(u, 7, t=4, bm=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # legacy path: explicit t-step callable, remainder still handled
    from repro.kernels import ops
    tstep = ops.make_step_fn("v2", t=4, bm=16, interpret=True)
    got2 = J.jacobi_run_temporal(u, 7, tstep, t=4)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # iters < t: pure remainder, zero fused blocks
    got3 = J.jacobi_run_temporal(u, 2, t=4, bm=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got3),
                               np.asarray(_oracle(u, jacobi_2d_5pt(), 2)),
                               rtol=1e-5, atol=1e-6)


def test_deprecated_wrappers_still_work():
    from repro.kernels import jacobi as legacy
    from repro.kernels.stencil_general import stencil_rowchunk
    u = _problem(16, 128, jnp.float32)
    want = _oracle(u, jacobi_2d_5pt())
    for fn in [legacy.jacobi_v0_shifted, legacy.jacobi_v1_rowchunk,
               legacy.jacobi_v1_dbuf]:
        with pytest.warns(DeprecationWarning):
            got = fn(u, bm=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    with pytest.warns(DeprecationWarning):
        got = legacy.jacobi_v2_temporal(u, t=2, bm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(u, jacobi_2d_5pt(), 2)),
                               rtol=1e-6, atol=1e-6)
    with pytest.warns(DeprecationWarning):
        got = stencil_rowchunk(u, laplace_2d_9pt(), bm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(u, laplace_2d_9pt())),
                               rtol=1e-6, atol=1e-6)
