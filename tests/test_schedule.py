"""SweepSchedule: the one derivation both executors run.

Covers the schedule arithmetic (fused blocks, remainder, exchange count),
the clamp warning, remainder-policy validation, policy resolution at the
*real* (iters, t) — including the regression where distributed tuning used
to key its cache at the hard-coded t=1 — and the masked temporal plan.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
from repro.engine.plan import PlanError
from repro.engine.schedule import (DEFAULT_REMAINDER_POLICY, SweepSchedule,
                                   build_schedule, effective_depth,
                                   price_exchange)

SPEC = jacobi_2d_5pt()
SHAPE = (34, 66)
DTYPE = jnp.float32


def _sched(iters, **kw):
    kw.setdefault("spec", SPEC)
    kw.setdefault("shape", SHAPE)
    kw.setdefault("dtype", DTYPE)
    return build_schedule(iters, **kw)


def test_fused_schedule_blocks_and_exchanges():
    s = _sched(16, policy="temporal", t=8)
    assert (s.fused, s.t, s.fused_blocks, s.remainder) == (True, 8, 2, 0)
    assert s.exchanges == 2
    assert s.halo_depth == 8 * SPEC.radius
    assert s.fused_blocks * s.t + s.remainder == s.iters == 16


def test_fused_schedule_remainder():
    s = _sched(7, policy="temporal", t=3)
    assert (s.fused_blocks, s.t, s.remainder) == (2, 3, 1)
    assert s.remainder_policy == DEFAULT_REMAINDER_POLICY
    assert s.exchanges == 3  # 2 fused + 1 shallow remainder round
    assert s.remainder_halo_depth == 1 * SPEC.radius


def test_explicit_clamped_t_warns():
    with pytest.warns(UserWarning, match="fusion depth t=9 exceeds iters=4"):
        s = _sched(4, policy="temporal", t=9)
    assert s.t == 4 and s.fused_blocks == 1 and s.remainder == 0


def test_default_t_clamps_silently():
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        s = _sched(3, policy="temporal")  # DEFAULT_T=8 quietly becomes 3
    assert s.t == 3


def test_invalid_depth_and_remainder_policy():
    with pytest.raises(PlanError, match="t=0"):
        _sched(4, policy="temporal", t=0)
    with pytest.raises(ValueError, match="non-fused"):
        _sched(7, policy="temporal", t=3, remainder_policy="temporal")


def test_non_fused_ignores_t_without_exchange_cadence():
    s = _sched(10, policy="rowchunk", t=4)
    assert (s.fused, s.t, s.fused_blocks, s.remainder) == (False, 1, 10, 0)


def test_non_fused_groups_under_exchange_cadence():
    s = _sched(10, policy="rowchunk", t=4, exchange_cadence=True)
    assert (s.t, s.fused_blocks, s.remainder) == (4, 2, 2)
    assert s.remainder_policy == "rowchunk"  # non-fused remainders re-run
    assert s.exchanges == 3


def test_zero_iters_schedule_is_empty():
    s = _sched(0, policy="temporal", t=4)
    assert (s.fused_blocks, s.remainder, s.exchanges) == (0, 0, 0)


def test_auto_resolves_at_real_iters():
    # Many sweeps + a window that fits -> temporal; a single sweep cannot
    # amortize fusion -> non-fused. The schedule must see the real iters.
    assert _sched(100, policy="auto").fused
    assert not _sched(1, policy="auto").fused


def test_describe_mentions_exchanges():
    s = _sched(7, policy="temporal", t=3)
    d = s.describe()
    assert "3 exchanges" in d and "temporal" in d and "7 sweeps" in d


def test_schedule_is_hashable_value():
    a = _sched(7, policy="temporal", t=3)
    b = _sched(7, policy="temporal", t=3)
    assert a == b and hash(a) == hash(b) and isinstance(a, SweepSchedule)


def test_effective_depth_is_the_single_clamp():
    assert effective_depth(10, None) == 8  # DEFAULT_T
    assert effective_depth(3, None) == 3
    assert effective_depth(10, 4) == 4
    assert effective_depth(2, 4) == 2
    assert effective_depth(0, 4) == 1
    with pytest.raises(PlanError):
        effective_depth(10, 0)


def test_auto_demotes_when_only_the_masked_plan_overflows():
    """The distributed executor launches temporal in its masked form
    (~one extra window of fast memory). Auto must gate the candidate by
    that plan: a budget between the two footprints demotes instead of
    letting local_sweep_for crash on the masked plan."""
    import dataclasses

    # bm=1 plans the smallest legal block: the planner shrinks blocks to
    # fit, so the budget sits between the two smallest footprints.
    plain = engine.plan_for(SHAPE, DTYPE, SPEC, "temporal", t=4, bm=1)
    masked = engine.plan_for(SHAPE, DTYPE, SPEC, "temporal", t=4,
                             masked=True, bm=1)
    budget = (plain.vmem_bytes + masked.vmem_bytes) // 2
    tight = dataclasses.replace(engine.get_device("tpu_v5e"),
                                name="tight", fast_memory_bytes=budget)
    assert engine.resolve_auto(SHAPE, DTYPE, SPEC, iters=8, t=4,
                               device=tight) == "temporal"
    assert engine.resolve_auto(SHAPE, DTYPE, SPEC, iters=8, t=4,
                               device=tight, masked=True) != "temporal"
    # End to end: auto over a mesh on the tight device must not raise.
    u = make_laplace_problem(SHAPE[0] - 2, SHAPE[1] - 2, dtype=DTYPE)
    got = engine.run_distributed(u, SPEC, mesh=_mesh1(), policy="auto",
                                 iters=8, t=4, row_axis="x", device=tight)
    want = engine.run(u, SPEC, policy="rowchunk", iters=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_masked_temporal_plan_costs_more_fast_memory():
    plain = engine.plan_for(SHAPE, DTYPE, SPEC, "temporal", t=4)
    masked = engine.plan_for(SHAPE, DTYPE, SPEC, "temporal", t=4,
                             masked=True)
    assert masked.masked and not plain.masked
    assert masked.vmem_bytes > plain.vmem_bytes
    with pytest.raises(PlanError, match="mask"):
        engine.plan_for(SHAPE, DTYPE, SPEC, "rowchunk", masked=True)


# ---------------------------------------------------------------------------
# plan_distributed / run_distributed ride the same schedule
# ---------------------------------------------------------------------------

def _mesh1():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))


def test_plan_distributed_exposes_exchange_bill():
    u = make_laplace_problem(32, 64, dtype=DTYPE)
    sched, shard_shape, (row_axis, col_axis) = engine.plan_distributed(
        u.shape, u.dtype, mesh=_mesh1(), policy="temporal", iters=7, t=3,
        row_axis="x")
    assert sched.policy == "temporal" and sched.fused
    assert (sched.fused_blocks, sched.remainder, sched.exchanges) == (2, 1, 3)
    # The extended shard carries the depth-t*r halo on both sides.
    assert shard_shape == (32 + 2 * 3, 64 + 2 * 3)
    assert row_axis == "x" and col_axis is None


def test_run_distributed_warns_on_clamped_t():
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    with pytest.warns(UserWarning, match="exceeds iters"):
        got = engine.run_distributed(u, mesh=_mesh1(), policy="rowchunk",
                                     iters=2, t=5, row_axis="x")
    want = engine.run(u, policy="rowchunk", iters=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_run_distributed_validates_remainder_policy():
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    with pytest.raises(ValueError, match="non-fused"):
        engine.run_distributed(u, mesh=_mesh1(), policy="temporal", iters=5,
                               t=2, row_axis="x",
                               remainder_policy="temporal")


def test_distributed_tuned_keys_cache_at_real_t(tmp_path, monkeypatch):
    """Regression: local_sweep_for used to resolve "tuned" at iters=1, t=1
    even when the caller ran a t>1 schedule — the winner was measured and
    cached for the wrong schedule. The tuned cache key must carry the real
    fusion depth and the mesh decomposition."""
    from repro.engine import tune

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.clear()
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    got = engine.run_distributed(u, mesh=_mesh1(), policy="tuned", iters=6,
                                 t=3, row_axis="x")
    want = engine.run(u, policy="rowchunk", iters=6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with open(tmp_path / "tune.json") as f:
        keys = list(json.load(f))
    mesh_keys = [k for k in keys if "mesh=1" in k]
    assert mesh_keys, keys
    assert all("t=3" in k and "masked=True" in k for k in mesh_keys), keys
    tune.clear()


def test_run_distributed_fused_matches_engine_run_single_shard():
    """One-device mesh, fused temporal: the masked kernel path must agree
    with the single-device oracle bit-for-bit (fp32, dyadic weights)."""
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    u = u.at[1:-1, 1:-1].set(
        jax.random.uniform(jax.random.PRNGKey(3), (16, 32)))
    want = np.asarray(engine.run(u, policy="rowchunk", iters=6))
    got = np.asarray(engine.run_distributed(
        u, mesh=_mesh1(), policy="temporal", iters=6, t=3, row_axis="x"))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# overlap: the exchange-hiding interior/rind split, priced end to end
# ---------------------------------------------------------------------------

def test_price_exchange_overlap_wins_when_exchange_bound():
    """Wide, thin shards on the e150: its PCIe-isolated cards bill the
    t*r-deep halo over the 1.25 GB/s host link (``mesh_direct_links=False``
    -> ``halo_link_bw``), while an 8-row shard's interior is cheap — so
    ``max(exchange, interior) + rind`` beats ``exchange + compute``."""
    shard = (128 + 2, 2040 + 2)
    sched = build_schedule(2, spec=SPEC, shape=shard, dtype=DTYPE,
                           policy="rowchunk", t=1, device="grayskull_e150",
                           exchange_cadence=True)
    bill = price_exchange(sched, shard_shape=shard, dtype=DTYPE, spec=SPEC,
                          device="grayskull_e150", mesh_shape=(8,))
    assert bill.feasible and bill.wins
    assert bill.overlapped_s < bill.serial_s
    # The bill's own arithmetic: serial is the unhidden sum, overlapped
    # hides the exchange under the interior and pays the rind after.
    assert bill.serial_s == pytest.approx(bill.exchange_s + bill.compute_s)
    assert bill.overlapped_s == pytest.approx(
        max(bill.exchange_s, bill.interior_s) + bill.rind_s)
    assert "overlap wins" in bill.describe()


def test_price_exchange_serial_wins_when_compute_bound():
    """A small, chunky shard on the host model: the rind's ~3x-redundant
    recompute costs more than the short exchange it hides."""
    shard = (14, 70)
    sched = build_schedule(3, spec=SPEC, shape=shard, dtype=DTYPE,
                           policy="rowchunk", t=3, exchange_cadence=True)
    bill = price_exchange(sched, shard_shape=shard, dtype=DTYPE, spec=SPEC,
                          mesh_shape=(4,))
    assert bill.feasible and not bill.wins
    assert bill.overlapped_s >= bill.serial_s
    assert "serial wins" in bill.describe()


def test_price_exchange_infeasible_falls_back_to_serial():
    """A shard thinner than twice the halo depth has no halo-independent
    interior; the bill must say so and price overlapped as serial."""
    shard = (8 + 2 * 4, 64 + 2 * 4)  # hl = 8 = 2*d at t=4
    sched = build_schedule(4, spec=SPEC, shape=shard, dtype=DTYPE,
                           policy="temporal", t=4, exchange_cadence=True)
    bill = price_exchange(sched, shard_shape=shard, dtype=DTYPE, spec=SPEC,
                          mesh_shape=(4,))
    assert not bill.feasible and not bill.wins
    assert bill.overlapped_s == bill.serial_s


def test_build_schedule_resolves_overlap_by_price():
    """``overlap=None`` under exchange_cadence consults the bill: the
    exchange-bound e150 geometry turns the split on, the compute-bound
    host geometry leaves it off — and describe() says which."""
    on = build_schedule(2, spec=SPEC, shape=(130, 2042), dtype=DTYPE,
                        policy="rowchunk", t=1, device="grayskull_e150",
                        mesh_shape=(8,), exchange_cadence=True)
    off = build_schedule(3, spec=SPEC, shape=(14, 70), dtype=DTYPE,
                         policy="rowchunk", t=3, mesh_shape=(4,),
                         exchange_cadence=True)
    assert on.overlap and not off.overlap
    assert "overlapped" in on.describe()
    assert "overlapped" not in off.describe()


def test_overlap_forced_and_gated():
    s_on = _sched(4, policy="rowchunk", exchange_cadence=True, overlap=True)
    s_off = _sched(4, policy="rowchunk", exchange_cadence=True, overlap=False)
    assert s_on.overlap and not s_off.overlap
    # A single-device schedule has no exchange to hide.
    with pytest.raises(PlanError, match="exchange_cadence"):
        _sched(4, policy="rowchunk", overlap=True)


def test_distributed_tuned_keys_bucket_overlap(tmp_path, monkeypatch):
    """Satellite regression: the tuned cache key must fold ``overlap`` in,
    so the winner measured for the interior/rind launch geometry never
    aliases the serial one (their kernel launch shapes differ)."""
    from repro.engine import tune

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.clear()
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    want = np.asarray(engine.run(u, policy="rowchunk", iters=6))
    for ovl in (False, True):
        got = engine.run_distributed(u, mesh=_mesh1(), policy="tuned",
                                     iters=6, t=3, row_axis="x", overlap=ovl)
        np.testing.assert_array_equal(np.asarray(got), want)
    with open(tmp_path / "tune.json") as f:
        keys = list(json.load(f))
    assert any("overlap=True" in k for k in keys), keys
    assert any("overlap=False" in k for k in keys), keys
    tune.clear()


def test_run_distributed_overlap_single_shard_bitexact():
    """Even with nothing to exchange (one shard), forcing the split must
    stay bit-exact — the interior/rind stitch is pure reordering."""
    u = make_laplace_problem(16, 32, dtype=DTYPE)
    u = u.at[1:-1, 1:-1].set(
        jax.random.uniform(jax.random.PRNGKey(5), (16, 32)))
    want = np.asarray(engine.run(u, policy="rowchunk", iters=6))
    got = np.asarray(engine.run_distributed(
        u, mesh=_mesh1(), policy="temporal", iters=6, t=3, row_axis="x",
        overlap=True))
    np.testing.assert_array_equal(got, want)
