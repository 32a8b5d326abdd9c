"""repro.backends: IR, lowering, and functional-simulator contracts.

The load-bearing assertion is equivalence: the simulator must agree with
``engine.run`` bit-for-bit in fp32 (row-major path) for every registry
policy, spec, and fusion depth — the backends layer re-implements the
numerics op-for-op, and any drift means the lowering no longer describes
the kernels. The tilized path re-quantizes through 32x32 bf16 tiles, so
bf16 grids stay exact (cast is identity) while f32 grids agree to bf16
tolerance only.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends, engine
from repro.backends import ir, report
from repro.backends.lower import LoweringError, lower, make_copy_program
from repro.core.stencil import (StencilSpec, jacobi_2d_5pt,
                                make_laplace_problem)
from repro.engine import tune
from repro.engine.device import GRAYSKULL_E150, get_device

DIAG9 = StencilSpec(offsets=((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1),
                             (1, -1), (1, 0), (1, 1)),
                    weights=(0.125,) * 8)
ROW3 = StencilSpec(offsets=((0, -1), (0, 0), (0, 1)),
                   weights=(0.25, 0.5, 0.25))


def _problem(ny=32, nx=64, dtype=jnp.float32):
    u = make_laplace_problem(ny, nx, dtype=dtype, left=1.0, right=0.0)
    bumps = (jnp.arange(ny * nx, dtype=jnp.float32).reshape(ny, nx) % 7) / 8
    return u.at[1:-1, 1:-1].set(bumps.astype(dtype))


# ---------------------------------------------------------------------------
# tilize / untilize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 32), (64, 96), (33, 65), (5, 130)])
def test_tilize_untilize_roundtrip_bf16(shape):
    rng = np.random.default_rng(0)
    a = rng.normal(size=shape).astype(ir.np_dtype("bfloat16"))
    tiles = ir.tilize(a, 32, 32)
    assert tiles.shape[2:] == (32, 32)
    assert tiles.shape[:2] == ir.tile_grid(*shape, 32, 32)
    back = ir.untilize(tiles, *shape)
    assert back.dtype == a.dtype
    np.testing.assert_array_equal(np.asarray(back, np.float32),
                                  np.asarray(a, np.float32))


def test_tilize_pads_ragged_edges_with_zeros():
    a = np.ones((33, 40), np.float32)
    tiles = ir.tilize(a, 32, 32)
    assert tiles.shape[:2] == (2, 2)
    # padding region of the last row-tile is zero
    assert tiles[1, 0, 1:, :].sum() == 0.0


def test_tilize_casts_to_bf16_lossy_for_f32():
    a = np.full((32, 32), 1.0 + 2**-10, np.float32)
    tiles = ir.tilize(a, 32, 32, dtype=ir.np_dtype("bfloat16"))
    back = ir.untilize(tiles, 32, 32, dtype=np.float32)
    assert (back != a).all()  # bf16 has 8 mantissa bits; 2^-10 is dropped


# ---------------------------------------------------------------------------
# CB bookkeeping: overflow / underflow
# ---------------------------------------------------------------------------

def _tiny_program(cb_tiles: int, with_producer: bool = True):
    """A hand-built one-block program with an undersized / unfed CB."""
    dev = get_device("grayskull_e150")
    spec = jacobi_2d_5pt()
    plan = engine.plan_for((34, 66), jnp.float32, spec, "rowchunk", bm=32,
                           device=dev)
    cbs = (ir.CircularBuffer("in", cb_tiles, dev.tile_rows, dev.tile_cols,
                             "float32"),
           ir.CircularBuffer("out", 64, dev.tile_rows, dev.tile_cols,
                             "float32"))
    reader = (ir.ReadBlock(cb="in", dy=-1, rows=34, col0=0, cols=66),) \
        if with_producer else ()
    return ir.TensixProgram(
        policy="rowchunk", spec=spec, plan=plan, cbs=cbs, reader=reader,
        compute=(ir.TapReduce(src="in", dst="out", row_off=1, col_off=1,
                              out_rows=32, out_cols=64),),
        writer=(ir.WriteBlock(cb="out", dy=0, rows=32, col0=1, cols=64,
                              contiguous=False),))


def test_cb_overflow_detected_at_push():
    prog = _tiny_program(cb_tiles=2)  # window needs 2x3 tiles
    u = np.zeros((34, 66), np.float32)
    with pytest.raises(ir.CBOverflowError, match="overflow"):
        backends.simulate_program(u, prog)


def test_cb_underflow_detected_statically_and_at_pop():
    prog = _tiny_program(cb_tiles=64, with_producer=False)
    with pytest.raises(ir.CBUnderflowError, match="underflow|pops"):
        prog.validate()
    u = np.zeros((34, 66), np.float32)
    with pytest.raises(ir.CBUnderflowError):
        backends.simulate_program(u, prog)


def test_program_rejects_undeclared_cb():
    prog = _tiny_program(cb_tiles=64)
    bad = dataclasses.replace(
        prog, writer=(ir.WriteBlock(cb="nope", dy=0, rows=32, col0=1,
                                    cols=64),))
    with pytest.raises(ir.BackendError, match="undeclared"):
        bad.validate()


# ---------------------------------------------------------------------------
# Lowering: device budgets bind a second time
# ---------------------------------------------------------------------------

def test_lowering_validates_cb_count():
    tiny = dataclasses.replace(GRAYSKULL_E150, name="cb_poor", cb_count=3)
    with pytest.raises(LoweringError, match="circular buffers"):
        lower((34, 66), jnp.float32, jacobi_2d_5pt(), "shifted", device=tiny)


def test_lowering_validates_sram_budget():
    # Plan passes (generous plan budget) but the tilized CB layout with its
    # staging buffers does not fit a deliberately tiny SRAM.
    tiny = dataclasses.replace(GRAYSKULL_E150, name="sram_poor",
                               fast_memory_bytes=96 * 1024)
    with pytest.raises((LoweringError, engine.PlanError)):
        lower((130, 258), jnp.float32, jacobi_2d_5pt(), "dbuf", bm=64,
              device=tiny, tilized=True)


def test_lowered_programs_fit_declared_budget():
    for policy in backends.lowerable_policies():
        prog = lower((34, 66), jnp.float32, jacobi_2d_5pt(), policy, t=2,
                     device="grayskull_e150")
        assert prog.sram_bytes <= prog.plan.device.fast_memory_bytes
        assert len(prog.cbs) <= prog.plan.device.cb_count
        prog.validate()
        assert prog.describe()  # IR dump renders


def test_dbuf_is_double_buffered_rowchunk_is_not():
    db = lower((34, 66), jnp.float32, jacobi_2d_5pt(), "dbuf",
               device="grayskull_e150")
    rc = lower((34, 66), jnp.float32, jacobi_2d_5pt(), "rowchunk",
               device="grayskull_e150")
    assert db.double_buffered and not rc.double_buffered


# ---------------------------------------------------------------------------
# Simulator == engine.run (the acceptance bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["shifted", "rowchunk", "dbuf",
                                    "temporal"])
@pytest.mark.parametrize("spec_name,spec", [("jacobi5", jacobi_2d_5pt()),
                                            ("diag9", DIAG9),
                                            ("row3", ROW3)])
@pytest.mark.parametrize("t", [1, 3])
def test_sim_matches_engine_run_fp32_exact(policy, spec_name, spec, t):
    u = _problem()
    iters = 4  # t=3 exercises the fused + remainder schedule
    want = np.asarray(engine.run(u, spec, policy=policy, iters=iters, t=t))
    res = backends.simulate(u, spec, policy=policy, iters=iters, t=t)
    np.testing.assert_array_equal(np.asarray(res.grid), want)
    assert res.counters.sweeps == iters
    assert res.model_time_s > 0


@pytest.mark.parametrize("policy", ["shifted", "rowchunk", "dbuf",
                                    "temporal"])
def test_sim_matches_engine_run_bf16_tilized_exact(policy):
    # bf16 grids lower to the tilized path by default on the e150 model and
    # the tilize cast is the identity, so even this path is bit-exact.
    u = _problem(dtype=jnp.bfloat16)
    want = np.asarray(engine.run(u, jacobi_2d_5pt(), policy=policy,
                                 iters=3, t=3)).astype(np.float32)
    res = backends.simulate(u, jacobi_2d_5pt(), policy=policy, iters=3, t=3,
                            device="grayskull_e150")
    assert res.programs[0].tilized
    np.testing.assert_array_equal(np.asarray(res.grid).astype(np.float32),
                                  want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sim_temporal_ragged_blocks_match_engine_run(dtype):
    """At 256x256 on the e150 budget the temporal blocks tile the 258
    ringed rows raggedly over several blocks; the last block's write is
    clipped at the grid's end and the result stays bit-exact."""
    u = _problem(256, 256, dtype=dtype)
    spec = jacobi_2d_5pt()
    res = backends.simulate(u, spec, policy="temporal", iters=8, t=4,
                            device="grayskull_e150")
    plan = res.programs[0].plan
    assert plan.nblocks > 1 and u.shape[0] % plan.bm
    assert "clip" in res.programs[0].describe()
    want = np.asarray(engine.run(u, spec, policy="temporal", iters=8, t=4))
    np.testing.assert_array_equal(np.asarray(res.grid).astype(np.float32),
                                  want.astype(np.float32))


def test_masked_temporal_lowering_streams_the_pin_mask():
    """The distributed-shard temporal program carries an explicit mask
    stream: a mask CB fed by a second DRAM source, consumed by the fused
    local sweeps."""
    from repro.backends.ir import LocalSweeps, ReadBlock
    from repro.backends.lower import lower

    prog = lower((34, 66), jnp.float32, jacobi_2d_5pt(), "temporal", t=2,
                 masked=True)
    mask_reads = [op for op in prog.reader
                  if isinstance(op, ReadBlock) and op.src == "mask"]
    assert len(mask_reads) == 1 and mask_reads[0].cb == "mask"
    sweeps = [op for op in prog.compute if isinstance(op, LocalSweeps)]
    assert sweeps[0].mask == "mask"
    assert prog.plan.masked
    assert "mask" in prog.describe()
    # The unmasked program carries none of it.
    plain = lower((34, 66), jnp.float32, jacobi_2d_5pt(), "temporal", t=2)
    assert all(op.src == "grid" for op in plain.reader
               if isinstance(op, ReadBlock))


def test_sim_masked_temporal_matches_engine_masked_kernel():
    """Sim of the masked shard program == the engine's masked Pallas
    kernel, bit-for-bit in fp32, on the valid (cropped) region — and both
    pin exactly the masked cells."""
    t, d = 2, 2
    u = _problem()
    h, w = u.shape
    mask = np.zeros((h, w), bool)
    mask[:d, :] = mask[:, :d] = True  # a corner shard's global-ring slice
    spec = jacobi_2d_5pt()
    res = backends.simulate(u, spec, policy="temporal", iters=t, t=t,
                            mask=mask)
    want = np.asarray(engine.stencil_temporal(
        u, spec, t=t, interpret=True, mask=jnp.asarray(mask)))
    got = np.asarray(res.grid)
    np.testing.assert_array_equal(got[:h - d, :w - d],
                                  want[:h - d, :w - d])
    np.testing.assert_array_equal(got[mask], np.asarray(u)[mask])
    # The mask stream is real modeled traffic: reader bytes grow vs the
    # unmasked program of the same schedule.
    plain = backends.simulate(u, spec, policy="temporal", iters=t, t=t)
    assert res.counters.reader.bytes > plain.counters.reader.bytes


def test_sim_masked_program_requires_the_mask_stream():
    from repro.backends.ir import BackendError
    from repro.backends.lower import lower
    from repro.backends.sim import run_program

    prog = lower((34, 66), jnp.float32, jacobi_2d_5pt(), "temporal", t=2,
                 masked=True)
    with pytest.raises(BackendError, match="mask"):
        run_program(np.zeros((34, 66), np.float32), prog)


def test_sim_mask_rejects_unfused_and_remainder_schedules():
    """Only fused blocks honor the pin mask; a remainder sweep (or a
    non-fused policy) would silently re-pin the geometric ring instead of
    the mask, so the simulator must refuse those schedules."""
    from repro.backends.ir import BackendError

    u = _problem()
    mask = np.zeros(u.shape, bool)
    mask[:2, :] = mask[:, :2] = True
    with pytest.raises(BackendError, match="fully-fused"):
        backends.simulate(u, jacobi_2d_5pt(), policy="temporal", iters=3,
                          t=2, mask=mask)
    with pytest.raises(BackendError, match="fully-fused"):
        backends.simulate(u, jacobi_2d_5pt(), policy="rowchunk", iters=2,
                          mask=mask)


def test_sim_f32_through_tiles_is_bf16_tolerant():
    u = _problem()
    want = np.asarray(engine.run(u, jacobi_2d_5pt(), policy="rowchunk",
                                 iters=5))
    res = backends.simulate(u, jacobi_2d_5pt(), policy="rowchunk", iters=5,
                            device="grayskull_e150", tilized=True)
    got = np.asarray(res.grid)
    assert not np.array_equal(got, want)  # quantization really happened
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("policy", ["rowchunk", "temporal"])
def test_sim_iters_zero_returns_grid_unchanged(policy):
    # engine.run's zero-length scan contract: iters=0 is a no-op, not an
    # error, for fused and non-fused policies alike.
    u = _problem()
    res = backends.simulate(u, jacobi_2d_5pt(), policy=policy, iters=0)
    np.testing.assert_array_equal(np.asarray(res.grid), np.asarray(u))
    assert res.counters.sweeps == 0 and res.model_time_s == 0.0


def test_cb_queue_is_fifo_under_multiple_pushes():
    # Two pushes before any pop must hand blocks back in ring order, with
    # occupancy tracking both (regression: the second push used to
    # overwrite the first entry while occupancy counted both).
    prog = _tiny_program(cb_tiles=64)
    cbs = backends.sim._CBState(prog)
    dev = prog.plan.device
    a = backends.sim._block_entry(np.zeros((32, 32), np.float32), dev)
    b = backends.sim._block_entry(np.ones((32, 32), np.float32), dev)
    cbs.push("in", a)
    cbs.push("in", b)
    assert cbs.occ["in"] == a["tiles"] + b["tiles"]
    assert cbs.pop("in") is a
    assert cbs.pop("in") is b
    assert cbs.occ["in"] == 0
    with pytest.raises(ir.CBUnderflowError):
        cbs.pop("in")


def test_sim_auto_policy_resolves_like_engine():
    u = _problem()
    res = backends.simulate(u, jacobi_2d_5pt(), policy="auto", iters=6)
    want = np.asarray(engine.run(u, jacobi_2d_5pt(), policy="auto", iters=6))
    np.testing.assert_array_equal(np.asarray(res.grid), want)


# ---------------------------------------------------------------------------
# Counters / step model: the paper's ordering falls out
# ---------------------------------------------------------------------------

def test_counter_traffic_reproduces_policy_ordering():
    u = _problem()
    spec = jacobi_2d_5pt()
    bpp = {}
    for policy in backends.lowerable_policies():
        res = backends.simulate(u, spec, policy=policy, iters=4, t=4,
                                device="grayskull_e150")
        bpp[policy] = report.bytes_per_point(res)
    # §IV per-tap re-reads >> §VI resident window; temporal amortizes ~t-x.
    assert bpp["shifted"] > 2 * bpp["rowchunk"]
    assert bpp["dbuf"] == bpp["rowchunk"]
    assert bpp["temporal"] < bpp["rowchunk"] / 1.5


def test_double_buffering_overlaps_the_pipeline():
    u = _problem(64, 128)
    kw = dict(iters=2, device="grayskull_e150", bm=16)
    t_rc = backends.simulate(u, policy="rowchunk", **kw).model_time_s
    t_db = backends.simulate(u, policy="dbuf", **kw).model_time_s
    assert t_db < t_rc


def test_copy_model_matches_paper_access_sweep_shape():
    dev = "grayskull_e150"
    base = report.model_copy_seconds((4096, 4096), "int32", seg_cols=4096,
                                     device=dev)
    small = report.model_copy_seconds((4096, 4096), "int32", seg_cols=1,
                                      device=dev)
    sync = report.model_copy_seconds((4096, 4096), "int32", seg_cols=1,
                                     sync=True, device=dev)
    repl = report.model_copy_seconds((4096, 4096), "int32", seg_cols=4096,
                                     reads=32, device=dev)
    il = report.model_copy_seconds((4096, 4096), "int32", seg_cols=4096,
                                   reads=32, interleaved=True, device=dev)
    # Paper Table III/V/VI: collapse below ~1KB requests, ~7x sync cost,
    # ~linear replication, ~2x interleaving win under replicated load.
    assert 100 < small / base < 250          # paper: 160x
    assert 5 < sync / small < 10             # paper: 7.2x
    assert 14 < repl / base < 20             # paper: 16.8x
    assert 1.8 < repl / il < 2.3             # paper: 2.05x
    assert abs(base - 0.011) / 0.011 < 0.1   # paper: 0.011 s


def test_simulate_program_and_summarize_shapes():
    prog = make_copy_program((64, 128), "float32", bm=16)
    res = backends.simulate_program(np.ones((64, 128), np.float32), prog)
    np.testing.assert_array_equal(np.asarray(res.grid),
                                  np.ones((64, 128), np.float32))
    s = report.summarize(res)
    assert s["policy"] == "copy" and s["dram_bytes"] == 2 * 64 * 128 * 4
    assert set(s) >= {"gpts", "energy_j", "model_time_s", "bytes_per_point"}


def test_tile_efficiency_penalizes_misalignment():
    full = report.tile_efficiency(512, 512, device="grayskull_e150")
    ragged = report.tile_efficiency(512, 514, device="grayskull_e150")
    assert full == 1.0 and ragged < 0.95


# ---------------------------------------------------------------------------
# Satellite: mesh-aware tune keys
# ---------------------------------------------------------------------------

def test_tune_key_folds_in_mesh_shape():
    dev = get_device("grayskull_e150")
    kw = dict(t=1, bm=None, interpret=True)
    k_local = tune.tune_key((34, 130), jnp.float32, jacobi_2d_5pt(), dev,
                            **kw)
    k_m4 = tune.tune_key((34, 130), jnp.float32, jacobi_2d_5pt(), dev,
                         mesh=(4,), **kw)
    k_m22 = tune.tune_key((34, 130), jnp.float32, jacobi_2d_5pt(), dev,
                          mesh=(2, 2), **kw)
    k_m22_masked = tune.tune_key((34, 130), jnp.float32, jacobi_2d_5pt(),
                                 dev, mesh=(2, 2), masked=True, **kw)
    k_m22_overlap = tune.tune_key((34, 130), jnp.float32, jacobi_2d_5pt(),
                                  dev, mesh=(2, 2), masked=True,
                                  overlap=True, **kw)
    assert len({k_local, k_m4, k_m22, k_m22_masked, k_m22_overlap}) == 5
    assert "mesh=local" in k_local and "mesh=2x2" in k_m22
    # masked-gated (distributed) cells never alias unmasked measurements,
    # and the interior/rind split's winners never alias serial ones.
    assert k_local.endswith("masked=False|overlap=False")
    assert k_m22_masked.endswith("masked=True|overlap=False")
    assert k_m22_overlap.endswith("masked=True|overlap=True")


def test_best_policy_mesh_cells_are_distinct(tmp_path):
    tune.clear()
    path = str(tmp_path / "tune.json")
    kw = dict(iters=1, interpret=True, device="tpu_v5e", cache_path=path)
    n0 = tune.measure_count
    tune.best_policy((34, 130), jnp.float32, jacobi_2d_5pt(), **kw)
    tune.best_policy((34, 130), jnp.float32, jacobi_2d_5pt(), mesh=(2, 2),
                     **kw)
    assert tune.measure_count == n0 + 2  # distinct cells both measured
    tune.best_policy((34, 130), jnp.float32, jacobi_2d_5pt(), mesh=(2, 2),
                     **kw)
    assert tune.measure_count == n0 + 2  # second mesh call is a cache hit


# ---------------------------------------------------------------------------
# Mesh step model: exchange hidden behind the interior, priced by the sim
# ---------------------------------------------------------------------------

def test_sim_mesh_exchange_model_overlap_wins_when_exchange_bound():
    """Wide, thin shards on the e150's PCIe-isolated cards: the halo rides
    the 1.25 GB/s host link while each 8-row shard's interior is cheap at
    the simulator's counters-derived rate, so the double-buffered bill
    (max(exchange, interior) + rind) beats the serial sum — and the grid
    itself is identical to the single-chip simulation, because the mesh
    model prices time, never touches numerics."""
    from repro.core.stencil import make_laplace_problem

    u = make_laplace_problem(64, 2040, dtype=np.float32, left=1.0)
    kw = dict(policy="rowchunk", iters=2, bm=16, device="grayskull_e150")
    base = backends.simulate(u, **kw)
    ser = backends.simulate(u, mesh_shape=(8,), **kw)
    ovl = backends.simulate(u, mesh_shape=(8,), overlap=True, **kw)
    assert base.exchange_model is None
    bill = ovl.exchange_model
    assert bill is not None and bill.feasible and bill.wins
    assert ovl.model_time_s < ser.model_time_s
    assert ser.model_time_s == bill.serial_s
    assert ovl.model_time_s == bill.overlapped_s
    # Exchange dominates each round's interior: the regime overlap exists
    # for, and the acceptance gate for the modeled win.
    assert bill.exchange_s > bill.interior_s
    np.testing.assert_array_equal(np.asarray(ovl.grid), np.asarray(ser.grid))
    np.testing.assert_array_equal(np.asarray(ovl.grid), np.asarray(base.grid))


def test_sim_mesh_rejects_undecomposable_grid():
    from repro.core.stencil import make_laplace_problem

    u = make_laplace_problem(30, 66, dtype=np.float32)
    with pytest.raises(backends.BackendError, match="does not decompose"):
        backends.simulate(u, policy="rowchunk", iters=1, mesh_shape=(4,),
                          device="grayskull_e150")
