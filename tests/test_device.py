"""Device-model layer: registry, per-device planning, measured autotuner.

The acceptance bar for the device abstraction replacing the old constants:
a plan that fits the v5e VMEM budget must raise ``PlanError`` when planned
for the Grayskull e150's 1.5 MiB Tensix SRAM; ``resolve_auto`` crossovers
must move with the device; ``policy="tuned"`` must measure once and serve
the winner from cache afterwards.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
from repro.engine import tune
from repro.engine.device import (DeviceModel, available_devices, detect,
                                 get_device)
from repro.engine.plan import PlanError, pick_bm

SPEC = jacobi_2d_5pt()

# Ringed f32 grid whose rowchunk window (~6 MiB) fits 16 MiB of v5e VMEM
# but overflows the e150's 1.5 MiB SRAM.
BIG = (132, 4100)


def _problem(ny, nx, dtype=jnp.float32):
    u = make_laplace_problem(ny, nx, dtype=dtype)
    noise = jax.random.uniform(jax.random.PRNGKey(0), u.shape, jnp.float32)
    return u.at[1:-1, 1:-1].set(noise[1:-1, 1:-1].astype(dtype))


# ---------------------------------------------------------------------------
# Registry and detection
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert {"tpu_v5e", "grayskull_e150", "gpu_sm90",
            "cpu_ref"} <= set(available_devices())
    e150 = get_device("grayskull_e150")
    assert e150.cores == 108
    assert e150.fast_memory_bytes == int(1.5 * 2**20)
    assert e150.preferred_dtype == "bfloat16"
    assert e150.fast_memory_bytes < get_device("tpu_v5e").fast_memory_bytes
    with pytest.raises(ValueError, match="grayskull_e150"):
        get_device("warp9")


def test_detect_matches_backend():
    dev = detect()
    assert isinstance(dev, DeviceModel)
    # On the CI/dev host jax runs on CPU; a TPU/GPU process detects its own.
    assert dev.backend in (jax.default_backend(), "cpu")
    assert get_device(None) is dev
    assert get_device(dev) is dev  # models pass through


def test_roofline_hw_comes_from_registry():
    from repro import roofline
    assert roofline.V5E == get_device("tpu_v5e").as_roofline_hw()
    assert roofline.resolve_hw("grayskull_e150")["hbm_bw"] == \
        pytest.approx(118.4e9)
    assert roofline.resolve_hw(None) is roofline.V5E
    raw = {"peak_flops": 1.0}
    assert roofline.resolve_hw(raw) is raw


# ---------------------------------------------------------------------------
# Per-device planning
# ---------------------------------------------------------------------------

def test_e150_budget_rejects_plan_v5e_accepts():
    plan = engine.plan_for(BIG, jnp.float32, SPEC, "rowchunk", bm=128,
                           device="tpu_v5e")
    assert plan.vmem_bytes < get_device("tpu_v5e").fast_memory_bytes
    assert plan.device.name == "tpu_v5e"
    with pytest.raises(PlanError, match="grayskull_e150"):
        engine.plan_for(BIG, jnp.float32, SPEC, "rowchunk", bm=128,
                        device="grayskull_e150")
    # Left to choose (bm=None), the planner shrinks the block until the
    # window fits the device it plans for.
    fit = engine.plan_for(BIG, jnp.float32, SPEC, "rowchunk",
                          device="grayskull_e150")
    assert fit.bm < plan.bm
    assert fit.vmem_bytes <= get_device("grayskull_e150").fast_memory_bytes
    # shifted streams (bm, wi) tap blocks with a small bm, so the e150 can
    # still run the problem — just not with the resident-window policies
    small = engine.plan_for(BIG, jnp.float32, SPEC, "shifted",
                            device="grayskull_e150")
    assert small.bm <= 16
    assert small.vmem_bytes < get_device("grayskull_e150").fast_memory_bytes


def test_engine_run_enforces_device_budget():
    u = _problem(130, 4098)
    out = engine.run(u, SPEC, policy="rowchunk", iters=1, bm=128,
                     interpret=True, device="tpu_v5e")
    assert out.shape == u.shape
    with pytest.raises(PlanError, match="1.50 MiB"):
        engine.run(u, SPEC, policy="rowchunk", iters=1, bm=128,
                   interpret=True, device="grayskull_e150")


def test_plan_cache_keys_differ_per_device():
    engine.plan_cache_clear()
    p_v5e = engine.plan_for((34, 130), jnp.float32, SPEC, "rowchunk", bm=16,
                            device="tpu_v5e")
    p_e150 = engine.plan_for((34, 130), jnp.float32, SPEC, "rowchunk", bm=16,
                             device="grayskull_e150")
    info = engine.plan_cache_info()
    assert info.misses == 2 and info.currsize == 2  # distinct entries
    assert p_v5e is not p_e150
    assert (p_v5e.device.name, p_e150.device.name) == \
        ("tpu_v5e", "grayskull_e150")
    # re-asking for either is a hit, not a re-derivation
    engine.plan_for((34, 130), jnp.float32, SPEC, "rowchunk", bm=16,
                    device="grayskull_e150")
    assert engine.plan_cache_info().hits == 1


def test_resolve_auto_crossover_shifts_on_e150():
    # v5e: the t=8 temporal window fits VMEM -> fuse; e150: even the
    # smallest temporal window (bm plus two t*r-deep halos) overflows
    # 1.5 MiB SRAM, while a small double-buffered row window fits -> dbuf.
    # Same problem, different hardware, different policy.
    assert engine.resolve_auto(BIG, jnp.float32, SPEC, iters=100,
                               device="tpu_v5e") == "temporal"
    assert engine.resolve_auto(BIG, jnp.float32, SPEC, iters=100,
                               device="grayskull_e150") == "dbuf"
    # narrow problem: every window fits both; both fuse
    assert engine.resolve_auto((130, 130), jnp.float32, SPEC, iters=100,
                               device="grayskull_e150") == "temporal"


def test_distributed_plan_validates_against_device():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))
    u = _problem(130, 4098)
    with pytest.raises(PlanError, match="grayskull_e150"):
        engine.run_distributed(u, SPEC, mesh=mesh, policy="rowchunk",
                               iters=1, bm=128, device="grayskull_e150")
    out = engine.run_distributed(u, SPEC, mesh=mesh, policy="rowchunk",
                                 iters=1, bm=128, device="tpu_v5e")
    assert out.shape == u.shape


# ---------------------------------------------------------------------------
# pick_bm degradation warning (prime interior heights)
# ---------------------------------------------------------------------------

def test_pick_bm_warns_on_prime_interior():
    with pytest.warns(UserWarning, match="realized bm=1"):
        assert pick_bm(1021, 256) == 1  # 1021 is prime: 1021 grid steps
    # The planner aligns blocks to the sublane tile instead: a prime
    # interior height gets tile-sized blocks with a ragged last one.
    engine.plan_cache_clear()
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = engine.plan_for((1023, 130), jnp.float32, SPEC, "rowchunk")
    assert plan.bm == 256 and plan.nblocks == 4


def test_pick_bm_quiet_cases():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert pick_bm(1024, 256) == 256     # exact divisor
        assert pick_bm(30, 16) == 15         # degrades, but usefully
        assert pick_bm(1, 256) == 1          # single-row interior is bm=1
        assert pick_bm(7, 1) == 1            # caller asked for 1


# ---------------------------------------------------------------------------
# Measured autotuner (policy="tuned")
# ---------------------------------------------------------------------------

def test_tuned_cache_roundtrip(tmp_path):
    cache = str(tmp_path / "tune.json")
    tune.clear()
    before = tune.measure_count
    kw = dict(iters=4, t=2, bm=8, interpret=True, device="tpu_v5e",
              cache_path=cache)
    best = tune.best_policy((34, 130), jnp.float32, SPEC, **kw)
    assert best in engine.available_policies()
    assert tune.measure_count == before + 1
    # second call: in-memory hit, no re-measure
    assert tune.best_policy((34, 130), jnp.float32, SPEC, **kw) == best
    assert tune.measure_count == before + 1
    # the JSON on disk round-trips: fresh process state reads, not measures
    rec = json.load(open(cache))
    [key] = list(rec)
    assert rec[key]["policy"] == best and "tpu_v5e" in key
    tune.clear()
    assert tune.best_policy((34, 130), jnp.float32, SPEC, **kw) == best
    assert tune.measure_count == before + 1  # served from disk
    tune.clear()


def test_tuned_keys_are_device_specific(tmp_path):
    cache = str(tmp_path / "tune.json")
    tune.clear()
    kw = dict(iters=1, bm=8, interpret=True, cache_path=cache)
    tune.best_policy((34, 130), jnp.float32, SPEC, device="tpu_v5e", **kw)
    tune.best_policy((34, 130), jnp.float32, SPEC,
                     device="grayskull_e150", **kw)
    keys = list(json.load(open(cache)))
    assert len(keys) == 2
    assert any("tpu_v5e" in k for k in keys)
    assert any("grayskull_e150" in k for k in keys)
    tune.clear()


def test_engine_run_tuned_policy(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.clear()
    before = tune.measure_count
    u = _problem(34, 130)
    want = u
    for _ in range(4):
        want = engine.run(want, SPEC, policy="rowchunk", bm=8, interpret=True)
    got = engine.run(u, SPEC, policy="tuned", iters=4, bm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert tune.measure_count == before + 1
    # second run(): cached winner, no re-measure (acceptance criterion)
    engine.run(u, SPEC, policy="tuned", iters=4, bm=8, interpret=True)
    assert tune.measure_count == before + 1
    tune.clear()


def test_unregistered_device_model_works_end_to_end():
    """A custom DeviceModel never passed to register_device must plan and
    dispatch like a registry name (it rides through whole, not by name)."""
    import dataclasses

    custom = dataclasses.replace(get_device("grayskull_e150"),
                                 name="bespoke_sram",
                                 fast_memory_bytes=64 * 2**20)
    u = _problem(130, 4098)
    out = engine.run(u, SPEC, policy="rowchunk", iters=1, interpret=True,
                     device=custom)  # 64 MiB budget: fits
    assert out.shape == u.shape
    tight = dataclasses.replace(custom, fast_memory_bytes=2**20)
    with pytest.raises(PlanError, match="bespoke_sram"):
        engine.run(u, SPEC, policy="rowchunk", iters=1, interpret=True,
                   device=tight)


def test_tuned_distributed_path(tmp_path, monkeypatch):
    """policy="tuned" must work through run_distributed (the solve CLI's
    --devices path): the winner is tuned for the extended shard shape."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.clear()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))
    u = _problem(34, 130)
    want = engine.run(u, SPEC, policy="rowchunk", bm=8, iters=2,
                      interpret=True)
    got = engine.run_distributed(u, SPEC, mesh=mesh, policy="tuned",
                                 iters=2, bm=8, device="tpu_v5e")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    tune.clear()


def test_tune_cache_files_stay_isolated(tmp_path):
    """Saving one cache file must not leak another file's entries into it."""
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    tune.clear()
    kw = dict(iters=1, bm=8, interpret=True, device="tpu_v5e")
    tune.best_policy((34, 130), jnp.float32, SPEC, cache_path=a, **kw)
    tune.best_policy((24, 130), jnp.float32, SPEC, cache_path=b, **kw)
    keys_a, keys_b = list(json.load(open(a))), list(json.load(open(b)))
    assert len(keys_a) == 1 and len(keys_b) == 1
    assert keys_a != keys_b
    tune.clear()


def test_tune_key_folds_in_interpret():
    key_i = tune.tune_key((34, 130), jnp.float32, SPEC,
                          get_device("tpu_v5e"), t=1, bm=8, interpret=True)
    key_c = tune.tune_key((34, 130), jnp.float32, SPEC,
                          get_device("tpu_v5e"), t=1, bm=8, interpret=False)
    assert key_i != key_c  # interpret timings never serve compiled runs


def test_bench_dry_env_falsy_values(monkeypatch):
    from benchmarks.common import dry_run
    for val, want in (("1", True), ("true", True), ("0", False),
                      ("false", False), ("", False), ("off", False)):
        monkeypatch.setenv("REPRO_BENCH_DRY", val)
        assert dry_run() is want, (val, want)
    monkeypatch.delenv("REPRO_BENCH_DRY")
    assert dry_run() is False


def test_tuned_respects_device_budget(tmp_path):
    cache = str(tmp_path / "tune.json")
    tune.clear()
    # With a 128-row block, no policy's window fits the e150's 1.5 MiB
    # SRAM for BIG: the tuner must refuse with every candidate's
    # rejection in the message, not silently pick an unplannable winner.
    with pytest.raises(PlanError, match="no policy plans"):
        tune.best_policy(BIG, jnp.float32, SPEC, iters=1, bm=128,
                         interpret=True, device="grayskull_e150",
                         cache_path=cache)
    # With a small streamed block everything fits; the measured winner is
    # a real, plannable policy and the skip list is empty.
    best = tune.best_policy((34, 130), jnp.float32, SPEC, iters=1, bm=8,
                            interpret=True, device="grayskull_e150",
                            cache_path=cache)
    assert best in engine.available_policies()
    [rec] = json.load(open(cache)).values()
    assert rec["skipped"] == [] and rec["device"] == "grayskull_e150"
    tune.clear()


# ---------------------------------------------------------------------------
# Planning the paper's domain for the chip's tiling
# ---------------------------------------------------------------------------

PAPER = (1026, 9218)  # the paper's 1024x9216 interior, ringed


@pytest.mark.parametrize("align", [8, 16])
def test_pick_bm_returns_only_tile_aligned_heights(align):
    for h in (1024, 270, 526, 1021, 64, 30, 9):
        for req in (1, 8, 16, 64, 100, 256, 5000):
            bm = pick_bm(h, req, align)
            assert bm == h or bm % align == 0, (h, req, bm)
            assert bm <= max(req, align) or bm == h, (h, req, bm)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("policy,masked", [
    ("shifted", False), ("rowchunk", False), ("dbuf", False),
    ("temporal", False), ("temporal", True)])
def test_plan_without_bm_fits_the_paper_domain(policy, masked, dtype):
    from repro.engine.plan import sublane_tile
    plan = engine.plan_for(PAPER, dtype, SPEC, policy, device="tpu_v5e",
                           t=8 if policy == "temporal" else None,
                           masked=masked)
    assert plan.vmem_bytes <= get_device("tpu_v5e").fast_memory_bytes
    assert plan.bm % sublane_tile(dtype) == 0
    assert plan.nblocks * plan.bm >= plan.interior_shape[0]
    top, bot = plan.halo_rows
    assert top % sublane_tile(dtype) == 0 and bot % sublane_tile(dtype) == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("iters,masked", [(1, False), (5000, False),
                                          (5000, True)])
def test_resolve_auto_returns_a_plannable_policy(iters, masked, dtype):
    policy = engine.resolve_auto(PAPER, dtype, SPEC, iters=iters,
                                 device="tpu_v5e", masked=masked)
    fused = engine.get_policy(policy).fused
    engine.plan_for(PAPER, dtype, SPEC, policy, device="tpu_v5e",
                    t=8 if fused else None, masked=masked and fused)
    assert policy == ("temporal" if iters > 1 else "dbuf")


def test_resolve_auto_raises_when_no_policy_plans():
    import dataclasses
    tiny = dataclasses.replace(get_device("tpu_v5e"), name="tiny",
                               fast_memory_bytes=1024)
    with pytest.raises(PlanError, match="no policy plans"):
        engine.resolve_auto(PAPER, jnp.float32, SPEC, iters=100,
                            device=tiny)


def test_detect_matches_tpu_kind_and_refuses_unknown(monkeypatch):
    import types
    from repro.engine import device as dev_mod
    monkeypatch.setattr(dev_mod.jax, "default_backend", lambda: "tpu")
    chip = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(dev_mod.jax, "devices", lambda *a: [chip])
    assert detect().name == "tpu_v5e"
    chip.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        detect()
    monkeypatch.setattr(dev_mod.jax, "default_backend", lambda: "warp")
    with pytest.raises(ValueError, match="warp"):
        detect()


def test_tune_interpret_follows_the_backend(tmp_path):
    cache = str(tmp_path / "tune.json")
    tune.clear()
    tune.best_policy((34, 130), jnp.float32, SPEC, iters=1, bm=8,
                     device="tpu_v5e", cache_path=cache)
    [key] = json.load(open(cache))
    want = jax.default_backend() != "tpu"
    assert f"interpret={want}" in key
    tune.clear()
