"""The harness on a configuration of any number of axes, at a small size on
the CPU with the look for a chip skipped: a 3-D heat stencil in the n-D
configuration form runs through ``harness.run`` and comes out correct, and
an answer one sweep short does not. The 2-D form keeps making the grids it
made before the n-D form existed, bit for bit."""
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, loads, verify  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HEAT3D = harness.load_json(os.path.join(DATA, "heat3d-tiny-f32.json"))
E2E = {m["name"]: m for m in harness.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["end_to_end"]}
TRAFFIC = {
    "fixed": dict(harness.load_json(os.path.join(
        harness.BENCH, "traffic", "fixed5000.json")), sweeps=6),
    "tol": dict(harness.load_json(os.path.join(
        harness.BENCH, "traffic", "tol.json")), tol=0.05, max_iters=400),
}
METRIC = {"fixed": "gpts", "tol": "solve_s"}


def heat3d_cell(kind):
    return harness.Cell(
        name=f"heat3d-tiny-f32.{kind}", chips=1, config=HEAT3D,
        traffic=TRAFFIC[kind], per_layer=[],
        end_to_end=[E2E[METRIC[kind]], E2E["setup_s"]])


def run_heat3d(kind, seed=2**31 + 29):
    return harness.run(heat3d_cell(kind), seed, 0.4, False,
                       time.perf_counter(), devices=jax.devices())


def test_the_affine_ring_holds_polybench_boundary():
    interior, shape, ring = loads.grid_form(HEAT3D, 1)
    assert interior == (6, 10, 126) and shape == (8, 12, 128)
    u = np.asarray(ring(jnp.float32))
    i, j, k = np.indices(shape)
    np.testing.assert_allclose(u, 10 + 0.05 * i + 0.05 * j - 0.05 * k,
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("kind", ["fixed", "tol"])
def test_3d_runs_are_correct(kind):
    res = run_heat3d(kind)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    value = res["metrics"][METRIC[kind]]["value"]
    assert math.isfinite(value) and value > 0


def test_3d_answer_one_sweep_short_fails_grid_gap(monkeypatch):
    from repro import engine
    real = engine.run

    def short(u, spec, *, iters, **kw):
        return real(u, spec, iters=iters - 1, **kw)

    monkeypatch.setattr(engine, "run", short)
    res = run_heat3d("fixed")
    assert not res["correct"]
    gap = res["compared"]["grid_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("kind", ["fixed", "tol"])
def test_3d_under_auto_runs_or_fails_in_the_program_at_setup(kind):
    """Under the program's own choice of kernel a 3-D configuration either
    runs correct or stops at set-up with the program's error: the harness
    itself takes any number of axes."""
    cell = heat3d_cell(kind)
    cell.config = dict(HEAT3D, policy="auto")
    try:
        res = harness.run(cell, 2**31 + 31, 0.4, False, time.perf_counter(),
                          devices=jax.devices())
    except Exception as e:
        frames = traceback.extract_tb(e.__traceback__)
        assert f"{os.sep}repro{os.sep}" in frames[-1].filename, frames[-1]
        assert any(f.name == "setup" and f.filename.endswith(
            os.path.join("bench", "loads.py")) for f in frames), frames
    else:
        assert res["correct"], res["compared"]


@pytest.mark.parametrize("kind", ["fixed", "tol"])
def test_3d_control_is_not_correct(kind):
    """The reference one precision down, in the program's place, on the
    3-D answers: the control path takes any number of axes."""
    system = loads.System(HEAT3D, jax.devices())
    d = loads.LOADS[TRAFFIC[kind]["kind"]](system, TRAFFIC[kind])
    d.setup(11, 0.2)
    d.window(0.2)
    dev = jax.devices()[0]
    good = verify.numbers(d.answers, HEAT3D, dev, 0)
    assert verify.judge(good, HEAT3D["limits"])[0]
    ctl = verify.numbers(verify.control_answers(d.answers, HEAT3D, dev),
                         HEAT3D, dev, 0)
    assert not verify.judge(ctl, HEAT3D["limits"])[0]


def _parent_pool(config, shape, r, dtype, seed, n):
    """The 2-D generator as it stood before the n-D configuration form."""
    ring = config["ring"]
    ny, nx = config["ny"], config["nx"]
    words = np.random.SeedSequence(seed).generate_state(2)

    def gen(k0, k1):
        key = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
        u = jnp.zeros(shape, dtype)
        u = u.at[:, :r].set(ring["left"]).at[:, -r:].set(ring["right"])
        u = u.at[:r, :].set(ring["top"]).at[-r:, :].set(ring["bottom"])
        return tuple(
            u.at[r:-r, r:-r].set(jax.random.uniform(
                k, (ny, nx), jnp.float32).astype(dtype))
            for k in jax.random.split(key, n))

    return jax.jit(gen)(np.uint32(words[0]), np.uint32(words[1]))


@pytest.mark.parametrize("name", ["jacobi2d-paper-f32", "jacobi2d-4card-f32"])
def test_2d_pool_is_the_parents(name):
    """At the configuration's own size; the mesh plays no part in the
    pool, so the four-chip configuration is built on one device."""
    cfg = dict(harness.load_json(os.path.join(
        harness.BENCH, "configs", name + ".json")), mesh=None, chips=1)
    system = loads.System(cfg, jax.devices())
    seed = 2**31 + 3
    got = system.pool(seed, 2)
    want = _parent_pool(cfg, system.shape, system.r, system.dtype, seed, 2)
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        assert g.shape == w.shape == (cfg["ny"] + 2, cfg["nx"] + 2)
        assert np.array_equal(np.asarray(g), np.asarray(w))
