"""Whole benchmark runs at a small size on the CPU, with the look for a chip
skipped: a sound program comes out correct, and each fault a cell can
have, planted underneath the timed path, comes out not correct.

Faults: a solve that returns its state unchanged; an answer altered where
it is produced; half of the served batch left out; the halo exchange
between chips left out (in a child process with four CPU devices).
"""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, verify  # noqa: E402

FIXED = "jacobi2d-paper-f32.fixed5000"
TOL = "jacobi2d-paper-f32.tol"
SERVE = "jacobi2d-paper-f32.serve"
DIST = "jacobi2d-4card-f32.fixed5000"
SMALL = {FIXED: {"sweeps": 40}, TOL: {"tol": 0.05, "max_iters": 400},
         SERVE: {"rate": 40.0, "max_iters": 400, "pool": 4},
         DIST: {"sweeps": 40}}


def small_cell(name):
    """The cell's own files, cut to a size the CPU runs in a second."""
    cell = harness.cell_from_files(name)
    cell.config = dict(cell.config, ny=16 * cell.chips, nx=256)
    cell.traffic = dict(cell.traffic, **SMALL[name])
    return cell


def run_small(name, seconds=0.4, seed=2**31 + 11):
    return harness.run(small_cell(name), seed, seconds, False,
                       time.perf_counter(), devices=jax.devices())


@pytest.fixture
def serve_mod():
    from repro.serve import solve
    solve._superblock_for.cache_clear()
    yield solve
    solve._superblock_for.cache_clear()


@pytest.mark.parametrize("name", [FIXED, TOL, SERVE])
def test_sound_runs_are_correct(name, serve_mod):
    res = run_small(name)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"


def _perturb(u):
    return u.at[2, 3].add(0.01)


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fixed_sweep_faults_fail(fault, monkeypatch):
    from repro import engine
    real = engine.run
    fake = ((lambda u, *a, **k: u) if fault == "unchanged"
            else (lambda u, *a, **k: _perturb(real(u, *a, **k))))
    monkeypatch.setattr(engine, "run", fake)
    assert not run_small(FIXED)["correct"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_tolerance_faults_fail(fault, monkeypatch):
    from repro import engine
    real = engine.run_converged

    def fake(u, *a, **k):
        if fault == "unchanged":
            return u, 8, 0.0
        v, n, r = real(u, *a, **k)
        return _perturb(v), n, r

    monkeypatch.setattr(engine, "run_converged", fake)
    assert not run_small(TOL)["correct"]


def test_served_state_unchanged_fails(serve_mod, monkeypatch):
    monkeypatch.setattr(serve_mod, "run", lambda u, *a, **k: u)
    assert not run_small(SERVE)["correct"]


def test_served_answer_altered_fails(serve_mod, monkeypatch):
    real = serve_mod.SolveServer._finish

    def finish(self, bucket, req, result, converged):
        result = result.copy()
        result[2, 3] += 0.01
        return real(self, bucket, req, result, converged)

    monkeypatch.setattr(serve_mod.SolveServer, "_finish", finish)
    assert not run_small(SERVE)["correct"]


def test_served_half_batch_left_out_fails(serve_mod, monkeypatch):
    import jax.numpy as jnp
    real = serve_mod._superblock_for

    def broken(key, k):
        launch = real(key, k)

        def half(us, *rest):
            h = us.shape[0] // 2
            kept = jnp.array(us[h:])          # lanes that will not move
            out = launch(us, *rest)
            return (out[0].at[h:].set(kept),) + tuple(out[1:])
        return half

    monkeypatch.setattr(serve_mod, "_superblock_for", broken)
    assert not run_small(SERVE)["correct"]


def test_the_control_is_not_correct():
    """The reference one precision down, in the program's place."""
    cell = small_cell(TOL)
    cell.traffic["max_iters"] = 2000
    from bench import loads
    system = loads.System(cell.config, jax.devices())
    d = loads.ClosedTol(system, cell.traffic)
    d.setup(7, 0.2)
    d.window(0.2)
    good = verify.numbers(d.answers, cell.config, jax.devices()[0], 0)
    assert verify.judge(good, cell.config["limits"])[0]
    ctl = verify.numbers(verify.control_answers(
        d.answers, cell.config, jax.devices()[0]), cell.config,
        jax.devices()[0], 0)
    assert not verify.judge(ctl, cell.config["limits"])[0]


_CHILD = r"""
import json, sys, time
sys.path.insert(0, {root!r})
sys.path.insert(0, {test_dir!r})
import jax
import test_bench_faults as t
from repro.dist import stencil
sound = t.run_small(t.DIST)["correct"]
def no_exchange(u, axis, n, depth):
    import jax.numpy as jnp
    z = jnp.zeros((depth,) + u.shape[1:], u.dtype)
    return z, z
stencil.exchange_rows = no_exchange
stencil.run_sharded_cache_clear()
broken = t.run_small(t.DIST)["correct"]
print(json.dumps({{"sound": sound, "broken": broken}}))
"""


def test_exchange_left_out_fails():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count"
               "=4", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    code = _CHILD.format(root=ROOT, test_dir=os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "broken": False}
