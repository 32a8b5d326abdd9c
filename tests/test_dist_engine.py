"""engine.run_distributed == single-device engine.run, bit-for-bit (fp32).

Runs the full matrix in one subprocess (8 forced host devices): 2 mesh
shapes x 3 policies x 3 stencil specs (face, row, and diagonal-tap — the
latter exercises physical-corner transport) x halo depths t in {1, 3},
each compared exactly against the single-device oracle. Dyadic tap weights
keep every policy's f32 tap accumulation bit-identical regardless of XLA
fusion; a non-dyadic spec (advection) is additionally checked to 1-ulp.

The fused matrix then runs ``policy="temporal"`` over the same meshes at
t in {2, 3} (divisible and remainder cases) for the face and diagonal-tap
specs: the masked temporal kernel advances all t sweeps per shard between
exchanges, and ``engine.plan_distributed`` must report the exchange count
the schedule implies (iters // t fused + one remainder round).

A third matrix forces the exchange-hiding interior/rind overlap on and
off (2 meshes x {jacobi5, diag9} x t in {1, 3}): both modes must stay
bit-exact — overlap reorders the launch, never the arithmetic.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import engine
from repro.core.stencil import (StencilSpec, advection_2d_3pt,
                                jacobi_2d_5pt, make_laplace_problem)

u = make_laplace_problem(32, 64, dtype=jnp.float32)
u = u.at[1:-1, 1:-1].set(jax.random.uniform(jax.random.PRNGKey(0), (32, 64)))
diffusion_row = StencilSpec(offsets=((0, -1), (0, 0), (0, 1)),
                            weights=(0.25, 0.5, 0.25))
# Diagonal taps read the physical ring corners -> exercises corner transport.
diag9 = StencilSpec(offsets=((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1),
                             (1, -1), (1, 0), (1, 1)),
                    weights=(0.125,) * 8)
ITERS = 6
failures = 0
for spec, name in [(jacobi_2d_5pt(), "jacobi5"), (diffusion_row, "diff3"),
                   (diag9, "diag9")]:
    want = np.asarray(engine.run(u, spec, policy="rowchunk", iters=ITERS))
    for mesh_shape, axes in [((4,), ("x",)), ((2, 2), ("x", "y"))]:
        mesh = jax.make_mesh(mesh_shape, axes)
        for policy in ("reference", "shifted", "rowchunk"):
            for t in (1, 3):
                got = np.asarray(engine.run_distributed(
                    u, spec, mesh=mesh, policy=policy, iters=ITERS, t=t))
                exact = bool((got == want).all())
                tag = f"{name} mesh={mesh_shape} {policy} t={t}"
                print(("ok   " if exact else "FAIL ") + tag)
                failures += not exact

# Fused temporal at mesh scale: t sweeps per exchange run inside ONE
# masked kernel invocation per shard (not the single-sweep degenerate).
# t=3 divides ITERS exactly; t=2 leaves a remainder round. The schedule
# must price the exchanges and the result must stay bit-exact.
for spec, name in [(jacobi_2d_5pt(), "jacobi5"), (diag9, "diag9")]:
    want = np.asarray(engine.run(u, spec, policy="rowchunk", iters=ITERS))
    for mesh_shape, axes in [((4,), ("x",)), ((2, 2), ("x", "y"))]:
        mesh = jax.make_mesh(mesh_shape, axes)
        for t in (2, 3):
            sched, _, _ = engine.plan_distributed(
                u.shape, u.dtype, spec, mesh=mesh, policy="temporal",
                iters=ITERS, t=t)
            nfull, rem = divmod(ITERS, t)
            assert sched.policy == "temporal" and sched.fused, sched
            assert sched.exchanges == nfull + (1 if rem else 0), sched
            assert sched.halo_depth == t * spec.radius, sched
            got = np.asarray(engine.run_distributed(
                u, spec, mesh=mesh, policy="temporal", iters=ITERS, t=t))
            exact = bool((got == want).all())
            tag = f"{name} mesh={mesh_shape} temporal-fused t={t} " \
                  f"exchanges={sched.exchanges}"
            print(("ok   " if exact else "FAIL ") + tag)
            failures += not exact

# Exchange-hiding interior/rind split: forced on AND forced off must be
# bit-exact vs the single-device oracle. The split is a schedule-level
# rewrite — interior launched while the exchange is in flight, rind strips
# patched in after — of the SAME f32 tap accumulation, so diagonal-tap
# corner transport included, fp32 equality is exact, not approximate.
for spec, name in [(jacobi_2d_5pt(), "jacobi5"), (diag9, "diag9")]:
    want = np.asarray(engine.run(u, spec, policy="rowchunk", iters=ITERS))
    for mesh_shape, axes in [((4,), ("x",)), ((2, 2), ("x", "y"))]:
        mesh = jax.make_mesh(mesh_shape, axes)
        for t in (1, 3):
            policy = "temporal" if t > 1 else "rowchunk"
            for ovl in (True, False):
                sched, _, _ = engine.plan_distributed(
                    u.shape, u.dtype, spec, mesh=mesh, policy=policy,
                    iters=ITERS, t=t, overlap=ovl)
                assert sched.overlap is ovl, sched
                got = np.asarray(engine.run_distributed(
                    u, spec, mesh=mesh, policy=policy, iters=ITERS, t=t,
                    overlap=ovl))
                exact = bool((got == want).all())
                tag = (f"{name} mesh={mesh_shape} {policy} t={t} "
                       f"overlap={'on' if ovl else 'off'}")
                print(("ok   " if exact else "FAIL ") + tag)
                failures += not exact

# Non-dyadic weights: XLA fusion may differ by 1 ulp between programs.
adv = advection_2d_3pt()
want = np.asarray(engine.run(u, adv, policy="rowchunk", iters=ITERS))
mesh = jax.make_mesh((4,), ("x",))
got = np.asarray(engine.run_distributed(u, adv, mesh=mesh, policy="rowchunk",
                                        iters=ITERS, t=2))
np.testing.assert_allclose(got, want, rtol=0, atol=2e-7)
print("advection close ok")
assert failures == 0, f"{failures} exactness failures"
print("DIST ENGINE OK")
"""


@pytest.mark.slow
def test_run_distributed_matches_engine_run_bitexact():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    assert "DIST ENGINE OK" in proc.stdout


STRIP_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import engine
from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
from repro.dist.stencil import extended_shard_shape
from repro.engine.plan import _window_and_vmem

T, ITERS = 8, 16
u = make_laplace_problem(128, 2300, dtype=jnp.float32)
u = u.at[1:-1, 1:-1].set(jax.random.uniform(jax.random.PRNGKey(0),
                                            (128, 2300)))
spec = jacobi_2d_5pt()
want = np.asarray(engine.run(u, spec, policy="rowchunk", iters=ITERS))
mesh = jax.make_mesh((4,), ("x",))
ext = extended_shard_shape(u.shape, mesh, spec, t=T)
# A fast memory that holds the masked shard kernel at bm=16 and no more,
# as a v5e's does at the paper's width.
budget = _window_and_vmem("temporal", ext, jnp.float32, spec, 16, T,
                          masked=True)[1]
device = dataclasses.replace(engine.get_device("cpu_ref"), name="fits_bm16",
                             fast_memory_bytes=budget)
failures = 0
serial, interior = (engine.plan_for(
    shape, jnp.float32, spec, "temporal", t=T, device=device, masked=True)
    for shape in (ext, (ext[0] - 2 * T, ext[1] - 2 * T)))
assert serial.strip_rows == 8 and serial.nblocks > 1, serial.describe()
assert interior.strip_rows == 8, interior.describe()
for ovl in (True, False):
    got = np.asarray(engine.run_distributed(
        u, spec, mesh=mesh, policy="temporal", iters=ITERS, t=T,
        overlap=ovl, device=device))
    exact = bool((got == want).all())
    print(("ok   " if exact else "FAIL ") + f"overlap={ovl}")
    failures += not exact
assert failures == 0, f"{failures} exactness failures"
print("STRIP SHARDS OK")
"""


def test_run_distributed_strip_kernel_matches_engine_run():
    """The masked strip kernel as a row mesh runs it: four 32-row shards
    of a 2300-column grid, so the middle shards' exchanged halo rows are
    unpinned and evolve, in 16-row blocks, with the interior/rind overlap
    off and on (the interior launch sweeps the raw shard, in strips too,
    with an all-zero mask). Bit-exact against ``engine.run``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", STRIP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    assert "STRIP SHARDS OK" in proc.stdout
