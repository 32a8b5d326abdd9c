#!/usr/bin/env python3
"""Bring-up check: the stencil-solve path on a TPU at the paper's size.

The paper's domain is a 1024x9216 interior (a 1026x9218 ringed grid)
advanced 5000 Jacobi sweeps, in bf16 as in ``configs/jacobi2d.py``, plus
f32. The problem's interior is random, drawn from ``--seed``. Everything
runs in this one process, through the entry points users call:

* ``engine.run`` under each policy and under ``policy="auto"``;
* ``engine.run_converged`` to a residual tolerance;
* a ``SolveServer`` answering mixed-tolerance requests, plus a lone
  request that takes its ``run_converged`` bypass;
* ``python -m repro.launch.solve`` (its ``main``, called in-process)
  for a fixed-sweep, a tolerance-driven and a served solve, each with
  ``--check``.

Every result is compared with the pure-jnp oracle (``kernels.ref.sweeps``).
f32 must match to the CPU tests' tolerance (rtol 1e-5, atol 1e-6).
bf16 is compared with an oracle that rounds to bf16 where the policy
does (every sweep, or every t fused sweeps); the tap products are exact
(weights 0.25) and the f32 sums run in the same order, so the two should
agree bit for bit, and the gate allows one bf16 ulp below 1 (2**-8).
bf16 is not held to the f32 oracle: after 5000 sweeps the bf16 iteration
has stalled wherever an update is below half an ulp, and its gap to f32
is large (0.44 at 256x2304 on a CPU host); that gap is printed, not gated.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # run_distributed on a 2x2 host

``--four-chips`` runs only ``run_distributed`` over (4,) and (2, 2)
meshes, with rowchunk and fused temporal, against single-chip
``engine.run`` (bit-exact in f32). Each phase prints one line; the last
line is a JSON object with ``"ok": true`` and the device, printed only
when every phase passed. Exits non-zero, without that line, when JAX
finds no TPU or any phase fails. No child processes are started.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
NY, NX, ITERS = 1024, 9216, 5000
F32_TOL = (1e-5, 1e-6)         # rtol, atol: the CPU tests' tolerance
BF16_TOL = (0.0, 2.0 ** -8)    # one bf16 ulp in [0.5, 1)
POLICIES = ("shifted", "rowchunk", "dbuf", "temporal", "auto")


def _line(tag: str, **kv) -> None:
    print(tag + " " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class Smoke:
    def __init__(self, seed: int):
        import jax
        import jax.numpy as jnp
        from repro import engine
        from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
        from repro.kernels import ref
        self.jax, self.jnp, self.engine, self.ref = jax, jnp, engine, ref
        self.spec = jacobi_2d_5pt()
        self._residual = jax.jit(engine.residual_for(self.spec))
        self.seed = seed
        self._make = make_laplace_problem
        self.failed: list[str] = []

    # ------------------------------------------------------------ helpers

    def problem(self, dtype, seed: int):
        """The paper's ringed grid with a random interior in [0, 1)."""
        jax, jnp = self.jax, self.jnp
        u = self._make(NY, NX, dtype=dtype, left=1.0, right=0.0)
        noise = jax.random.uniform(jax.random.PRNGKey(seed), (NY, NX))
        return u.at[1:-1, 1:-1].set(noise.astype(dtype))

    def fuse(self, policy: str, t: int) -> int:
        if policy == "reference" or not self.engine.get_policy(policy).fused:
            return 1
        return t

    def compare(self, got, want, dtype) -> tuple[float, bool]:
        """Max |got - want| and whether every cell is within tolerance."""
        jnp = self.jnp
        rtol, atol = F32_TOL if dtype == jnp.float32 else BF16_TOL
        g = got.astype(jnp.float32)
        w = want.astype(jnp.float32)
        d = jnp.abs(g - w)
        ok = bool(jnp.all(d <= atol + rtol * jnp.abs(w)))
        return float(jnp.max(d)), ok

    def residual(self, u) -> float:
        return float(self._residual(u))

    def phase(self, name: str, fn, *args) -> None:
        try:
            fn(*args)
        except BaseException as e:  # noqa: BLE001 — SystemExit included
            if isinstance(e, KeyboardInterrupt):
                raise
            self.failed.append(name)
            _line("FAIL", phase=name, error=repr(e)[:500])

    def timed(self, fn):
        """(result, first-call wall incl. compile, steady-call wall)."""
        block = self.jax.block_until_ready
        t0 = time.perf_counter()
        block(fn())
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = block(fn())
        return out, first, time.perf_counter() - t0

    # ------------------------------------------------------------- phases

    def engine_run(self, dtype) -> None:
        engine, jnp = self.engine, self.jnp
        name = jnp.dtype(dtype).name
        u = self.problem(dtype, self.seed)
        oracles = {}
        bad = []
        for policy in POLICIES:
            sched = engine.build_schedule(ITERS, spec=self.spec,
                                          shape=u.shape, dtype=dtype,
                                          policy=policy)
            plan = engine.plan_for(u.shape, dtype, self.spec, sched.policy,
                                   t=sched.t if sched.fused else None)
            fuse = self.fuse(sched.policy, sched.t)
            if fuse not in oracles:
                oracles[fuse] = self.ref.sweeps(u, ITERS, self.spec,
                                                fuse=fuse)
            out, first, wall = self.timed(lambda: engine.run(
                u, self.spec, policy=policy, iters=ITERS))
            err, ok = self.compare(out, oracles[fuse], dtype)
            _line("engine.run", dtype=name,
                  policy=f"{policy}->{sched.policy}", t=sched.t,
                  bm=plan.bm, blocks=plan.nblocks, sweeps=ITERS,
                  first_call_s=f"{first:.3f}", run_s=f"{wall:.3f}",
                  gpts=f"{NY * NX * ITERS / wall / 1e9:.2f}",
                  residual=f"{self.residual(out):.4e}",
                  max_err=f"{err:.3e}", ok=ok)
            if policy == "auto" and dtype != jnp.float32:
                f32 = self.ref.sweeps(u.astype(jnp.float32), ITERS, self.spec)
                gap, _ = self.compare(out, f32, jnp.float32)
                _line("engine.run", dtype=name, policy="auto",
                      gap_vs_f32_oracle=f"{gap:.3e}", gated=False)
            if not ok:
                bad.append(policy)
        assert not bad, f"{name}: {bad} differ from the oracle"

    def run_converged(self, dtype, tol: float) -> None:
        engine, jnp = self.engine, self.jnp
        name = jnp.dtype(dtype).name
        u = self.problem(dtype, self.seed + 1)
        cadence = engine.effective_depth(ITERS, None)
        sched = engine.build_schedule(cadence, spec=self.spec, shape=u.shape,
                                      dtype=dtype, policy="auto", t=cadence)
        (out, n, res), first, wall = self.timed(lambda: engine.run_converged(
            u, self.spec, tol=tol, max_iters=ITERS, policy="auto"))
        want = self.ref.sweeps(u, n, self.spec,
                               fuse=self.fuse(sched.policy, sched.t))
        err, ok = self.compare(out, want, dtype)
        _line("engine.run_converged", dtype=name, policy=sched.policy,
              tol=tol, sweeps=f"{n}/{ITERS}", residual=f"{res:.4e}",
              first_call_s=f"{first:.3f}", run_s=f"{wall:.3f}",
              max_err=f"{err:.3e}", ok=ok)
        assert ok, f"{name}: run_converged differs from the oracle"
        assert res <= tol or n == ITERS, (res, tol, n)

    def serve(self, dtype, tols: tuple, lone_tol: float) -> None:
        from repro.obs.trace import Tracer
        from repro.serve import SolveRequest, SolveServer
        jnp = self.jnp
        name = jnp.dtype(dtype).name
        tracer = Tracer()
        server = SolveServer(tracer=tracer)
        reqs = [SolveRequest(grid=self.problem(dtype, self.seed + 10 + i),
                             tol=tol, max_iters=ITERS)
                for i, tol in enumerate(tols)]
        t0 = time.perf_counter()
        server.solve(reqs)
        batch_s = time.perf_counter() - t0
        lone = SolveRequest(grid=self.problem(dtype, self.seed + 20),
                            tol=lone_tol, max_iters=ITERS)
        t0 = time.perf_counter()
        server.solve([lone])
        lone_s = time.perf_counter() - t0
        lone_spans = [e for e in tracer.events
                      if e.name == "serve.block" and e.attrs.get("lone")]
        bad = []
        for i, r in enumerate(reqs + [lone]):
            key = r.key
            want = self.ref.sweeps(self.problem(dtype, self.seed + (
                20 if r is lone else 10 + i)), r.iters_done, self.spec,
                fuse=self.fuse(key.policy, key.t))
            err, ok = self.compare(jnp.asarray(r.result), want, dtype)
            consistent = (r.residual <= r.tol if r.converged
                          else r.iters_done == (ITERS // key.t) * key.t)
            _line("serve.request", dtype=name, lone=r is lone,
                  policy=key.policy, t=key.t, tol=r.tol,
                  sweeps=f"{r.iters_done}/{ITERS}", converged=r.converged,
                  residual=f"{r.residual:.4e}",
                  latency_s=f"{r.latency_s:.3f}", max_err=f"{err:.3e}",
                  ok=ok and consistent)
            if not (ok and consistent):
                bad.append(i)
        st = server.stats()
        _line("serve", dtype=name, requests=len(reqs) + 1,
              launches=st["launches"], evicted_early=st["evicted_early"],
              buckets=st["buckets"], batch_wall_s=f"{batch_s:.3f}",
              lone_wall_s=f"{lone_s:.3f}", lone_bypass=bool(lone_spans),
              interpret=key.interpret)
        assert not bad, f"{name}: requests {bad} wrong"
        assert lone_spans, "the lone request did not take the bypass"
        assert key.interpret is False

    def launch_solve(self, dtype, tol: float) -> None:
        from repro.launch import solve
        name = self.jnp.dtype(dtype).name
        base = ["--ny", str(NY), "--nx", str(NX), "--iters", str(ITERS),
                "--kernel", "auto", "--dtype", name, "--check"]
        for extra in ([], ["--tol", str(tol)],
                      ["--serve", "--tol", str(tol)]):
            _line("launch.solve", args=" ".join(base + extra))
            solve.main(base + extra)

    def distributed(self) -> None:
        jax, jnp, engine = self.jax, self.jnp, self.engine
        dtype = jnp.float32
        u = self.problem(dtype, self.seed)
        meshes = {"4": jax.make_mesh((4,), ("x",)),
                  "2x2": jax.make_mesh((2, 2), ("x", "y"))}
        bad = []
        for policy, t in (("rowchunk", 1), ("temporal", 8)):
            want, _, ref_s = self.timed(lambda: engine.run(
                u, self.spec, policy=policy, iters=ITERS, t=t))
            _line("engine.run", dtype="float32", policy=policy, t=t,
                  sweeps=ITERS, run_s=f"{ref_s:.3f}",
                  device=str(list(want.sharding.device_set)[0]))
            for mname, mesh in meshes.items():
                sched, shard, _ = engine.plan_distributed(
                    u.shape, dtype, self.spec, mesh=mesh, policy=policy,
                    iters=ITERS, t=t)
                out, first, wall = self.timed(lambda: engine.run_distributed(
                    u, self.spec, mesh=mesh, policy=policy, iters=ITERS,
                    t=t))
                ndev = len(out.sharding.device_set)
                err = float(jnp.max(jnp.abs(out - want)))
                exact = bool(jnp.array_equal(out, want))
                _line("run_distributed", dtype="float32", mesh=mname,
                      policy=sched.policy, t=sched.t,
                      exchanges=sched.exchanges, overlap=sched.overlap,
                      shard=f"{shard[0]}x{shard[1]}", sweeps=ITERS,
                      first_call_s=f"{first:.3f}", run_s=f"{wall:.3f}",
                      output_devices=ndev, max_err=f"{err:.3e}",
                      bitexact=exact)
                if not (exact and ndev == 4):
                    bad.append(f"{mname}/{policy}")
        assert not bad, f"not bit-exact over 4 devices: {bad}"

    def interpret_check(self) -> None:
        from repro.obs import metrics
        n_int = metrics.counter("engine.kernel.interpret").value
        n_comp = metrics.counter("engine.kernel.compiled").value
        _line("kernels", compiled_traces=int(n_comp),
              interpret_traces=int(n_int))
        assert n_int == 0 and n_comp > 0, (n_int, n_comp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_distributed over 4 chips and its "
                         "single-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips; found {len(devs)}",
              file=sys.stderr)
        return 2
    import jax.numpy as jnp
    from repro import engine
    model = engine.detect()
    _line("device", platform=devs[0].platform,
          kind=repr(devs[0].device_kind), count=len(devs),
          model=model.name, jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    s = Smoke(args.seed)
    if args.four_chips:
        s.phase("run_distributed", s.distributed)
    else:
        for dtype, tols, lone, conv in (
                (jnp.bfloat16, (None, 1e-1, 6e-2, 5e-2), 6e-2, 5e-2),
                (jnp.float32, (None, 5e-2, 3e-2, 2e-2), 3e-2, 2e-2)):
            name = jnp.dtype(dtype).name
            s.phase(f"engine.run[{name}]", s.engine_run, dtype)
            s.phase(f"run_converged[{name}]", s.run_converged, dtype, conv)
            s.phase(f"serve[{name}]", s.serve, dtype, tols, lone)
            s.phase(f"launch.solve[{name}]", s.launch_solve, dtype, conv)
    s.phase("interpret", s.interpret_check)
    _line("done", wall_s=f"{time.perf_counter() - t0:.1f}",
          failed=",".join(s.failed) or "none")
    if s.failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
