"""End-to-end driver for the paper's own workload: distributed Jacobi solve.

Runs Laplace diffusion on a ringed grid with any kernel generation, over
however many devices this host exposes (decomposed like the paper's
cores-in-Y x cores-in-X), and reports GPt/s + the converged residual.

  PYTHONPATH=src python -m repro.launch.solve --ny 1024 --nx 9216 \
      --iters 500 --kernel temporal --devices 8 --t 8

(--devices N>1 needs N devices: N chips of a TPU host, or on a CPU host
XLA_FLAGS=--xla_force_host_platform_device_count=N)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ny", type=int, default=512)
    ap.add_argument("--nx", type=int, default=512)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--kernel", default="ref",
                    choices=["ref", "v0", "v1", "v1db", "v2",
                             "reference", "shifted", "rowchunk", "dbuf",
                             "temporal", "auto", "tuned"],
                    help="engine policy name (legacy v* tags still accepted; "
                         "'tuned' measures once and caches the winner)")
    ap.add_argument("--temporal", type=int, default=8,
                    help="temporal-policy fusion depth")
    ap.add_argument("--t", type=int, default=None,
                    help="sweeps per fused block / halo exchange; overrides "
                         "--temporal (single device) and --depth "
                         "(distributed, where t fused sweeps run per shard "
                         "between t*r-deep exchanges — the "
                         "communication-avoiding schedule)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--device-model", default=None,
                    help="device registry name to plan against (e.g. "
                         "tpu_v5e, grayskull_e150); default: detect the "
                         "host backend")
    ap.add_argument("--backend", default="jax", choices=["jax", "sim"],
                    help="'jax' runs the Pallas/XLA engine; 'sim' lowers "
                         "the policy to a Tensix-style three-kernel "
                         "program and runs the functional simulator "
                         "(repro.backends), reporting modeled GPt/s and "
                         "per-kernel counters for the device model")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--depth", type=int, default=1,
                    help="halo exchange depth (sweeps per exchange)")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "on", "off"],
                    help="hide each halo exchange behind the shard's "
                         "halo-independent interior compute, patching the "
                         "rind in after arrival (distributed only; "
                         "bit-exact either way). 'auto' lets the schedule "
                         "price it against --device-model")
    ap.add_argument("--serve", action="store_true",
                    help="route the solve through repro.serve.SolveServer "
                         "as a thin client: admission, bucketing, one "
                         "vmapped launch per block of t sweeps, and "
                         "residual-based eviction (with --tol)")
    ap.add_argument("--tol", type=float, default=None,
                    help="residual tolerance: stop at the first block of "
                         "t sweeps whose max-norm update delta is <= TOL "
                         "instead of running all --iters sweeps. With "
                         "--serve the server evicts the solve; without it "
                         "engine.run_converged runs the residual check "
                         "inside one lax.while_loop launch (single "
                         "device, jax backend)")
    ap.add_argument("--check", action="store_true",
                    help="verify against the single-device reference")
    ap.add_argument("--verify", action="store_true",
                    help="statically verify the chosen schedule (and, when "
                         "the policy lowers, the Tensix program) before "
                         "execution and print the diagnostic report")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a repro.obs trace of the run and write it "
                         "as Chrome-trace JSON (open in Perfetto / "
                         "chrome://tracing, or inspect with "
                         "'python -m repro.obs summarize PATH'). With "
                         "--devices N the distributed executor runs its "
                         "span-per-phase form: one exchange/interior/rind "
                         "span per halo round, each carrying the round's "
                         "modeled ExchangeBill")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.obs.compare import reconcile
    from repro.obs.trace import Tracer, use_tracer

    if args.trace or args.serve:
        # --serve always installs a tracer so the per-block progress sink
        # has serve.block spans to print; the file is written on --trace.
        tracer = Tracer(sink=_serve_progress if args.serve else None)
        with use_tracer(tracer):
            _dispatch(args)
        if args.trace:
            tracer.write_trace(args.trace)
            print(f"trace: {len(tracer.events)} spans, "
                  f"{len(tracer.counters)} counter samples -> {args.trace}")
            print(tracer.describe())
            print(reconcile(tracer).describe())
    else:
        _dispatch(args)


def _serve_progress(ev) -> None:
    """Tracer sink: one compact line per completed ``serve.block`` span."""
    if getattr(ev, "name", None) != "serve.block":
        return
    a = ev.attrs
    mr = a.get("max_residual")
    print(f"[serve] launch={a.get('launch', '?')} "
          f"blocks={a.get('blocks', 1)}{' lone' if a.get('lone') else ''} "
          f"active={a.get('active')} queue={a.get('queue')} "
          f"max_residual={'?' if mr is None else format(mr, '.3e')} "
          f"wall={ev.dur_us / 1e3:.1f}ms")


def _dispatch(args):
    from repro import engine
    from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
    from repro.kernels.ops import VERSION_TO_POLICY
    from repro.obs.trace import get_tracer

    device = engine.get_device(args.device_model).name \
        if args.device_model else None
    if device:
        print(f"planning for {engine.get_device(device).describe()}")

    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    u0 = make_laplace_problem(args.ny, args.nx, dtype=dtype,
                              left=1.0, right=0.0)

    def _verify(policy, t_fuse, mesh_shape=None):
        """Static pre-flight: schedule feasibility + program protocol."""
        from repro.analysis import check_schedule
        from repro.backends.lower import (LoweringError, lower,
                                          lowerable_policies)
        spec = jacobi_2d_5pt()
        sched = engine.build_schedule(
            args.iters, spec=spec, shape=u0.shape, dtype=u0.dtype,
            policy=policy, t=t_fuse, device=device,
            mesh_shape=mesh_shape, exchange_cadence=mesh_shape is not None)
        prog = None
        if sched.policy in lowerable_policies():
            try:
                prog = lower(u0.shape, u0.dtype, spec, sched.policy,
                             t=sched.t if sched.fused else None,
                             device=device)
            except LoweringError as e:
                print(f"verify: lowering rejected — {e}")
                raise SystemExit(1)
        report = check_schedule(sched, shape=u0.shape, dtype=u0.dtype,
                                spec=spec, device=device,
                                mesh_shape=mesh_shape, program=prog)
        print(f"verify: {report.describe()}")
        if not report.ok:
            raise SystemExit(1)

    if args.serve:
        # Thin client of the solve server: one request through the full
        # admission -> bucket -> vmapped-launch -> evict lifecycle.
        from repro.serve import SolveRequest, SolveServer
        if args.devices > 1 or args.backend != "jax":
            raise SystemExit("--serve drives the single-device jax engine; "
                             "drop --devices/--backend")
        policy = VERSION_TO_POLICY.get(args.kernel, args.kernel)
        if policy in ("ref", "reference"):
            policy = "reference"
        t_fuse = args.t if args.t is not None else args.temporal
        if args.verify and policy != "reference":
            _verify(policy, t_fuse)
        server = SolveServer(device=device)
        req = SolveRequest(grid=u0, tol=args.tol, max_iters=args.iters,
                           policy=policy, t=t_fuse)
        server.submit(req)
        print(f"bucket: {req.key.describe()}  "
              f"target_blocks={req.target_blocks}")
        t0 = time.perf_counter()
        server.drain()
        dt = time.perf_counter() - t0
        result = np.asarray(req.result, np.float32)[1:-1, 1:-1]
        stats = server.stats()
        gpts = args.ny * args.nx * req.iters_done / dt / 1e9
        print(f"kernel={args.kernel} serve=1 grid={args.ny}x{args.nx} "
              f"iters={req.iters_done}/{args.iters} "
              f"(evicted_early={stats['evicted_early']} "
              f"launches={stats['launches']})")
        print(f"wall={dt:.3f}s  GPt/s={gpts:.3f}  "
              f"residual={req.residual:.3e}  "
              f"mean={result.mean():.6f}  max={result.max():.6f}")
        if args.check:
            _check(result, u0, req.iters_done,
                   _fuse(req.key.policy, req.key.t), dtype)
        return

    if args.backend == "sim":
        # Lower to the decoupled reader/compute/writer program and run the
        # functional simulator: numbers + modeled cost, no XLA involved.
        from repro import backends
        from repro.backends.report import summarize
        if args.devices > 1:
            raise SystemExit("--backend sim models one chip's core grid; "
                             "drop --devices (cores are simulated inside)")
        policy = VERSION_TO_POLICY.get(args.kernel, args.kernel)
        if policy in ("ref", "reference"):
            policy = "rowchunk"  # the oracle has no lowering; use §VI
        t_fuse = args.t if args.t is not None else args.temporal
        if args.verify:
            _verify(policy, t_fuse)
        t0 = time.perf_counter()
        res = backends.simulate(u0, policy=policy, iters=args.iters,
                                t=t_fuse, device=device)
        dt = time.perf_counter() - t0
        s = summarize(res)
        result = np.asarray(res.grid, np.float32)[1:-1, 1:-1]
        print(res.programs[0].describe())
        print(f"kernel={s['policy']} backend=sim device={s['device']} "
              f"grid={args.ny}x{args.nx} iters={args.iters} "
              f"cores={s['cores_used']}")
        print(f"sim_wall={dt:.3f}s  model={s['model_time_s']:.6f}s  "
              f"model_GPt/s={s['gpts']:.3f}  "
              f"model_energy_J={s['energy_j']:.3f} (MODELED)  "
              f"bytes/pt={s['bytes_per_point']:.2f}  "
              f"dram_txns={s['dram_txns']}")
        sim_res = float(engine.residual_for()(jnp.asarray(res.grid)))
        print(f"residual={sim_res:.3e}  mean={float(result.mean()):.6f}  "
              f"max={float(result.max()):.6f}")
        if args.check:
            _check(result, u0, args.iters, 1, dtype)
        return

    if args.devices > 1:
        # Any kernel policy runs per shard inside the depth-t halo loop —
        # the distributed solve is no longer a separate hard-coded path.
        ndev = len(jax.devices())
        if ndev < args.devices:
            hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count={args.devices} to split the CPU host"
                    if jax.default_backend() == "cpu" else
                    f"run on a host with {args.devices} "
                    f"{jax.default_backend().upper()} chips")
            raise SystemExit(f"--devices {args.devices}: this host exposes "
                             f"{ndev} {jax.default_backend()} device(s); "
                             f"{hint}")
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:args.devices]), ("x",))
        policy = VERSION_TO_POLICY.get(args.kernel, args.kernel)
        if policy in ("ref", "reference"):
            policy = "reference"
        # --t is the sweeps-per-exchange knob; fused policies run all t
        # sweeps per shard in one kernel between t*r-deep exchanges.
        t_fuse = args.t if args.t is not None else args.depth
        overlap = {"auto": None, "on": True, "off": False}[args.overlap]
        if args.verify:
            _verify(policy, t_fuse, mesh_shape=(args.devices,))
        sched, shard_shape, _ = engine.plan_distributed(
            u0.shape, u0.dtype, mesh=mesh, policy=policy, iters=args.iters,
            t=t_fuse, row_axis="x", device=device, overlap=overlap)
        print(f"schedule: {sched.describe()}  shard={shard_shape}")
        bill = engine.price_exchange(sched, shard_shape=shard_shape,
                                     dtype=u0.dtype, spec=jacobi_2d_5pt(),
                                     device=device,
                                     mesh_shape=(args.devices,))
        print(f"exchange bill: {bill.describe()}")
        if get_tracer() is not None:
            # Traced: run eagerly so the executor's span-per-phase form
            # engages (an outer jit would fold the spans into trace time
            # and hide the per-round exchange/interior/rind splits).
            t0 = time.perf_counter()
            out = jax.block_until_ready(engine.run_distributed(
                u0, mesh=mesh, policy=policy, iters=args.iters, t=t_fuse,
                row_axis="x", device=device, overlap=overlap))
            dt = time.perf_counter() - t0
        else:
            run = jax.jit(lambda u: engine.run_distributed(
                u, mesh=mesh, policy=policy, iters=args.iters, t=t_fuse,
                row_axis="x", device=device, overlap=overlap))
            run(u0).block_until_ready()  # compile
            t0 = time.perf_counter()
            out = run(u0)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        result = np.asarray(out, np.float32)[1:-1, 1:-1]
        fuse = _fuse(sched.policy, sched.t)
    else:
        policy = VERSION_TO_POLICY.get(args.kernel, args.kernel)
        if policy == "ref":
            policy = "reference"
        if args.tol is not None:
            # Tolerance-driven solve without the server: ONE cached
            # lax.while_loop launch with the residual check in-launch
            # (engine.run_converged) — no host round-trip per block.
            t_fuse = args.t if args.t is not None else args.temporal
            if args.verify and policy != "reference":
                _verify(policy, t_fuse)
            engine.run_converged(u0, tol=args.tol, max_iters=args.iters,
                                 policy=policy, t=t_fuse,
                                 device=device)  # compile
            t0 = time.perf_counter()
            out, iters_done, res = engine.run_converged(
                u0, tol=args.tol, max_iters=args.iters, policy=policy,
                t=t_fuse, device=device)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            result = np.asarray(out, np.float32)[1:-1, 1:-1]
            gpts = args.ny * args.nx * max(iters_done, 1) / dt / 1e9
            print(f"kernel={args.kernel} tol={args.tol:g} "
                  f"grid={args.ny}x{args.nx} "
                  f"iters={iters_done}/{args.iters} (launch=while_loop)")
            print(f"wall={dt:.3f}s  GPt/s={gpts:.3f}  "
                  f"residual={res:.3e}  mean={result.mean():.6f}  "
                  f"max={result.max():.6f}")
            if args.check:
                cadence = engine.effective_depth(args.iters, t_fuse)
                sched = engine.build_schedule(
                    cadence, spec=jacobi_2d_5pt(), shape=u0.shape,
                    dtype=u0.dtype, policy=policy, t=cadence,
                    device=device)
                _check(result, u0, iters_done,
                       _fuse(sched.policy, sched.t), dtype)
            return
        fuse = 1
        if policy == "reference":
            from repro.core import jacobi as J
            run = jax.jit(lambda u: J.jacobi_run(u, args.iters))
        else:
            t_fuse = args.t if args.t is not None else args.temporal
            sched = engine.build_schedule(
                args.iters, spec=jacobi_2d_5pt(), shape=u0.shape,
                dtype=u0.dtype, policy=policy, t=t_fuse, device=device)
            fuse = _fuse(sched.policy, sched.t)
            if args.verify:
                _verify(policy, t_fuse)
            if get_tracer() is not None:
                # Traced: eager call so engine.run's span measures real
                # wall-clock (the policy kernels are jitted inside).
                t0 = time.perf_counter()
                out = jax.block_until_ready(engine.run(
                    u0, policy=policy, iters=args.iters, t=t_fuse,
                    device=device))
                dt = time.perf_counter() - t0
                result = np.asarray(out, np.float32)[1:-1, 1:-1]
                _report(args, out, result, dt, fuse)
                return
            run = jax.jit(lambda u: engine.run(
                u, policy=policy, iters=args.iters, t=t_fuse,
                device=device))
        run(u0).block_until_ready()
        t0 = time.perf_counter()
        out = run(u0)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        result = np.asarray(out, np.float32)[1:-1, 1:-1]

    _report(args, out, result, dt, fuse)


def _fuse(policy: str, t: int) -> int:
    """Sweeps between bf16 roundings for a resolved policy: ``t`` for a
    fused policy, 1 otherwise (what the oracle must mimic)."""
    from repro import engine
    if policy == "reference" or not engine.get_policy(policy).fused:
        return 1
    return t


def _check(result, u0, iters: int, fuse: int, dtype) -> None:
    """Compare a solve's interior with the pure-jnp oracle at ``iters``
    sweeps, rounded to the grid dtype where the policy rounds.

    f32 must agree to 1e-4. bf16 gets 5e-2: the oracle rounds at the same
    points as the kernels, so only a tap-sum order or rounding-mode
    difference could separate them, and one ulp of bf16 at these values
    is 2**-8; the bound leaves room for such an ulp to spread while
    still catching a wrong tap or a misplaced halo row.
    """
    from repro.kernels import ref
    want = np.asarray(ref.sweeps(u0, iters, fuse=fuse),
                      np.float32)[1:-1, 1:-1]
    err = np.abs(np.asarray(result, np.float32) - want).max()
    print(f"max |err| vs reference at {iters} iters: {err:.3e}")
    assert err < (1e-4 if dtype == jnp.float32 else 5e-2), err
    print("CHECK OK")


def _report(args, out, result, dt, fuse: int = 1):
    """The shared kernel/wall/GPt/s/residual report + optional --check."""
    from repro import engine
    gpts = args.ny * args.nx * args.iters / dt / 1e9
    # The converged residual, through the same engine helper the solve
    # server's eviction check uses.
    res = float(jax.jit(engine.residual_for())(out))
    print(f"kernel={args.kernel} devices={args.devices} "
          f"t={args.t if args.t is not None else args.depth} "
          f"grid={args.ny}x{args.nx} iters={args.iters}")
    print(f"wall={dt:.3f}s  GPt/s={gpts:.3f}  residual={res:.3e}  "
          f"mean={result.mean():.6f}  max={result.max():.6f}")

    if args.check:
        from repro.core.stencil import make_laplace_problem
        dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
        u0 = make_laplace_problem(args.ny, args.nx, dtype=dtype,
                                  left=1.0, right=0.0)
        _check(result, u0, args.iters, fuse, dtype)


if __name__ == "__main__":
    main()
