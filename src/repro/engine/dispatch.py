"""Variant registry and dispatch for the stencil engine.

Every execution policy registers itself here with enough metadata for the
benchmark tables to enumerate variants (name, paper provenance, modeled
bytes/point) — no caller keeps a hand-written kernel list. ``run`` is the
public entry point: pick a policy (``"auto"`` consults the device-aware
heuristic, ``"tuned"`` the measured cache in :mod:`repro.engine.tune`),
advance any 2-D ``StencilSpec`` (or a 3-D one, under the temporal kernel)
any number of sweeps on any registered
:class:`~repro.engine.device.DeviceModel`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilSpec, jacobi_2d_5pt
from repro.engine import policies as P
from repro.engine.device import DeviceModel, get_device
from repro.engine.plan import DEFAULT_T, PlanError, default_depth, plan_for
from repro.engine.schedule import DEFAULT_REMAINDER_POLICY  # noqa: F401
from repro.engine.schedule import build_schedule
from repro.obs import metrics as _metrics
from repro.obs.trace import NULL_SPAN
from repro.obs.trace import span as _obs_span


@dataclasses.dataclass(frozen=True)
class Policy:
    """A registered execution policy.

    fn(u, spec, *, bm=None, interpret=False[, t=None]) advances the grid by
    one sweep (``fused=False``) or by ``t`` sweeps (``fused=True``).
    ``bytes_per_point(spec, dtype_bytes, t)`` is the HBM traffic model per
    interior point per sweep used by the roofline-derived benchmark columns.
    """

    name: str
    fn: Callable
    description: str
    paper_ref: str
    fused: bool
    bytes_per_point: Callable[[StencilSpec, int, int], float]


_REGISTRY: dict[str, Policy] = {}


def register_policy(policy: Policy) -> Policy:
    if policy.name in _REGISTRY:
        raise ValueError(f"policy {policy.name!r} already registered")
    _REGISTRY[policy.name] = policy
    return policy


def get_policy(name: str) -> Policy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown policy {name!r}; registered: {available_policies()}"
        ) from None


def available_policies() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def registry() -> tuple[Policy, ...]:
    """All registered policies, in registration (paper-arc) order."""
    return tuple(_REGISTRY.values())


register_policy(Policy(
    name="shifted",
    fn=P.stencil_shifted,
    description="one materialized shifted HBM copy per tap",
    paper_ref="§IV initial design (Table I 'initial')",
    fused=False,
    # taps operand reads + the source read XLA does to build the shifts + 1 write
    bytes_per_point=lambda spec, db, t: db * (spec.taps + 2),
))
register_policy(Policy(
    name="rowchunk",
    fn=P.stencil_rowchunk,
    description="contiguous row-chunk DMA + in-VMEM tap views",
    paper_ref="§VI optimized design (Table I 'write optimised')",
    fused=False,
    bytes_per_point=lambda spec, db, t: db * 2,  # 1 read + 1 write, halo amortized
))
register_policy(Policy(
    name="dbuf",
    fn=P.stencil_dbuf,
    description="rowchunk with double-buffered prefetching data mover",
    paper_ref="Table I 'double buffering'",
    fused=False,
    bytes_per_point=lambda spec, db, t: db * 2,
))
register_policy(Policy(
    name="temporal",
    fn=P.stencil_temporal,
    description="T sweeps fused per HBM round-trip (T*r-deep halos)",
    paper_ref="beyond paper (§VII communication-avoiding direction)",
    fused=True,
    bytes_per_point=lambda spec, db, t: db * 2 / max(t, 1),
))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_auto(shape, dtype, spec: StencilSpec, *, iters: int = 1,
                 t: int | None = None,
                 device: str | DeviceModel | None = None,
                 masked: bool = False) -> str:
    """Pick a policy from a fast-memory/traffic heuristic for ``device``.

    Temporal blocking wins whenever several sweeps can amortize one HBM
    round-trip and its (t*r)-deep halo window passes plan validation *on
    that device*; with a multi-block grid the double-buffered mover hides
    DMA latency; a single resident block leaves nothing to prefetch, so
    plain rowchunk. The crossover points therefore move with the device:
    a window that fits 16 MiB of v5e VMEM can overflow the 1.5 MiB Tensix
    SRAM of ``grayskull_e150``, demoting temporal -> dbuf -> shifted.
    ``masked`` probes the temporal candidate in its masked
    (distributed-shard) form, whose pin-mask stream costs extra fast
    memory — the form the distributed executor will actually launch.
    Every candidate is planned before it is returned, so the answer is
    always a policy whose plan validates; when none does, ``PlanError``
    lists why each was refused. A 3-D grid has one candidate, the
    temporal kernel (even at ``t=1``): the other policies are 2-D.
    """
    if spec.ndim == 3:
        t_eff = t if t is not None else default_depth(shape, dtype, spec,
                                                      device)
        plan_for(shape, dtype, spec, "temporal", t=min(t_eff, max(iters, 1)),
                 device=device, masked=masked)
        return "temporal"
    t_eff = t if t is not None else min(DEFAULT_T, max(iters, 1))
    if iters >= 2 and t_eff >= 2:
        try:
            plan_for(shape, dtype, spec, "temporal", t=min(t_eff, iters),
                     device=device, masked=masked)
            return "temporal"
        except PlanError:
            pass
    tried = []
    for name in ("dbuf", "rowchunk", "shifted"):
        try:
            plan = plan_for(shape, dtype, spec, name, device=device)
        except PlanError as e:
            tried.append(f"{name}: {e}")
            continue
        if name == "dbuf" and plan.nblocks < 2:
            continue  # one resident block leaves nothing to prefetch
        return name
    raise PlanError(f"no policy plans for grid {tuple(shape)} "
                    f"({jnp.dtype(dtype).name}): " + "; ".join(tried))


def _resolve_device_name(device: str | DeviceModel | None
                         ) -> str | DeviceModel | None:
    """Normalize to a hashable static value for the jitted policy wrappers.

    Registry names are validated and stay names; DeviceModel instances pass
    through whole (frozen dataclasses hash fine, and an *unregistered*
    model has no name the planner could resolve later); None stays None so
    the planner detects the host backend.
    """
    if device is None or isinstance(device, DeviceModel):
        return device
    return get_device(device).name


def step(u: jax.Array, spec: StencilSpec | None = None, *,
         policy: str = "auto", bm: int | None = None, t: int | None = None,
         interpret: bool | None = None,
         device: str | DeviceModel | None = None) -> jax.Array:
    """One kernel invocation: a single sweep, or ``t`` fused sweeps for the
    temporal policy."""
    spec = spec if spec is not None else jacobi_2d_5pt()
    if interpret is None:
        interpret = not _on_tpu()
    device = _resolve_device_name(device)
    if policy in ("auto", "tuned"):
        # A single step must advance exactly one sweep, so auto/tuned
        # pick a fused policy here only on a 3-D grid, at t=1 (run() with
        # iters fuses).
        policy, t = resolve_auto(u.shape, u.dtype, spec, iters=1, t=1,
                                 device=device), 1
    p = get_policy(policy)
    if p.fused:
        return p.fn(u, spec, bm=bm, t=t, interpret=interpret, device=device)
    return p.fn(u, spec, bm=bm, interpret=interpret, device=device)


def _scan_steps(u: jax.Array, fn: Callable, n: int) -> jax.Array:
    if n <= 0:
        return u
    def body(v, _):
        return fn(v), None
    v, _ = jax.lax.scan(body, u, None, length=n)
    return v


def residual_for(spec: StencilSpec | None = None) -> Callable:
    """Jit-friendly residual evaluator for ``spec``: ``u -> |apply(u)-u|_inf``.

    The one max-norm update-delta every convergence check shares — the
    solve server's in-launch eviction test, ``launch/solve.py``'s final
    report, and tests all call the same closure instead of re-deriving
    the interior slice + max-abs reduction. Batched callers ``vmap`` it
    over a leading axis (it is pure jnp, so the vmapped form is exactly
    the per-grid form).
    """
    from repro.core.stencil import residual
    spec = spec if spec is not None else jacobi_2d_5pt()

    def res(u):
        with jax.named_scope("solver.residual"):
            return residual(u, spec=spec)
    return res


def kernel_attrs(sched, shape, dtype, spec: StencilSpec, bm,
                 device, masked: bool = False) -> dict:
    """The launched kernel's form as span attributes: its strip height
    and the rows it sweeps per row it keeps, its halo rows on a 2-D grid,
    and on a 3-D grid its planes per block and halo planes
    (``recompute`` there counts planes and rows); none for the pure-jnp
    reference, which has no plan."""
    if sched.policy == "reference":
        return {}
    plan = plan_for(shape, dtype, spec, sched.policy, bm=bm, t=sched.t,
                    device=device, masked=masked)
    attrs = {"strip_rows": plan.strip_rows,
             "recompute": round(plan.recompute, 4)}
    if plan.ndim == 3:
        attrs.update(block_planes=plan.bm, halo_planes=plan.halo_planes)
    else:
        attrs.update(halo_rows=plan.halo_rows)
    return attrs


def _is_traced(u) -> bool:
    """True when ``u`` is an abstract tracer (we are inside jit/vmap/scan).

    The cached jitted launches below only apply to concrete host calls;
    inside an outer trace the schedule is inlined so the enclosing jit
    compiles one fused program (today's behavior, bit-identical).
    """
    return isinstance(u, jax.core.Tracer)


def _block_fn(sched, spec: StencilSpec, bm, interpret, device) -> Callable:
    """One ``t``-sweep fused block (or one sweep for unfused policies)."""
    p = get_policy(sched.policy)
    if p.fused:
        return functools.partial(p.fn, spec=spec, bm=bm, t=sched.t,
                                 interpret=interpret, device=device)
    return functools.partial(p.fn, spec=spec, bm=bm, interpret=interpret,
                             device=device)


def _execute_schedule(u: jax.Array, sched, spec: StencilSpec, bm,
                      interpret, device) -> jax.Array:
    """Execute a frozen :class:`SweepSchedule` as kernel launches.

    Shared verbatim by the inline (traced) path and the cached jitted
    host launch, so both are the same XLA program by construction.
    ``"reference"`` (the pure-jnp oracle, not a registry policy) runs
    single un-fused sweeps — so every entry point built on this
    (``run``, ``run_batched``, ``run_converged``, the solve server)
    accepts the oracle uniformly."""
    if sched.policy == "reference":
        from repro.core.stencil import apply_stencil
        return _scan_steps(u, functools.partial(apply_stencil, spec=spec),
                           sched.iters)
    p = get_policy(sched.policy)
    if p.fused:
        u = _scan_steps(u, _block_fn(sched, spec, bm, interpret, device),
                        sched.fused_blocks)
        if sched.remainder:
            rp = get_policy(sched.remainder_policy)
            if rp.fused:  # a 3-D grid: the kernel at the remainder's depth
                return rp.fn(u, spec=spec, bm=bm, t=sched.remainder,
                             interpret=interpret, device=device)
            u = _scan_steps(u, functools.partial(
                rp.fn, spec=spec, bm=bm, interpret=interpret,
                device=device), sched.remainder)
        return u
    return _scan_steps(u, functools.partial(
        p.fn, spec=spec, bm=bm, interpret=interpret, device=device),
        sched.iters)


# Jitted launches, keyed by everything that shapes their program: a
# launch tag, the schedule and the static arguments. Bounded in practice
# by the handful of schedules a process runs.
_LAUNCHES: dict = {}


def cached_launch(cache: dict, key: tuple, build: Callable, *args):
    """Call the launch cached under ``key``, building it on a miss.

    A miss counts ``engine.launch.miss`` and makes its first call — the
    trace and the compile, or the load from the persistent compile cache
    — inside an ``engine.compile`` span, so that a recompile shows at its
    place on the timeline. ``key[0]`` names the launch."""
    fn = cache.get(key)
    if fn is not None:
        return fn(*args)
    _metrics.counter("engine.launch.miss").inc()
    with _obs_span("engine.compile", launch=key[0]):
        fn = build()
        out = fn(*args)
    cache[key] = fn
    return out


def _run_program(sched, spec: StencilSpec, bm, interpret, device,
                 donate: bool) -> Callable:
    """The jitted whole-schedule launch: one dispatch per solve.

    With ``donate=True`` the input grid's buffer is donated to XLA
    (``donate_argnums``) so the sweep updates in place — the caller's
    array is dead after the call."""
    def go(u):
        return _execute_schedule(u, sched, spec, bm, interpret, device)
    return jax.jit(go, donate_argnums=(0,) if donate else ())


def _batched_program(sched, spec: StencilSpec, bm, interpret, device,
                     donate: bool) -> Callable:
    def go(us):
        return jax.vmap(lambda u: _execute_schedule(
            u, sched, spec, bm, interpret, device))(us)
    return jax.jit(go, donate_argnums=(0,) if donate else ())


def _converged_program(sched, spec: StencilSpec, bm, interpret, device,
                       max_blocks: int, donate: bool) -> Callable:
    """The jitted tolerance-driven launch: ``lax.while_loop`` over
    ``t``-sweep blocks with the in-launch residual as exit test.

    ``sched`` is the one-block (cadence-``t``) schedule; the loop body
    executes it whole, so non-fused policies advance ``t`` single sweeps
    per residual check — the same block the solve server launches.
    ``tol`` rides in as a traced operand (no retrace across tolerances);
    ``tol < 0`` never triggers, so the sentinel ``-1.0`` means "run the
    whole budget" (fixed-iteration semantics, residual still reported).
    """
    res_fn = residual_for(spec)

    def block(v):
        return _execute_schedule(v, sched, spec, bm, interpret, device)

    def go(u, tol):
        def cond(carry):
            _, n, r = carry
            with jax.named_scope("solver.residual"):
                return (n < max_blocks) & (r > tol)

        def body(carry):
            v, n, _ = carry
            v = block(v)
            return (v, n + 1, res_fn(v))

        u, n, r = jax.lax.while_loop(
            cond, body, (u, jnp.int32(0), jnp.float32(jnp.inf)))
        return u, n, r

    return jax.jit(go, donate_argnums=(0,) if donate else ())


def run_converged(u: jax.Array, spec: StencilSpec | None = None, *,
                  tol: float | None, max_iters: int, policy: str = "auto",
                  bm: int | None = None, t: int | None = None,
                  interpret: bool | None = None,
                  device: str | DeviceModel | None = None,
                  remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                  donate: bool = False
                  ) -> tuple[jax.Array, int, float]:
    """Advance ``u`` until the max-norm update delta is <= ``tol``,
    checking every ``t``-sweep block *inside* one launch.

    A single jitted ``lax.while_loop`` runs cadence-``t`` blocks and
    evaluates :func:`residual_for` on-device, so tolerance-driven solves
    exit without any host round-trip per block. Semantics match the
    solve server's eviction rule exactly: the cadence is
    ``effective_depth(max_iters, t)`` (the same rule bucket admission
    uses), residuals are tested at block boundaries only, so realized
    iterations are a multiple of the cadence and cap at
    ``(max_iters // cadence) * cadence`` (the remainder sweeps a
    fixed-``iters`` run would add never execute). ``tol=None`` runs the
    whole (rounded) budget and still reports the final residual.

    Returns ``(u, iters_done, residual)`` with ``iters_done``/``residual``
    as host scalars — the terminal sync every converged solve needs once.
    """
    from repro.engine.schedule import effective_depth
    spec = spec if spec is not None else jacobi_2d_5pt()
    if interpret is None:
        interpret = not _on_tpu()
    device = _resolve_device_name(device)
    if _is_traced(u):
        raise PlanError("run_converged is a host entry point (its result "
                        "shape is data-dependent); call it on concrete "
                        "arrays, not under jit/vmap")
    import jax.numpy as jnp
    with _obs_span("engine.run_converged", max_iters=max_iters, tol=tol,
                   shape=tuple(u.shape), requested_policy=policy) as sp:
        cadence = effective_depth(max_iters, t, default_depth(
            u.shape, u.dtype, spec, device))
        sched = build_schedule(cadence, spec=spec, shape=u.shape,
                               dtype=u.dtype, policy=policy, t=cadence,
                               bm=bm, interpret=interpret, device=device,
                               remainder_policy=remainder_policy)
        max_blocks = max_iters // cadence
        args = (sched, spec, bm, interpret, device, max_blocks, donate)
        tol_arr = jnp.float32(-1.0 if tol is None else tol)
        u, n, r = cached_launch(
            _LAUNCHES, ("run_converged",) + args,
            lambda: _converged_program(*args), u, tol_arr)
        iters_done = int(n) * cadence
        if sp is not NULL_SPAN:
            sp.set(policy=sched.policy, t=cadence, iters_done=iters_done,
                   residual=float(r), launch="while_loop",
                   **kernel_attrs(sched, u.shape, u.dtype, spec, bm, device))
    return u, iters_done, float(r)


def run_batched(us: jax.Array, spec: StencilSpec | None = None, *,
                policy: str = "auto", iters: int = 1, bm: int | None = None,
                t: int | None = None, interpret: bool | None = None,
                device: str | DeviceModel | None = None,
                remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                donate: bool = False
                ) -> jax.Array:
    """Advance a batch ``(B, H, W)`` of ringed grids ``iters`` sweeps each
    through ONE launch.

    This is the serving entry: every grid in the batch shares one
    schedule (same shape/dtype/spec/policy/t — the bucket contract
    :mod:`repro.serve.solve` enforces at admission), so the whole batch
    is a single ``vmap`` of :func:`run` — one jitted launch instead of
    ``B``, and each batch lane is bit-identical to the solo call
    (``vmap`` of these kernels is elementwise over the leading axis).
    ``policy="reference"`` runs the pure-jnp oracle (no Pallas), useful
    for cheap host-side serving and for the benchmark's dry-mode sweep
    accounting.
    """
    if us.ndim != 3:
        raise PlanError(f"run_batched wants a (B, H, W) batch of ringed "
                        f"grids; got shape {tuple(us.shape)}")
    spec = spec if spec is not None else jacobi_2d_5pt()
    if policy == "reference":
        from repro.core.stencil import apply_stencil
        def one(u):
            return _scan_steps(u, functools.partial(apply_stencil,
                                                    spec=spec), iters)
        return jax.vmap(one)(us)
    if _is_traced(us):
        if donate:
            raise PlanError("donate=True needs a concrete host array; "
                            "inside jit the enclosing launch owns buffers")
        def one(u):
            return run(u, spec, policy=policy, iters=iters, bm=bm, t=t,
                       interpret=interpret, device=device,
                       remainder_policy=remainder_policy)
        return jax.vmap(one)(us)
    if interpret is None:
        interpret = not _on_tpu()
    device = _resolve_device_name(device)
    sched = build_schedule(iters, spec=spec, shape=us.shape[1:],
                           dtype=us.dtype, policy=policy, t=t, bm=bm,
                           interpret=interpret, device=device,
                           remainder_policy=remainder_policy)
    args = (sched, spec, bm, interpret, device, donate)
    return cached_launch(_LAUNCHES, ("run_batched",) + args,
                         lambda: _batched_program(*args), us)


def run(u: jax.Array, spec: StencilSpec | None = None, *,
        policy: str = "auto", iters: int = 1, bm: int | None = None,
        t: int | None = None, interpret: bool | None = None,
        device: str | DeviceModel | None = None,
        remainder_policy: str = DEFAULT_REMAINDER_POLICY,
        donate: bool = False) -> jax.Array:
    """Advance a ringed grid by exactly ``iters`` sweeps of ``spec``.

    ``policy`` is a registry name, ``"auto"`` (device-aware heuristic), or
    ``"tuned"`` (measured winner from the autotune cache). ``device`` is a
    registry name or :class:`DeviceModel`; plans are validated against its
    fast-memory budget (None = the detected host backend). Scheduling —
    policy resolution, fusion-depth clamping, the ``iters // t`` fused
    blocks plus an ``iters % t`` remainder under ``remainder_policy`` — is
    all :func:`repro.engine.schedule.build_schedule`; this function just
    executes the schedule as kernel launches.

    Called on a concrete array, the whole schedule runs as ONE cached
    jitted launch (``lax.scan`` over fused blocks) — no per-block Python
    dispatch. ``donate=True`` additionally donates the input buffer so
    the sweep updates in place; the caller's array is invalid afterwards.
    Under an enclosing jit/vmap trace the schedule inlines into the outer
    program exactly as before (and ``donate`` is rejected — the outer
    launch owns the buffers).
    """
    spec = spec if spec is not None else jacobi_2d_5pt()
    if interpret is None:
        interpret = not _on_tpu()
    device = _resolve_device_name(device)
    # Span note: under a jit trace this measures trace time (schedule and
    # plan resolution), not kernel wall-clock — still host work worth
    # seeing; eager callers get real durations.
    with _obs_span("engine.run", iters=iters, shape=tuple(u.shape),
                   requested_policy=policy) as sp:
        sched = build_schedule(iters, spec=spec, shape=u.shape,
                               dtype=u.dtype, policy=policy, t=t, bm=bm,
                               interpret=interpret, device=device,
                               remainder_policy=remainder_policy)
        if sp is not NULL_SPAN:
            sp.set(policy=sched.policy, t=sched.t,
                   fused_blocks=sched.fused_blocks,
                   remainder=sched.remainder,
                   **kernel_attrs(sched, u.shape, u.dtype, spec, bm, device))
        if _is_traced(u):
            if donate:
                raise PlanError("donate=True needs a concrete host array; "
                                "inside jit the enclosing launch owns "
                                "buffers")
            return _execute_schedule(u, sched, spec, bm, interpret, device)
        sp.set(launch="scan")
        args = (sched, spec, bm, interpret, device, donate)
        return cached_launch(_LAUNCHES, ("run",) + args,
                             lambda: _run_program(*args), u)
