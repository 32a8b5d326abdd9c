"""Distributed dispatch: any registered policy, per shard, over a mesh.

``run_distributed`` is the multi-device twin of ``engine.run``: it advances a
ringed grid by ``iters`` sweeps of any 2-D :class:`StencilSpec`, decomposed
over a JAX mesh with depth-``t`` halo exchange (``repro.dist.stencil``), and
runs the *local* computation through the same policy registry ``engine.run``
uses — so the paper's §VII multi-card scaling composes with every kernel
generation instead of the hard-coded 5-point Jacobi.

Scheduling is shared with ``engine.run``: both executors run a
:class:`~repro.engine.schedule.SweepSchedule` (``t`` sweeps per fused
block/halo exchange, remainder under a non-fused policy), built once by
:func:`plan_distributed` — inspect it to see the exchange count a run will
cost before paying for it.

Per-shard plans are validated against the target
:class:`~repro.engine.device.DeviceModel` *before* anything is sharded: the
static local block (shard interior + exchanged halo, from
``dist.stencil.extended_shard_shape``) must fit the device's fast-memory
budget, so an over-deep fusion depth on a small-SRAM device fails fast with
the device's numbers in the message instead of mid-trace inside shard_map.

The local sweep obeys the registry contract (f32 tap accumulation in fixed
tap order), so the distributed result is bit-identical to the single-device
``engine.run`` oracle in fp32. Fused policies run *fused* per shard: the
``temporal`` kernel takes the shard's pin mask (only the slice of the global
Dirichlet ring the shard owns stays fixed — exchanged halo evolves) and
advances all ``t`` sweeps in one fast-memory round-trip between exchanges —
the communication-avoiding schedule at mesh scale, not its single-sweep
degenerate.
"""
from __future__ import annotations

import jax

from repro.core.stencil import StencilSpec, apply_stencil, jacobi_2d_5pt
from repro.engine.device import DeviceModel
from repro.engine.dispatch import (_on_tpu, _resolve_device_name, get_policy,
                                   kernel_attrs, resolve_auto)
from repro.engine.plan import plan_for
from repro.engine.schedule import (DEFAULT_REMAINDER_POLICY, SweepSchedule,
                                   build_schedule, effective_depth,
                                   overlap_feasible, price_exchange)
from repro.obs.trace import NULL_SPAN, get_tracer, span as _obs_span


def _mesh_shape(mesh, row_axis: str | None, col_axis: str | None) -> tuple:
    """The decomposition shape folded into tuned cache keys — derived in
    exactly one place so the key built at schedule time and the one passed
    to ``local_sweep_for`` can never diverge."""
    return tuple(mesh.shape[a] for a in (row_axis, col_axis)
                 if a is not None)


def local_sweep_for(policy: str, spec: StencilSpec, *, shard_shape,
                    dtype, iters: int = 1, t: int = 1,
                    bm: int | None = None, interpret: bool = False,
                    device: str | None = None,
                    mesh_shape: tuple | None = None,
                    overlap: bool = False):
    """Resolve a policy name to a block callable on extended shards.

    The returned ``block(ext, fixed, t)`` advances an extended shard ``t``
    sweeps, keeping the ``fixed`` cells (the shard's slice of the global
    Dirichlet ring) pinned: fused policies pass the mask straight into the
    kernel and run all ``t`` sweeps in one fast-memory round-trip;
    non-fused policies loop single sweeps with re-pinning in between
    (:func:`repro.dist.stencil.masked_block`).

    ``"reference"`` selects the pure-jnp oracle; ``"auto"`` consults the
    planner and ``"tuned"`` the measured autotune cache, both against the
    (static) extended shard shape on ``device`` at the *real* ``iters``
    and ``t`` — the schedule the shard will actually run, not the ``t=1``
    degenerate (``mesh_shape`` folds the decomposition into the tuned
    cache key so local and distributed winners never alias, and
    ``overlap`` buckets the interior/rind split's winners separately from
    serial ones). For registry policies the shard plan is resolved
    eagerly here, surfacing device-budget violations before shard_map
    tracing starts.
    """
    from repro.dist.stencil import masked_block

    if policy == "reference":
        return masked_block(lambda ext: apply_stencil(ext, spec))
    if policy == "auto":
        policy = resolve_auto(shard_shape, dtype, spec, iters=iters, t=t,
                              device=device, masked=True)
    elif policy == "tuned":
        from repro.engine import tune  # deferred: tune dispatches back here
        policy = tune.best_policy(shard_shape, dtype, spec, iters=iters, t=t,
                                  bm=bm, interpret=interpret, device=device,
                                  mesh=mesh_shape, masked=True,
                                  overlap=overlap)
    p = get_policy(policy)
    if p.fused:
        plan_for(shard_shape, dtype, spec, policy, bm=bm, t=t, device=device,
                 masked=True)
        return lambda ext, fixed, tt: p.fn(ext, spec, bm=bm, t=tt,
                                           interpret=interpret, device=device,
                                           mask=fixed)
    plan_for(shard_shape, dtype, spec, policy, bm=bm, device=device)
    return masked_block(lambda ext: p.fn(ext, spec, bm=bm,
                                         interpret=interpret, device=device))


def _bulk_launch_shape(sched: SweepSchedule, shard_shape) -> tuple:
    """The block the launch that sweeps most of a shard sees: the extended
    shard, or under overlap the raw shard of the interior launch. The
    ``strip_rows`` and ``recompute`` of ``dist.run`` describe its plan; the
    four rind launches of an overlapped round plan their own narrow
    windows."""
    d = sched.halo_depth
    raw = (shard_shape[0] - 2 * d, shard_shape[1] - 2 * d)
    if sched.overlap and overlap_feasible(*raw, d):
        return raw
    return shard_shape


def plan_distributed(shape, dtype, spec: StencilSpec | None = None, *,
                     mesh, policy: str = "auto", iters: int = 1, t: int = 1,
                     bm: int | None = None, row_axis: str | None = None,
                     col_axis: str | None = None,
                     interpret: bool | None = None,
                     device: str | DeviceModel | None = None,
                     remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                     overlap: bool | None = None
                     ) -> tuple[SweepSchedule, tuple[int, int], tuple]:
    """Resolve what a ``run_distributed`` call will execute, without running.

    Returns ``(schedule, shard_shape, (row_axis, col_axis))``: the shared
    :class:`SweepSchedule` (resolved policy, realized ``t``, fused blocks,
    remainder, and — the mesh-scale quantity — ``schedule.exchanges`` halo
    exchanges of depth ``schedule.halo_depth``), plus the static extended
    shard shape per-shard plans are validated against. ``run_distributed``
    itself goes through here, so inspection and execution cannot disagree.

    ``overlap=None`` lets the schedule *choose* the interior/rind
    exchange-hiding split by price (``engine.price_exchange`` against
    ``device`` and the mesh decomposition); ``True``/``False`` force it.
    The choice lands in ``schedule.overlap`` — pass the returned schedule
    plus shard shape to :func:`repro.engine.schedule.price_exchange` to
    see the serial-vs-overlapped exchange bill the choice was made from.
    """
    spec = spec if spec is not None else jacobi_2d_5pt()
    if interpret is None:
        interpret = not _on_tpu()
    from repro.dist import stencil as dstencil

    row_axis, col_axis = dstencil.resolve_axes(mesh, row_axis, col_axis)
    t_eff = effective_depth(iters, t)
    shard_shape = dstencil.extended_shard_shape(
        shape, mesh, spec, t=t_eff, row_axis=row_axis, col_axis=col_axis)
    mesh_shape = _mesh_shape(mesh, row_axis, col_axis)
    sched = build_schedule(iters, spec=spec, shape=shard_shape, dtype=dtype,
                           policy=policy, t=t, bm=bm, interpret=interpret,
                           device=_resolve_device_name(device),
                           mesh_shape=mesh_shape,
                           remainder_policy=remainder_policy,
                           exchange_cadence=True, overlap=overlap)
    return sched, shard_shape, (row_axis, col_axis)


def run_distributed(u: jax.Array, spec: StencilSpec | None = None, *,
                    mesh, policy: str = "auto", iters: int = 1, t: int = 1,
                    bm: int | None = None, row_axis: str | None = None,
                    col_axis: str | None = None,
                    interpret: bool | None = None,
                    device: str | DeviceModel | None = None,
                    remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                    overlap: bool | None = None,
                    donate: bool = False) -> jax.Array:
    """Advance a ringed grid by ``iters`` sweeps of ``spec`` over ``mesh``.

    Same contract and return as ``engine.run`` (full grid, ring copied
    through), decomposed rows x cols over ``(row_axis, col_axis)`` (defaults:
    the mesh's first/second axes). ``t`` sweeps run per halo exchange
    (depth-``t*r`` halos — the communication-avoiding schedule; a ``t``
    that must be clamped to ``iters`` warns, like ``pick_bm`` does for a
    degraded block size); fused policies run all ``t`` sweeps in one
    kernel invocation per shard. ``policy`` is any registry name,
    ``"reference"`` (pure jnp), ``"auto"``, or ``"tuned"``; ``device``
    selects the device model each shard's plan is validated against (None
    = the detected host backend); leftover ``iters % t`` sweeps run under
    ``remainder_policy`` when the main policy is fused, exactly like
    ``engine.run``. ``overlap`` hides each exchange behind the shard's
    halo-independent interior compute (``None`` = let the schedule price
    it; the result is bit-identical either way).

    Called untraced (the hot path), the whole solve — band split, every
    exchange round as a ``lax.scan`` with the ``ppermute``\\ s inside the
    scan body, remainder, ring re-attach — runs as ONE cached jitted
    launch instead of one Python dispatch per round; ``donate=True``
    additionally donates ``u``'s buffer so the solve updates in place
    (the caller's array is invalid afterwards).

    The call is one ``dist.run`` span whose ``model_s`` is the schedule's
    whole-launch :class:`~repro.engine.schedule.ExchangeBill` total, the
    component ``obs.reconcile`` prices. With a :class:`~repro.obs.Tracer`
    installed the span waits for the launch, so its duration is the
    launch's wall-clock; the program is the same one launch either way.
    The split into exchange, interior and rind lives in the device trace,
    under the ``exchange.*`` scopes of :mod:`repro.dist.stencil`.
    """
    from repro.dist import stencil as dstencil

    spec = spec if spec is not None else jacobi_2d_5pt()
    if interpret is None:
        interpret = not _on_tpu()
    device = _resolve_device_name(device)
    with _obs_span("dist.run", iters=iters, shape=tuple(u.shape),
                   requested_policy=policy) as sp:
        sched, shard_shape, (row_axis, col_axis) = plan_distributed(
            u.shape, u.dtype, spec, mesh=mesh, policy=policy, iters=iters,
            t=t, bm=bm, row_axis=row_axis, col_axis=col_axis,
            interpret=interpret, device=device,
            remainder_policy=remainder_policy, overlap=overlap)
        mesh_shape = _mesh_shape(mesh, row_axis, col_axis)
        if sp is not NULL_SPAN:
            bill = price_exchange(sched, shard_shape=shard_shape,
                                  dtype=u.dtype, spec=spec, device=device,
                                  mesh_shape=mesh_shape)
            sp.set(policy=sched.policy, t=sched.t, overlap=sched.overlap,
                   exchanges=sched.exchanges, model_s=(
                       bill.overlapped_s if sched.overlap
                       else bill.serial_s), **bill.as_attrs(),
                   **kernel_attrs(sched, _bulk_launch_shape(sched,
                                                            shard_shape),
                                  u.dtype, spec, bm, device,
                                  masked=sched.fused))
        block = local_sweep_for(sched.policy, spec, shard_shape=shard_shape,
                                dtype=u.dtype, iters=iters, t=sched.t,
                                bm=bm, interpret=interpret, device=device,
                                mesh_shape=mesh_shape, overlap=sched.overlap)
        remainder_block = None
        if sched.remainder and sched.remainder_policy != sched.policy:
            # Fused main policy with leftovers: the shallower remainder
            # exchange runs the non-fused remainder policy per shard.
            remainder_block = local_sweep_for(
                sched.remainder_policy, spec, shard_shape=shard_shape,
                dtype=u.dtype, iters=sched.remainder, t=sched.remainder,
                bm=bm, interpret=interpret, device=device,
                mesh_shape=mesh_shape, overlap=sched.overlap)
        # Everything that shaped `block`/`remainder_block` beyond what the
        # schedule already pins — so the jitted single launch can be
        # reused across calls (a fresh closure is built per call, its
        # program isn't).
        cache_key = ("run_distributed", bm, interpret, device,
                     remainder_policy)
        out = dstencil.run_sharded(u, spec, mesh, block, schedule=sched,
                                   row_axis=row_axis, col_axis=col_axis,
                                   remainder_block=remainder_block,
                                   cache_key=cache_key, donate=donate)
        if get_tracer() is not None and not isinstance(out, jax.core.Tracer):
            out = jax.block_until_ready(out)
    return out
