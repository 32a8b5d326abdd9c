"""Sweep scheduling: how ``iters`` sweeps become fused blocks + exchanges.

Every executor used to re-derive the same bookkeeping — clamp the fusion
depth to the iteration count, split ``iters`` into ``iters // t`` fused
blocks plus an ``iters % t`` remainder, pick a non-fused policy for the
leftovers — once in ``engine.run``, once in ``engine.run_distributed`` /
``dist.stencil.run_sharded``, once in ``backends.sim.simulate``. Three
hand-rolled copies of the same arithmetic is how schedules drift; this
module is the single derivation.

A :class:`SweepSchedule` is the frozen answer: the resolved policy (after
``"auto"``/``"tuned"`` lookup), the realized fusion depth ``t``, how many
full-depth blocks run, how many remainder sweeps follow under which
non-fused policy, and — the quantity that matters at mesh scale — how many
halo exchanges the whole thing costs and how deep each halo band is
(``t * r``). ``engine.run`` executes a schedule as kernel launches;
``run_distributed`` executes the *same* schedule as ``exchange + t local
sweeps`` rounds, which is the paper's §VII communication-avoiding
direction made inspectable: ``build_schedule(iters=512, t=8, ...)`` says
"64 exchanges instead of 512" before anything runs.

This module also *prices* the exchange: :func:`price_exchange` bills a
schedule's halo rounds serially (exchange + full-block compute) and
overlapped (``max(exchange, interior) + rind`` — the interior of each
shard is independent of the incoming halo, so it computes while the
``t*r``-deep exchange is in flight, and only the rind strips wait; see
``repro.dist.stencil``). The resulting :class:`ExchangeBill` is how
``build_schedule(overlap=None)`` decides per (shape, spec, t, device,
mesh) whether hiding the exchange pays for the rind's redundant compute.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax.numpy as jnp

from repro.core.stencil import StencilSpec
from repro.engine.device import DeviceModel, get_device
from repro.engine.plan import DEFAULT_T, PlanError, default_depth
from repro.obs.trace import span as _obs_span

#: Non-fused policy used for the leftover sweeps when ``iters`` is not a
#: multiple of the temporal depth (on a 2-D grid; a 3-D schedule runs its
#: kernel once more at the remainder's depth).
DEFAULT_REMAINDER_POLICY = "rowchunk"


def overlap_feasible(hl: int, wl: int, depth: int, nshards: int = 2) -> bool:
    """Whether a ``(hl, wl)``-interior shard can hide a depth-``depth``
    exchange behind halo-independent compute.

    The single home of the ``hl > 2d and wl > 2d`` gate that used to be
    inlined in ``dist.stencil._local_sweeps``, ``_price_rounds`` *and*
    re-derived by callers: the interior launch is nonempty only when the
    shard extends beyond the ``2*depth`` band the rind strips recompute,
    and a single-shard mesh has no exchange to hide at all.
    """
    return nshards > 1 and hl > 2 * depth and wl > 2 * depth


def effective_depth(iters: int, t: int | None,
                    default: int = DEFAULT_T) -> int:
    """The realized fusion depth: the request clamped into ``[1, iters]``.

    The single home of the clamp every executor used to hand-roll
    (``min(t or DEFAULT_T, max(iters, 1))``). Callers that need the depth
    before building a full schedule (e.g. to size a shard's halo band)
    use this; :func:`build_schedule` warns when an *explicit* request is
    degraded, so the quiet path here stays quiet.
    """
    if t is not None and t < 1:
        raise PlanError(f"temporal depth t={t} must be >= 1")
    return min(t if t is not None else default, max(iters, 1))


@dataclasses.dataclass(frozen=True)
class SweepSchedule:
    """How ``iters`` sweeps of a radius-``r`` spec actually execute.

    ``fused_blocks`` blocks of ``t`` sweeps run under ``policy`` (one HBM
    round-trip each when the policy is fused; one halo exchange each at
    mesh scale), then ``remainder`` sweeps run under ``remainder_policy``
    (a non-fused registry policy; equal to ``policy`` when the main policy
    is itself non-fused, or fused on a 3-D grid, where the kernel runs
    once more at depth ``remainder``). Frozen and hashable, so a schedule
    can key caches and ride through jit closures like a plan does.
    """

    policy: str
    iters: int
    t: int
    fused: bool
    fused_blocks: int
    remainder: int
    remainder_policy: str
    radius: int
    #: Distributed execution only: split each shard block into a
    #: halo-independent interior (launched while the exchange is in
    #: flight) and rind strips (patched in after arrival), instead of
    #: serializing exchange then full-block compute. Numerically
    #: identical either way; priced by :func:`price_exchange`.
    overlap: bool = False

    def __post_init__(self):
        assert self.fused_blocks * self.t + self.remainder == self.iters, self

    @property
    def exchanges(self) -> int:
        """Halo exchanges a distributed execution of this schedule costs:
        one per fused block plus one for the remainder round."""
        return self.fused_blocks + (1 if self.remainder else 0)

    @property
    def halo_depth(self) -> int:
        """Rows/cols of halo each full-depth exchange must carry (t·r)."""
        return self.t * self.radius

    @property
    def remainder_halo_depth(self) -> int:
        return self.remainder * self.radius

    def describe(self) -> str:
        parts = [f"{self.policy}: {self.iters} sweeps = "
                 f"{self.fused_blocks} x t={self.t}"]
        if self.remainder:
            parts.append(f" + {self.remainder} ({self.remainder_policy})")
        parts.append(f"; {self.exchanges} exchange"
                     f"{'s' if self.exchanges != 1 else ''} "
                     f"(halo depth {self.halo_depth}"
                     f"{', overlapped' if self.overlap else ''})")
        return "".join(parts)


@dataclasses.dataclass(frozen=True)
class ExchangeBill:
    """Modeled cost of a distributed schedule's halo rounds, both ways.

    All times are seconds summed over every round (fused blocks plus the
    remainder). ``serial_s`` bills each round as ``exchange + full-block
    compute``; ``overlapped_s`` bills ``max(exchange, interior) +
    rind`` — the interior launch has no data dependence on the incoming
    halo, so it rides free under the exchange, and only the four rind
    strips (which recompute a band of width ``3*t*r`` around the shard,
    the redundancy overlap pays for) sit on the critical path.
    ``feasible`` is False when the shard is too small to hold a nonempty
    interior (``hl <= 2*t*r`` or ``wl <= 2*t*r``) or the mesh has a
    single shard; the executor then falls back to the serial round and
    ``overlapped_s == serial_s``.
    """

    exchange_s: float
    compute_s: float
    interior_s: float
    rind_s: float
    serial_s: float
    overlapped_s: float
    halo_bytes: int
    feasible: bool

    @property
    def wins(self) -> bool:
        """Whether overlapping beats the serial bill for this cell."""
        return self.feasible and self.overlapped_s < self.serial_s

    def describe(self) -> str:
        return (f"exchange {self.exchange_s * 1e6:.1f}us "
                f"({self.halo_bytes} B): serial "
                f"{self.serial_s * 1e6:.1f}us vs overlapped "
                f"{self.overlapped_s * 1e6:.1f}us "
                f"({'overlap wins' if self.wins else 'serial wins'})")

    def as_attrs(self) -> dict:
        """The bill as flat span attrs (``model_``-prefixed seconds), the
        form ``run_distributed`` attaches to its ``dist.run`` span beside
        the ``model_s`` total ``obs.reconcile`` joins against."""
        return {"model_exchange_s": self.exchange_s,
                "model_compute_s": self.compute_s,
                "model_interior_s": self.interior_s,
                "model_rind_s": self.rind_s,
                "model_serial_s": self.serial_s,
                "model_overlapped_s": self.overlapped_s,
                "halo_bytes": self.halo_bytes,
                "feasible": self.feasible}


def _price_rounds(rounds, *, d_max: int, radius: int, taps: int,
                  shard_shape, dtype, device, mesh_shape,
                  compute_rate: float | None = None) -> ExchangeBill:
    """Price halo rounds on one shard. ``rounds`` is ``[(reps, sweeps)]``;
    ``shard_shape`` is the *extended* shard (interior + 2*d_max halo)."""
    dev = get_device(device)
    db = jnp.dtype(dtype).itemsize
    hl = shard_shape[0] - 2 * d_max
    wl = shard_shape[1] - 2 * d_max
    mesh_shape = tuple(mesh_shape) if mesh_shape else (1,)
    px = int(mesh_shape[0])
    py = int(mesh_shape[1]) if len(mesh_shape) > 1 else 1
    feasible = overlap_feasible(hl, wl, d_max, px * py)

    def compute_s(area: int, sweeps: int) -> float:
        if compute_rate is not None and compute_rate > 0:
            # Measured/simulated seconds per point per sweep (e.g. the
            # backends simulator's counters-derived chip rate).
            return compute_rate * area * sweeps
        # Fused-traffic floor: one read + one write of the block per
        # round whatever the policy ends up being (non-fused policies pay
        # more on both sides of the comparison), flops per sweep.
        flops = 2 * taps * area * sweeps / max(dev.vector_flops, 1.0)
        mem = area * 2 * db / dev.dram_bw
        return max(flops, mem)

    exchange = compute = interior = rind = serial = overlapped = 0.0
    halo_bytes = 0
    for reps, sweeps in rounds:
        if reps <= 0 or sweeps <= 0:
            continue
        dd = sweeps * radius
        msgs, nbytes = 0, 0
        if px > 1:
            msgs += 2
            nbytes += 2 * dd * wl * db
        if py > 1:
            msgs += 2
            nbytes += 2 * dd * (hl + 2 * dd) * db
        ex = msgs * dev.txn_overhead_s + nbytes / dev.halo_link_bw \
            + (2 * dev.noc_hop_latency_s if msgs else 0.0)
        full = compute_s((hl + 2 * dd) * (wl + 2 * dd), sweeps)
        inner = compute_s(hl * wl, sweeps)
        # The four rind strips are separate launches: top/bottom span the
        # full extended width at height 3*dd, left/right fill the
        # remaining hl rows at width 3*dd (repro.dist.stencil geometry).
        rnd = 2 * compute_s(3 * dd * (wl + 2 * dd), sweeps) \
            + 2 * compute_s(hl * 3 * dd, sweeps)
        exchange += reps * ex
        compute += reps * full
        interior += reps * inner
        rind += reps * rnd
        halo_bytes += reps * nbytes
        serial += reps * (ex + full)
        overlapped += reps * ((max(ex, inner) + rnd) if feasible
                              else (ex + full))
    return ExchangeBill(exchange_s=exchange, compute_s=compute,
                        interior_s=interior, rind_s=rind, serial_s=serial,
                        overlapped_s=overlapped, halo_bytes=halo_bytes,
                        feasible=feasible)


def price_exchange(sched: SweepSchedule, *, shard_shape, dtype,
                   spec: StencilSpec,
                   device: "str | DeviceModel | None" = None,
                   mesh_shape: tuple | None = None,
                   compute_rate: float | None = None) -> ExchangeBill:
    """Bill a distributed schedule's halo rounds serial vs overlapped.

    ``shard_shape`` is the extended shard ``plan_distributed`` returns
    (interior + the depth-``t*r`` halo on each side); ``mesh_shape`` the
    decomposition (e.g. ``(4,)`` or ``(2, 2)``); ``device`` the model
    whose link/DRAM/vector numbers do the pricing — exchange bytes ride
    :attr:`~repro.engine.device.DeviceModel.halo_link_bw`, so a device
    whose mesh neighbours lack direct links (the paper's PCIe-isolated
    e150 cards) bills the thin host pipe and overlap starts winning.

    ``compute_rate`` (seconds per point per sweep) replaces the built-in
    compute roofline with a measured or simulated rate — the backends
    simulator passes its counters-derived chip rate here so both layers
    price the identical interior/rind geometry.
    """
    rounds = [(sched.fused_blocks, sched.t)]
    if sched.remainder:
        rounds.append((1, sched.remainder))
    return _price_rounds(rounds, d_max=sched.halo_depth,
                         radius=sched.radius, taps=spec.taps,
                         shard_shape=shard_shape, dtype=dtype,
                         device=device, mesh_shape=mesh_shape,
                         compute_rate=compute_rate)


def build_schedule(iters: int, *, spec: StencilSpec, shape, dtype,
                   policy: str = "auto", t: int | None = None,
                   bm: int | None = None, interpret: bool = False,
                   device: "str | DeviceModel | None" = None,
                   mesh_shape: tuple | None = None,
                   remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                   exchange_cadence: bool = False,
                   overlap: bool | None = None) -> SweepSchedule:
    """Resolve ``(iters, t, policy)`` into a :class:`SweepSchedule`.

    See :func:`_build_schedule` for the resolution rules; this wrapper
    only adds the observability span (requested vs resolved schedule),
    which is a no-op unless a :mod:`repro.obs` tracer is installed.
    """
    with _obs_span("engine.build_schedule", iters=iters,
                   requested_policy=policy, requested_t=t) as sp:
        sched = _build_schedule(
            iters, spec=spec, shape=shape, dtype=dtype, policy=policy, t=t,
            bm=bm, interpret=interpret, device=device, mesh_shape=mesh_shape,
            remainder_policy=remainder_policy,
            exchange_cadence=exchange_cadence, overlap=overlap)
        sp.set(policy=sched.policy, t=sched.t,
               fused_blocks=sched.fused_blocks, remainder=sched.remainder,
               overlap=sched.overlap)
        return sched


def _build_schedule(iters: int, *, spec: StencilSpec, shape, dtype,
                    policy: str = "auto", t: int | None = None,
                    bm: int | None = None, interpret: bool = False,
                    device: "str | DeviceModel | None" = None,
                    mesh_shape: tuple | None = None,
                    remainder_policy: str = DEFAULT_REMAINDER_POLICY,
                    exchange_cadence: bool = False,
                    overlap: bool | None = None) -> SweepSchedule:
    """Resolve ``(iters, t, policy)`` into a :class:`SweepSchedule`.

    ``policy`` may be a registry name, ``"reference"`` (the pure-jnp
    oracle, distributed callers only), ``"auto"`` (device-aware heuristic)
    or ``"tuned"`` (measured winner) — the latter two are resolved here
    against ``shape``/``dtype``/``device`` with the *real* ``iters`` and
    ``t`` (and ``mesh_shape`` folded into the tuned cache key), so the
    winner is chosen for the schedule that will actually run.

    ``t`` groups sweeps into blocks for fused policies always, and for
    non-fused policies only under ``exchange_cadence=True`` (the
    distributed executor, where ``t`` is the sweeps-per-exchange knob
    regardless of local fusion). An explicit ``t`` that must be clamped to
    ``iters`` raises a ``UserWarning`` — silently degrading the requested
    fusion depth is the same class of bug ``pick_bm`` warns about. A
    fused ``remainder_policy`` is rejected exactly like ``engine.run``
    always has. A 3-D grid ignores ``remainder_policy`` (every other
    policy is 2-D): its remainder runs the fused kernel at that depth, and
    its default depth is :func:`~repro.engine.plan.default_depth`'s.

    ``overlap`` (distributed executors only, i.e. under
    ``exchange_cadence``) selects the interior/rind split that hides each
    exchange behind the halo-independent compute: ``True``/``False``
    force it, ``None`` asks :func:`price_exchange` whether the hidden
    exchange beats the rind's redundant compute for this (shape, spec,
    t, device, mesh) cell — resolved *before* the policy so the tuned
    cache key can carry it and overlapped/serial winners never alias.
    """
    if iters < 0:
        raise PlanError(f"iters={iters} must be >= 0")
    if overlap and not exchange_cadence:
        raise PlanError(
            "overlap=True requires exchange_cadence=True (the distributed "
            "executor): a single-device schedule has no halo exchange to "
            "hide")
    overlap_eff = bool(overlap) and exchange_cadence
    if overlap is None and exchange_cadence and iters > 0:
        t_probe = effective_depth(iters, t)
        nfull_p, rem_p = divmod(iters, t_probe)
        rounds = [(nfull_p, t_probe)] + ([(1, rem_p)] if rem_p else [])
        bill = _price_rounds(rounds, d_max=t_probe * spec.radius,
                             radius=spec.radius, taps=spec.taps,
                             shard_shape=shape, dtype=dtype, device=device,
                             mesh_shape=mesh_shape)
        overlap_eff = bill.wins
    if policy == "auto":
        from repro.engine.dispatch import resolve_auto
        # Distributed executors launch fused policies in their masked
        # (pin-mask-streaming) form; the candidate must be gated by the
        # plan that will actually run, or auto crashes where it should
        # demote.
        policy = resolve_auto(shape, dtype, spec, iters=iters, t=t,
                              device=device, masked=exchange_cadence)
    elif policy == "tuned":
        from repro.engine import tune  # deferred: tune dispatches back here
        policy = tune.best_policy(shape, dtype, spec, iters=iters, t=t,
                                  bm=bm, interpret=interpret, device=device,
                                  mesh=mesh_shape, masked=exchange_cadence,
                                  overlap=overlap_eff)
    if policy == "reference":
        fused = False
    else:
        from repro.engine.dispatch import get_policy
        fused = get_policy(policy).fused

    if fused or exchange_cadence:
        t_eff = effective_depth(iters, t, default_depth(shape, dtype, spec,
                                                        device))
        if t is not None and iters > 0 and t_eff < t:
            warnings.warn(
                f"requested fusion depth t={t} exceeds iters={iters}; "
                f"running t={t_eff} sweeps per "
                f"{'exchange' if exchange_cadence else 'fused block'} "
                f"instead (the schedule cannot fuse sweeps that do not "
                f"exist)", stacklevel=2)
    else:
        t_eff = 1
    nfull, rem = divmod(iters, t_eff)

    if fused and spec.ndim == 3:
        rp = policy  # the kernel again, at the remainder's depth
    elif fused:
        if rem:
            from repro.engine.dispatch import get_policy
            if get_policy(remainder_policy).fused:
                raise ValueError(
                    f"remainder_policy {remainder_policy!r} must be "
                    f"non-fused")
        rp = remainder_policy
    else:
        rp = policy  # non-fused remainders re-run the main policy
    return SweepSchedule(policy=policy, iters=iters, t=t_eff, fused=fused,
                         fused_blocks=nfull, remainder=rem,
                         remainder_policy=rp, radius=spec.radius,
                         overlap=overlap_eff)
