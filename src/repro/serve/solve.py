"""Stencil-solve-as-a-service: bucketed continuous batching with
residual-based eviction.

Every caller used to pay one ``engine.run`` launch per request and run a
*fixed* ``iters`` even after converging. :class:`SolveServer` is the
request-level scheduling layer above the engine:

* **admission** — each :class:`SolveRequest` is validated by building its
  real :class:`~repro.engine.schedule.SweepSchedule` (policy resolution,
  depth clamping, device budget) and running
  :func:`repro.analysis.check_schedule`; rejections are structured
  ``SCHED-*`` diagnostics, not ad-hoc ValueErrors.
* **bucketing** — compatible requests (same ringed shape / spec / dtype /
  resolved policy / block depth ``t`` / device) share a :class:`BucketKey`
  derived from that schedule. A bucket never mixes dtypes or specs:
  :func:`repro.analysis.check_bucket` gates every slot assignment.
* **superblock launch** — each bucket advances all its active slots up
  to ``superblock`` blocks of ``t`` sweeps through ONE jitted launch
  (``lax.scan`` over blocks of a ``vmap`` over the slot axis,
  bit-identical per lane to a solo ``engine.run``); per-slot residuals
  and convergence/budget flags accumulate *inside* the launch (a lane
  that converges is frozen by ``jnp.where`` at its stopping block), so
  one host sync replaces one per block. The slot tensor's buffer is
  donated to each launch, and the residual/liveness history rides back
  via an async device→host copy that overlaps the next bucket's launch.
  A bucket holding one lone request (no queue, no stream) bypasses the
  slot machinery entirely: one :func:`repro.engine.run_converged`
  ``while_loop`` launch carries it to convergence at solo cost.
* **eviction** — a slot whose residual reaches its request's ``tol`` (or
  whose iteration budget is spent) is evicted mid-flight and its slot is
  immediately refilled from the bucket's queue, ``serve/engine.py``
  slot-style. Realized iteration counts are always a multiple of the
  bucket cadence ``t``, so every result is bit-exact (fp32) against
  ``engine.run(iters=request.iters_done)``.
* **streaming** — a request may attach a callback that receives a
  :class:`SolveProgress` (iteration count, residual, optionally the
  iterate itself) after every block.
* **warmup** — :meth:`SolveServer.warm` populates the
  :mod:`repro.engine.tune` cache per (bucket, device) before traffic
  arrives, so the first wave never pays a measurement pass.

``benchmarks/bench_serve.py`` tracks the throughput/latency trajectory of
this layer under mixed traffic in ``BENCH_serve.json``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import check_bucket, check_schedule
from repro.analysis.diagnostics import Report, error
from repro.core.stencil import StencilSpec, jacobi_2d_5pt
from repro.engine.device import DeviceModel, get_device
from repro.engine.dispatch import (residual_for, run, run_batched,  # noqa: F401
                                   run_converged)
from repro.engine.plan import PlanError
from repro.engine.schedule import build_schedule, effective_depth
from repro.obs import metrics as _metrics
from repro.obs.trace import get_tracer, span as _obs_span, use_tracer


class SolveRejected(ValueError):
    """A request the server cannot admit; the message is the structured
    diagnostic report (stable ``SCHED-*`` codes)."""


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """The static launch identity a batch must agree on.

    Derived from the request's resolved schedule at admission: ``policy``
    is the post-``auto``/``tuned`` registry name (or ``"reference"``) and
    ``t`` the realized block cadence, so two requests land in the same
    bucket exactly when one vmapped launch can serve both. Frozen and
    hashable — it keys the server's bucket table and the jitted block
    functions.
    """

    shape: tuple[int, int]
    dtype: str
    spec: StencilSpec
    policy: str
    t: int
    device: "str | DeviceModel | None"
    interpret: bool

    def fields(self) -> dict:
        """Field dict for :func:`repro.analysis.check_bucket`."""
        return {"shape": self.shape, "dtype": self.dtype,
                "spec": self.spec, "policy": self.policy, "t": self.t,
                "device": self.device, "interpret": self.interpret}

    def describe(self) -> str:
        return (f"{self.shape[0]}x{self.shape[1]} {self.dtype} "
                f"{self.policy} t={self.t} "
                f"dev={getattr(self.device, 'name', self.device)}")


@dataclasses.dataclass(frozen=True)
class SolveProgress:
    """One streamed observation: the state after a block of ``t`` sweeps."""

    iters_done: int
    residual: float
    iterate: Optional[np.ndarray] = None  # only with ``stream_iterates``


@dataclasses.dataclass
class SolveRequest:
    """One solve: a ringed grid advanced until ``tol`` or ``max_iters``.

    ``tol=None`` disables residual eviction (fixed-iteration semantics,
    like a bare ``engine.run``). The realized iteration count is always a
    multiple of the bucket cadence ``t`` and never exceeds ``max_iters``;
    convergence is checked once per block, so ``iters_done`` is the first
    multiple of ``t`` at which ``residual <= tol`` held (or
    ``(max_iters // t) * t``). ``stream`` is called with a
    :class:`SolveProgress` after every block; set ``stream_iterates`` to
    also receive the iterate (a host copy — costs a transfer per block).
    """

    grid: "np.ndarray | jax.Array"
    spec: StencilSpec = dataclasses.field(default_factory=jacobi_2d_5pt)
    tol: float | None = None
    max_iters: int = 200
    policy: str = "auto"
    t: int | None = None
    stream: Callable[["SolveRequest", SolveProgress], None] | None = None
    stream_iterates: bool = False

    # Filled in by the server.
    result: np.ndarray | None = None
    iters_done: int = 0
    residual: float | None = None
    converged: bool = False
    done: bool = False
    key: BucketKey | None = None
    target_blocks: int = 0
    blocks_done: int = 0
    submitted_s: float | None = None
    finished_s: float | None = None

    @property
    def latency_s(self) -> float | None:
        if self.submitted_s is None or self.finished_s is None:
            return None
        return self.finished_s - self.submitted_s


class _Bucket:
    """One batch lane-set: slots, queue, and per-bucket counters.

    The jitted superblock launcher is resolved per step via
    :func:`_superblock_for` (the block count ``k`` varies with the
    remaining work), so the bucket itself holds no launch closure.
    """

    def __init__(self, key: BucketKey, max_slots: int):
        self.key = key
        self.max_slots = max_slots
        self.queue: collections.deque[SolveRequest] = collections.deque()
        self.slots: list[SolveRequest | None] = []
        self.us: jax.Array | None = None   # (S, H, W) slot tensor
        self.launches = 0
        self.evicted_early = 0
        self.completed = 0
        self.peak_active = 0

    def admit(self, req: SolveRequest, fields: dict) -> None:
        """Gate a request into this bucket (stable ``SCHED-BUCKET-MIX``
        diagnostics on any static-field mismatch), then enqueue it."""
        report = check_bucket(self.key.fields(), fields)
        for d in report.errors:
            _metrics.counter(f"serve.rejected.{d.code}").inc()
        report.raise_if_errors(SolveRejected)
        self.queue.append(req)

    @property
    def active(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or self.active > 0


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _tol_f32(tol: float) -> np.float32:
    """The largest float32 <= ``tol``: makes the in-launch f32 comparison
    ``residual <= tol32`` decide exactly like the host-side double
    comparison ``float(residual) <= tol`` for every f32 residual."""
    t32 = np.float32(tol)
    if float(t32) > tol:
        t32 = np.nextafter(t32, np.float32(-np.inf))
    return t32


@functools.lru_cache(maxsize=64)
def _superblock_for(key: BucketKey, k: int):
    """One jitted launch advancing every slot up to ``k`` blocks of ``t``
    sweeps, per-slot convergence/budget flags accumulated in-launch.

    The ``lax.scan`` body advances the whole batch one block (``vmap``
    over the slot axis — bit-identical per lane to a solo ``engine.run``)
    and computes per-slot residuals inside the same launch; a lane that
    has converged (``residual <= tol``) or spent its block budget is
    *frozen*: ``jnp.where`` (named scope ``serve.freeze``) carries its
    iterate through unchanged, so its final value is bit-exactly the
    iterate at its stopping block — the same array a one-block-per-launch
    server would have evicted. The
    residual history ``(k, S)`` plus per-block liveness flags return with
    the launch so the host replays block-boundary events (streaming,
    eviction accounting) after ONE sync per superblock instead of one
    per block.

    ``tol`` uses the ``-1.0`` sentinel for fixed-iteration requests
    (residuals are >= 0, so it never triggers). The slot tensor's buffer
    is donated — the server owns it and swaps in the launch result.

    Memoized at module level on ``(key, k)``, so every server instance
    serving the same bucket shares one jit cache.
    """
    res_fn = residual_for(key.spec)

    def one_block(u):
        return run(u, key.spec, policy=key.policy, iters=key.t, t=key.t,
                   interpret=key.interpret, device=key.device)

    def launch(us, conv0, n0, tols, budgets):
        def body(carry, _):
            us, n, conv = carry
            live = (~conv) & (n < budgets)
            vs = jax.vmap(one_block)(us)
            res = jax.vmap(res_fn)(vs)
            with jax.named_scope("serve.freeze"):
                us = jnp.where(live[:, None, None], vs, us)
            n = n + live.astype(n.dtype)
            conv = conv | (live & (res <= tols))
            return (us, n, conv), (res, live)

        (us, n, conv), (hist_res, hist_live) = jax.lax.scan(
            body, (us, n0, conv0), None, length=k)
        return us, n, conv, hist_res, hist_live

    return jax.jit(launch, donate_argnums=(0,))


class SolveServer:
    """Admit → bucket → vmap → evict: continuous batching for solves.

    ``max_slots`` caps each bucket's batch width (slot tensors grow in
    powers of two up to it, so the jit cache holds a handful of batch
    shapes, not one per arrival count). ``superblock`` caps how many
    blocks of ``t`` sweeps one launch may advance a bucket: per-slot
    convergence flags accumulate in-launch, so a 4-block superblock pays
    one host sync where the one-block server paid four (convergence is
    still decided at every block boundary — results are bit-identical).
    Requests submitted between steps are admitted at the next superblock
    boundary. ``device`` / ``interpret`` are server-wide: one server
    plans and launches for one device model.
    """

    def __init__(self, *, max_slots: int = 8, superblock: int = 4,
                 device: "str | DeviceModel | None" = None,
                 interpret: bool | None = None, tracer=None):
        if max_slots < 1:
            raise ValueError(f"max_slots={max_slots} must be >= 1")
        if superblock < 1:
            raise ValueError(f"superblock={superblock} must be >= 1")
        self.max_slots = int(max_slots)
        self.superblock = int(superblock)
        self._device = (get_device(device).name
                        if isinstance(device, str) else device)
        self._interpret = (interpret if interpret is not None
                           else jax.default_backend() != "tpu")
        #: Optional :class:`repro.obs.Tracer` this server installs around
        #: its own admission/stepping work — spans land on it even when
        #: the caller never set one on the context. None defers to
        #: whatever tracer (if any) is already installed.
        self.tracer = tracer
        self._buckets: dict[BucketKey, _Bucket] = {}
        self._completed: list[SolveRequest] = []
        self.warmed: dict[tuple, str] = {}

    def _obs(self):
        """The tracer scope server work runs under (no-op without one)."""
        return (use_tracer(self.tracer) if self.tracer is not None
                else contextlib.nullcontext())

    # ------------------------------------------------------- admission

    def submit(self, req: SolveRequest) -> SolveRequest:
        """Validate, bucket, and enqueue one request.

        Raises :class:`SolveRejected` with structured diagnostics when the
        request cannot be scheduled (``SCHED-REQUEST-INFEASIBLE`` wraps
        planner/budget failures; ``check_schedule`` findings pass through
        verbatim). Admissions bump ``serve.admitted``; every rejection
        bumps ``serve.rejected.<CODE>`` keyed by the diagnostic code.
        """
        with self._obs(), _obs_span("serve.submit", policy=req.policy,
                                    max_iters=req.max_iters) as sp:
            req = self._submit(req)
            sp.set(bucket=req.key.describe(), t=req.key.t)
            return req

    def _submit(self, req: SolveRequest) -> SolveRequest:
        grid = jnp.asarray(req.grid)
        if grid.ndim != 2:
            self._reject(f"grids are 2-D ringed arrays; got shape "
                         f"{tuple(grid.shape)}")
        if req.max_iters < 1:
            self._reject(f"max_iters={req.max_iters} must be >= 1 "
                         f"(nothing to solve)")
        shape = tuple(int(s) for s in grid.shape)
        dtype = jnp.dtype(grid.dtype).name
        try:
            sched = build_schedule(
                req.max_iters, spec=req.spec, shape=shape, dtype=dtype,
                policy=req.policy, t=req.t, interpret=self._interpret,
                device=self._device)
            cadence = effective_depth(req.max_iters, req.t)
            if req.policy != "reference" and sched.policy != "reference":
                # The block launch runs `cadence` sweeps per call; its
                # plan must validate at that depth too (for fused
                # policies sched.t == cadence already).
                build_schedule(cadence, spec=req.spec, shape=shape,
                               dtype=dtype, policy=sched.policy, t=cadence,
                               interpret=self._interpret,
                               device=self._device)
        except (PlanError, ValueError) as e:
            self._reject(str(e), cause=e)
        report = check_schedule(sched, shape=shape, dtype=dtype,
                                spec=req.spec, device=self._device)
        for d in report.errors:
            _metrics.counter(f"serve.rejected.{d.code}").inc()
        report.raise_if_errors(SolveRejected)

        key = BucketKey(shape=shape, dtype=dtype, spec=req.spec,
                        policy=sched.policy, t=cadence,
                        device=self._device, interpret=self._interpret)
        req.grid = grid.astype(jnp.dtype(dtype))
        req.key = key
        req.target_blocks = req.max_iters // cadence
        req.blocks_done = 0
        req.submitted_s = time.perf_counter()
        bucket = self._buckets.get(key)
        if bucket is None:
            bucket = self._buckets[key] = _Bucket(key, self.max_slots)
        bucket.admit(req, key.fields())
        _metrics.counter("serve.admitted").inc()
        return req

    def _reject(self, message: str, cause: Exception | None = None):
        _metrics.counter("serve.rejected.SCHED-REQUEST-INFEASIBLE").inc()
        report = Report((error(
            "SCHED-REQUEST-INFEASIBLE", "request", message,
            hint="resize the grid, lower t, or serve on a device with "
                 "more fast memory"),))
        raise SolveRejected(report.describe()) from cause

    # --------------------------------------------------------- warmup

    def warm(self, shapes, spec: StencilSpec | None = None, *,
             dtype=jnp.float32, iters: int = 1, t: int | None = None
             ) -> dict[tuple, str]:
        """Pre-measure the tune cache for the buckets traffic will hit.

        Thin wrapper over :func:`repro.engine.tune.warm` with the
        server's device/interpret, so ``policy="tuned"`` requests never
        pay a measurement pass at admission time. Idempotent; returns
        ``{shape: winner}`` and records it in :attr:`warmed`.
        """
        from repro.engine import tune
        spec = spec if spec is not None else jacobi_2d_5pt()
        won = tune.warm(shapes, dtype, spec, iters=iters, t=t,
                        interpret=self._interpret, device=self._device)
        self.warmed.update(won)
        return won

    # -------------------------------------------------------- stepping

    def _fill_slots(self, bucket: _Bucket) -> None:
        demand = bucket.active + len(bucket.queue)
        want = min(bucket.max_slots, _next_pow2(max(demand, 1)))
        if want > len(bucket.slots):
            pad = want - len(bucket.slots)
            dummy = jnp.zeros((pad,) + bucket.key.shape,
                              jnp.dtype(bucket.key.dtype))
            bucket.us = (dummy if bucket.us is None
                         else jnp.concatenate([bucket.us, dummy]))
            bucket.slots.extend([None] * pad)
        elif want < len(bucket.slots):
            # Compact the straggler tail: gather active lanes into a
            # narrower slot tensor (an exact copy — bit-exactness holds)
            # so evicted lanes stop paying sweeps. Widths stay powers of
            # two, so this reuses the same jitted block shapes growth
            # already compiled.
            keep = [i for i, r in enumerate(bucket.slots) if r is not None]
            kept = (bucket.us[jnp.asarray(keep, jnp.int32)] if keep
                    else jnp.zeros((0,) + bucket.key.shape,
                                   jnp.dtype(bucket.key.dtype)))
            pad = want - len(keep)
            if pad:
                kept = jnp.concatenate([
                    kept, jnp.zeros((pad,) + bucket.key.shape,
                                    jnp.dtype(bucket.key.dtype))])
            bucket.us = kept
            bucket.slots = [bucket.slots[i] for i in keep] + [None] * pad
        for i, slot in enumerate(bucket.slots):
            if slot is None and bucket.queue:
                req = bucket.queue.popleft()
                bucket.us = bucket.us.at[i].set(req.grid)
                bucket.slots[i] = req
        bucket.peak_active = max(bucket.peak_active, bucket.active)

    def _evict(self, bucket: _Bucket, i: int, converged: bool) -> None:
        req = bucket.slots[i]
        bucket.slots[i] = None           # the slot is free immediately
        self._finish(bucket, req, np.asarray(bucket.us[i]), converged)

    def _finish(self, bucket: _Bucket, req: SolveRequest,
                result: np.ndarray, converged: bool) -> None:
        req.result = result
        req.converged = converged
        req.done = True
        req.finished_s = time.perf_counter()
        bucket.completed += 1
        if converged and req.blocks_done < req.target_blocks:
            bucket.evicted_early += 1
        self._completed.append(req)

    def step(self) -> int:
        """Advance every busy bucket by one superblock (up to
        ``superblock`` blocks of its cadence ``t``).

        Returns the number of launches performed (0 = fully drained).
        Slots freed by eviction are refilled from the bucket queue
        *before* the next superblock, so a long queue streams through a
        fixed set of slots, and requests submitted between steps join at
        the next superblock boundary. Stepping is two-phase: every busy
        bucket's launch is dispatched first (with an async copy of its
        per-block residual/liveness history back to the host), then the
        histories are replayed — block-boundary events for one bucket
        overlap the next bucket's launch. A bucket whose only traffic is
        a single lone request (one active slot, empty queue, no stream)
        bypasses the slot machinery entirely: one ``run_converged``
        launch carries it to convergence or budget in-launch. Each
        launch runs under a ``serve.block`` span (bucket identity,
        active slots, queue depth, block count; max residual and
        evictions set at exit) and feeds the ``serve.*``
        gauges/counters.
        """
        with self._obs():
            return self._step()

    def _step(self) -> int:
        launches = 0
        pending = []
        for bucket in self._buckets.values():
            if not bucket.busy:
                continue
            if (bucket.active == 0 and len(bucket.queue) == 1
                    and bucket.queue[0].stream is None):
                # Fresh lone request: never touches the slot tensor.
                launches += self._step_lone(bucket)
                continue
            self._fill_slots(bucket)
            if bucket.active == 0:
                continue
            lone = [r for r in bucket.slots if r is not None]
            if (len(lone) == 1 and not bucket.queue
                    and lone[0].stream is None):
                launches += self._step_lone(bucket)
                continue
            launches += self._dispatch_superblock(bucket, pending)
        for bucket, k, out, cm, sp in pending:
            try:
                self._replay(bucket, k, out, sp)
            finally:
                cm.__exit__(None, None, None)
        return launches

    def _step_lone(self, bucket: _Bucket) -> int:
        """Single-request bypass: no vmap lane, no slot-history replay.

        ``run_converged`` advances the lone grid block-by-block inside
        ONE ``lax.while_loop`` launch with the in-launch residual check
        at the same ``t``-block cadence the batched path uses (``tol``
        narrowed by :func:`_tol_f32` so the f32 in-launch comparison
        decides exactly like the batched path's), so the request lands
        bit-identically to slot serving at solo-``engine.run`` cost.
        A fresh lone request (no slot occupied yet) runs straight off
        ``req.grid`` — the slot tensor is never allocated or copied
        into; a request left alone mid-flight resumes from its lane.
        """
        key = bucket.key
        if bucket.active:
            i = next(j for j, r in enumerate(bucket.slots)
                     if r is not None)
            req, u = bucket.slots[i], bucket.us[i]
        else:
            i, req = None, bucket.queue.popleft()
            u = req.grid
            bucket.peak_active = max(bucket.peak_active, 1)
        remaining = req.target_blocks - req.blocks_done
        tol = None if req.tol is None else float(_tol_f32(req.tol))
        with _obs_span("serve.block", bucket=key.describe(),
                       launch=bucket.launches, active=1, queue=0,
                       blocks=remaining, lone=True) as sp:
            v, iters, residual = run_converged(
                u, key.spec, tol=tol,
                max_iters=remaining * key.t, policy=key.policy, t=key.t,
                interpret=key.interpret, device=key.device)
            bucket.launches += 1
            req.blocks_done += int(iters) // key.t
            req.iters_done = req.blocks_done * key.t
            req.residual = float(residual)
            converged = req.tol is not None and req.residual <= req.tol
            if i is not None:
                bucket.slots[i] = None   # lane is stale; refills overwrite
            self._finish(bucket, req, np.asarray(v), converged)
            sp.set(max_residual=req.residual, evicted=1)
        _metrics.counter("serve.evictions").inc(1)
        _metrics.gauge("serve.active_slots").set(bucket.active)
        _metrics.gauge("serve.queue_depth").set(len(bucket.queue))
        _metrics.gauge("serve.max_residual").set(req.residual)
        tracer = get_tracer()
        if tracer is not None:
            tracer.counter("serve.slots", {"active": bucket.active,
                                           "queue": len(bucket.queue)})
        return 1

    def _dispatch_superblock(self, bucket: _Bucket, pending: list) -> int:
        """Launch up to ``superblock`` blocks for one bucket; defer the
        host-side replay until every bucket has dispatched."""
        key = bucket.key
        active = [r for r in bucket.slots if r is not None]
        k = max(1, min(self.superblock,
                       max(r.target_blocks - r.blocks_done
                           for r in active)))
        if any(r.stream_iterates for r in active):
            # Streamed iterates are host copies at every block boundary;
            # only a one-block launch exposes each boundary state.
            k = 1
        n_slots = len(bucket.slots)
        conv0 = np.zeros(n_slots, bool)
        n0 = np.zeros(n_slots, np.int32)
        tols = np.full(n_slots, -1.0, np.float32)  # sentinel: never fires
        budgets = np.zeros(n_slots, np.int32)
        for i, r in enumerate(bucket.slots):
            if r is None:
                conv0[i] = True            # empty lanes stay frozen
                continue
            n0[i] = r.blocks_done
            budgets[i] = r.target_blocks
            if r.tol is not None:
                tols[i] = _tol_f32(r.tol)
        cm = _obs_span("serve.block", bucket=key.describe(),
                       launch=bucket.launches, active=bucket.active,
                       queue=len(bucket.queue), blocks=k)
        sp = cm.__enter__()
        out = _superblock_for(key, k)(
            bucket.us, jnp.asarray(conv0), jnp.asarray(n0),
            jnp.asarray(tols), jnp.asarray(budgets))
        bucket.us = out[0]                 # the old buffer was donated
        for arr in out[1:]:
            if hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()   # readback overlaps next launch
        bucket.launches += 1
        pending.append((bucket, k, out, cm, sp))
        return 1

    def _replay(self, bucket: _Bucket, k: int, out, sp) -> None:
        """Replay one superblock's per-block history on the host:
        streaming callbacks, iteration accounting, and eviction — the
        same block-boundary events a one-block-per-launch server fires,
        reconstructed from the launch's ``(k, S)`` residual/liveness
        history after a single sync."""
        _, _, conv, hist_res, hist_live = out
        conv_arr = np.asarray(conv)
        hres = np.asarray(hist_res)
        hlive = np.asarray(hist_live)
        t = bucket.key.t
        evicted = 0
        max_residual = 0.0
        for i, req in enumerate(list(bucket.slots)):
            if req is None:
                continue
            for j in range(k):
                if not hlive[j, i]:
                    continue
                req.blocks_done += 1
                req.iters_done = req.blocks_done * t
                req.residual = float(hres[j, i])
                max_residual = max(max_residual, req.residual)
                if req.stream is not None:
                    iterate = (np.asarray(bucket.us[i])
                               if req.stream_iterates else None)
                    req.stream(req, SolveProgress(req.iters_done,
                                                  req.residual, iterate))
            converged = bool(conv_arr[i])
            if converged or req.blocks_done >= req.target_blocks:
                self._evict(bucket, i, converged)
                evicted += 1
        sp.set(max_residual=max_residual, evicted=evicted)
        if evicted:
            _metrics.counter("serve.evictions").inc(evicted)
        _metrics.gauge("serve.active_slots").set(bucket.active)
        _metrics.gauge("serve.queue_depth").set(len(bucket.queue))
        _metrics.gauge("serve.max_residual").set(max_residual)
        tracer = get_tracer()
        if tracer is not None:
            tracer.counter("serve.slots", {"active": bucket.active,
                                           "queue": len(bucket.queue)})

    @property
    def busy(self) -> bool:
        return any(b.busy for b in self._buckets.values())

    def drain(self, max_launches: int = 1_000_000) -> list[SolveRequest]:
        """Step until every admitted request has completed."""
        while self.busy:
            if max_launches <= 0:
                raise RuntimeError("drain exceeded its launch budget")
            max_launches -= self.step()
        return list(self._completed)

    def solve(self, requests) -> list[SolveRequest]:
        """Convenience: submit a batch of requests and drain the server.

        Returns the same request objects (mutated in place with results),
        in the caller's order.
        """
        reqs = list(requests)
        for r in reqs:
            self.submit(r)
        self.drain()
        return reqs

    # ------------------------------------------------------ inspection

    @property
    def buckets(self) -> tuple[BucketKey, ...]:
        return tuple(self._buckets)

    def stats(self) -> dict:
        """Aggregate serving counters (per bucket + totals)."""
        per = {
            b.key.describe(): {
                "launches": b.launches, "completed": b.completed,
                "evicted_early": b.evicted_early,
                "peak_active": b.peak_active, "slots": len(b.slots),
            } for b in self._buckets.values()
        }
        return {
            "buckets": len(self._buckets),
            "launches": sum(b.launches for b in self._buckets.values()),
            "completed": sum(b.completed for b in self._buckets.values()),
            "evicted_early": sum(b.evicted_early
                                 for b in self._buckets.values()),
            "per_bucket": per,
        }
