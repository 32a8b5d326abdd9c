"""Execution planning for the stencil engine.

A *plan* is everything that must be decided before a policy kernel can be
launched: the row-block size ``bm`` (the grid granularity), the fast-memory
window that block implies, the temporal fusion depth, and whether the whole
thing fits the *device's* per-core fast-memory budget (TPU VMEM, Tensix
SRAM, GPU shared memory — see :mod:`repro.engine.device`; the budget used
to be a single hard-coded 16 MiB constant). Plans are pure functions of
static arguments (shape, dtype, spec, policy, device, requested knobs), so
they are memoized in an in-process cache — re-dispatching the same problem
costs a dict lookup, not a re-derivation (and, because the policy wrappers
are jitted on the same static keys, not a retrace either). Plans for the
same problem on different devices are distinct cache entries.

``pick_bm`` lives here as the single shared copy; it used to be duplicated
verbatim in ``kernels/jacobi.py`` and ``kernels/stencil_general.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax.numpy as jnp

from repro.core.stencil import StencilSpec
from repro.engine.device import DeviceModel, get_device
from repro.obs import metrics as _metrics

# Knob defaults shared by every policy.
DEFAULT_BM = 256   # largest interior-row block bm=None plans
DEFAULT_T = 8      # temporal fusion depth (sweeps per HBM round-trip)

#: A temporal window of at most this many f32 vregs (8x128 tiles) sweeps
#: as one value, the register file's size: larger windows sweep strip by
#: strip from VMEM scratch, where a carried value would spill. Measured on
#: a TPU v5e: a 288x24 window takes 0.024 ms a launch whole and 0.094 ms
#: in strips; a 24x9232 one (219 vregs) 0.023 whole and 0.019 in strips.
WHOLE_WINDOW_VREGS = 64


class PlanError(ValueError):
    """A (shape, dtype, spec, policy, device) combination that cannot be
    planned."""


def sublane_tile(dtype) -> int:
    """Rows in one TPU sublane tile for ``dtype``: 8 for 4-byte elements,
    16 for 2-byte (two rows pack per sublane), 32 for 1-byte.

    Mosaic refuses a row block or window whose height is not a multiple
    of this (``Slice shape along dimension 0 must be aligned to tiling``),
    so every Pallas policy plans its row blocks in these units.
    """
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def bm_candidates(h_int: int, align: int) -> list[int]:
    """Exact row-block heights for an ``h_int``-row interior, largest
    first: every divisor that is a multiple of ``align``, plus the full
    interior height (a single block, always legal)."""
    out = {h_int}
    out.update(d for d in range(align, h_int, align) if h_int % d == 0)
    return sorted(out, reverse=True)


def pick_bm(h_int: int, bm: int, align: int = 1) -> int:
    """Largest legal block height <= ``bm``.

    Legal heights are the divisors of ``h_int`` that are multiples of
    ``align`` (the dtype's sublane tile on a TPU), or ``h_int`` itself;
    a request below all of them gets the smallest. Warns when the request
    degrades all the way to ``bm=1`` (e.g. a prime interior height like
    1021 rows with ``align=1`` turns into 1021 one-row grid steps) --
    that is always a performance bug the caller should hear about.
    """
    req = min(bm, h_int)
    cands = bm_candidates(h_int, align)
    fit = [c for c in cands if c <= req]
    bm = fit[0] if fit else cands[-1]
    if bm == 1 and req > 1:
        warnings.warn(
            f"pick_bm: interior height {h_int} has no divisor <= {req}; "
            f"realized bm=1 (one grid step per row — expect poor DMA "
            f"efficiency; pad the grid or pick a height with small factors)",
            stacklevel=2)
    return bm


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Fully-resolved launch parameters for one policy on one problem.

    shape/dtype describe the ringed grid (boundary included); ``bm`` is the
    number of interior rows each grid step produces; ``window_rows`` is the
    height of the fast-memory-resident input window that block needs
    (bm + halo); ``t`` is the number of sweeps fused per HBM round-trip
    (1 unless the policy is temporal); ``device`` is the model whose budget
    validated the plan.

    A 3-D plan (ringed shape (planes, rows, lanes), temporal only) blocks
    the plane axis alone: ``bm`` is the interior planes a grid step
    produces, ``window_rows`` the planes its window holds (``bm`` plus
    :attr:`halo_planes`), and every plane is streamed whole.
    """

    policy: str
    shape: tuple[int, ...]
    dtype: str
    spec: StencilSpec
    bm: int
    t: int
    window_rows: int
    vmem_bytes: int
    device: DeviceModel
    #: Temporal only: the kernel streams a per-cell pin mask alongside the
    #: grid (distributed shards pin the *global* Dirichlet ring, not the
    #: whole block edge). Changes the fast-memory footprint, so it is part
    #: of the plan, and the lowering emits the mask stream from it.
    masked: bool = False

    @property
    def radius(self) -> int:
        return self.spec.radius

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def interior_shape(self) -> tuple[int, ...]:
        r = self.spec.radius
        return tuple(s - 2 * r for s in self.shape)

    @property
    def nblocks(self) -> int:
        """Grid steps; the last block is ragged when ``bm`` does not
        divide the interior height."""
        return -(-self.interior_shape[0] // self.bm)

    @property
    def halo_rows(self) -> tuple[int, int]:
        """Tile-aligned rows the kernel streams above and below each
        ``bm``-row main block (``(0, 0)`` for one whole-grid block, and
        for a 3-D plan, which streams every plane whole)."""
        if self.ndim == 3:
            return (0, 0)
        return _halo_rows(self.policy, self.radius, self.t, self.dtype,
                          self.nblocks == 1)

    @property
    def halo_planes(self) -> tuple[int, int]:
        """3-D only: planes the kernel streams before and after each
        ``bm``-plane main block; ``(0, 0)`` for a 2-D plan."""
        if self.ndim != 3:
            return (0, 0)
        return _halo_planes(self.radius, self.t)

    @property
    def dtype_bytes(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def kernel_rows(self) -> int:
        """Window rows the kernel holds per block (see
        :func:`_kernel_rows`); a 3-D plan's rows per plane."""
        if self.ndim == 3:
            return _round_up(self.shape[1], sublane_tile(jnp.float32))
        return _kernel_rows(self.policy, self.shape[0], self.dtype, self.bm,
                            self.halo_rows, self.nblocks == 1)

    @property
    def strip_rows(self) -> int:
        """Rows one strip of a temporal sweep computes at a time (see
        :func:`_strip_rows`); 0 where the kernel has no strip loop. A 3-D
        plan always sweeps in strips."""
        if self.ndim == 3:
            return sublane_tile(jnp.float32)
        return _strip_rows(self.policy, self.kernel_rows, self.shape[1])

    @property
    def recompute(self) -> float:
        """Rows a sweep computes per interior row it keeps: the temporal
        kernel sweeps its whole window, halo included; the others compute
        only their block. A 3-D sweep ``s`` of ``t`` skips the ``s * r``
        planes at each end of the window that are already stale, so on
        average ``bm + (t - 1) * r`` planes are swept per ``bm`` kept, each
        over ``kernel_rows`` rows per interior row kept."""
        if self.policy != "temporal":
            return 1.0
        if self.ndim == 3:
            planes = (self.bm + (self.t - 1) * self.radius) / self.bm
            return planes * self.kernel_rows / self.interior_shape[1]
        return self.kernel_rows / self.bm

    def describe(self) -> str:
        if self.ndim == 3:
            return (f"{self.policy}: grid={self.shape} dtype={self.dtype} "
                    f"taps={self.spec.taps} r={self.radius} "
                    f"planes={self.bm} halo_planes={self.halo_planes} "
                    f"t={self.t} window={self.window_rows}x"
                    f"{self.kernel_rows}x{self.shape[2]} "
                    f"vmem={self.vmem_bytes / 1024:.0f}KiB "
                    f"blocks={self.nblocks} strip={self.strip_rows} "
                    f"recompute={self.recompute:.3f} "
                    f"device={self.device.name}")
        strip = (f"strip={self.strip_rows} recompute={self.recompute:.3f} "
                 if self.policy == "temporal" else "")
        return (f"{self.policy}: grid={self.shape} dtype={self.dtype} "
                f"taps={self.spec.taps} r={self.radius} bm={self.bm} "
                f"t={self.t} window={self.window_rows}x{self.shape[1]} "
                f"vmem={self.vmem_bytes / 1024:.0f}KiB blocks={self.nblocks} "
                f"{strip}device={self.device.name}")


def _row_align(policy: str, r: int, t: int, dtype) -> int:
    """Rows a block height must be a multiple of: the sublane tile, or
    the policy's halo block when that is taller (the halo rides as a
    block of its own, indexed in units of its height)."""
    tile = sublane_tile(dtype)
    if policy == "temporal":
        # The output rows sit r below the main block's first row; t sweeps
        # need t*r valid rows past them on each side.
        return _round_up((t + 1) * r, tile)
    if policy in ("rowchunk", "dbuf"):
        return _round_up(2 * r, tile)
    return tile


def _halo_rows(policy: str, r: int, t: int, dtype,
               single: bool) -> tuple[int, int]:
    """Rows above/below a main block that a policy's kernel streams,
    tile-aligned so every block DMA is."""
    if single or policy == "shifted":
        return (0, 0)
    hb = _row_align(policy, r, t, dtype)
    return (hb, hb) if policy == "temporal" else (0, hb)


def _kernel_rows(policy: str, h: int, dtype, bm: int, halo: tuple[int, int],
                 single: bool) -> int:
    """Window rows a kernel holds per block: its halo and main blocks, or
    the whole grid for a single block, which the temporal kernel rounds
    up to the sublane tile like every other block."""
    if not single:
        return halo[0] + bm + halo[1]
    return _round_up(h, sublane_tile(dtype)) if policy == "temporal" else h


def _strip_rows(policy: str, kwin: int, w: int) -> int:
    """Rows one strip of a temporal sweep computes: the sublane tile of
    the f32 scratch the sweeps run in; 0 where the window is at most
    :data:`WHOLE_WINDOW_VREGS` and sweeps as one value, and for the
    single-sweep policies."""
    tile = sublane_tile(jnp.float32)
    vregs = -(-kwin // tile) * -(-w // 128)
    if policy != "temporal" or vregs <= WHOLE_WINDOW_VREGS:
        return 0
    return tile


def _window_and_vmem(policy: str, shape, dtype, spec: StencilSpec,
                     bm: int, t: int, masked: bool = False) -> tuple[int, int]:
    """Stencil window height and the kernel's fast-memory footprint.

    The footprint counts what the Pallas kernel holds per grid step: the
    streamed blocks times their buffering depth (the Pallas pipeline
    double-buffers a block unless the policy asks for one buffer), plus
    the f32 working copies or scratch the tap arithmetic runs on.
    """
    h, w = shape
    r = spec.radius
    hi, wi = h - 2 * r, w - 2 * r
    db = jnp.dtype(dtype).itemsize
    if policy not in ("shifted", "rowchunk", "dbuf", "temporal"):
        raise PlanError(f"unknown policy {policy!r}")
    halo = _halo_rows(policy, r, t, dtype, bm == hi)
    kwin = _kernel_rows(policy, h, dtype, bm, halo, bm == hi)
    out = 2 * bm * wi * db                       # double-buffered output
    if policy == "shifted":
        # One streamed (bm, wi) block per tap plus the output block; the
        # Pallas pipeline double-buffers them (x2).
        return bm, 2 * spec.taps * bm * wi * db + out
    if policy in ("rowchunk", "dbuf"):
        # rowchunk streams its window through one buffer (load, compute,
        # store in turn); dbuf double-buffers it so the next window loads
        # while this one computes.
        bufs = 1 if policy == "rowchunk" else 2
        f32 = kwin * w * 4 + 2 * bm * wi * 4     # window + accumulator
        return min(bm + 2 * r, h), bufs * kwin * w * db + out + f32
    # temporal: grid (and pin-mask) windows double-buffered, the output
    # block, and the f32 copies the t sweeps run on: in strips, a scratch
    # of two ping-pong windows, plus the pin mask's with a mask (an even
    # number of lane tiles: a tile of each column parity); whole, the
    # carried window, the pinned originals, a rolled tap and the sum.
    # Every VMEM row holds whole lane tiles.
    lw, lwi = _round_up(w, 128), _round_up(wi, 128)
    streams = 2 if masked else 1
    if _strip_rows(policy, kwin, w):
        f32 = (streams + 1) * kwin * _round_up(w, 256) * 4
    else:
        f32 = 4 * kwin * lw * 4
    return (min(bm + 2 * t * r, h),
            2 * streams * kwin * lw * db + 2 * bm * lwi * db + f32)


def _halo_planes(r: int, t: int) -> tuple[int, int]:
    """Planes a 3-D temporal window holds before and after its main block:
    the output planes sit ``r`` past the main block's first, and ``t``
    sweeps need ``t * r`` valid planes past them on each side."""
    return ((t - 1) * r, (t + 1) * r)


def _vmem_3d(shape, dtype, bp: int, t: int, r: int) -> int:
    """Fast-memory footprint of the 3-D temporal kernel for ``bp``-plane
    blocks: the window's planes streamed in (double-buffered), the output
    block (double-buffered), and two f32 ping-pong scratch windows of
    whole lane-tile pairs. Each plane takes whole sublane and lane tiles."""
    _, h, w = shape
    hi, wi = h - 2 * r, w - 2 * r
    tile = sublane_tile(dtype)
    db = jnp.dtype(dtype).itemsize
    kp = bp + sum(_halo_planes(r, t))
    plane_in = _round_up(h, tile) * _round_up(w, 128) * db
    plane_out = _round_up(hi, tile) * _round_up(wi, 128) * db
    plane_f32 = _round_up(h, sublane_tile(jnp.float32)) * _round_up(w, 256) * 4
    return 2 * kp * plane_in + 2 * bp * plane_out + 2 * kp * plane_f32


def _plan_3d(shape, dtype: str, spec: StencilSpec, policy: str,
             bm_req: int | None, t: int, device: DeviceModel,
             masked: bool) -> ExecutionPlan:
    """A 3-D temporal plan: the deepest plane block up to the interior's
    (or the ``bm`` request) whose footprint fits; the last may be
    ragged."""
    r = spec.radius
    if policy != "temporal":
        raise PlanError(f"policy {policy!r} is 2-D only; a 3-D grid runs "
                        f"the temporal kernel")
    if masked:
        raise PlanError("a 3-D plan takes no pin mask: the mesh path is "
                        "2-D")
    pi = shape[0] - 2 * r
    for bp in range(min(bm_req or pi, pi), 0, -1):
        vmem = _vmem_3d(shape, dtype, bp, t, r)
        if vmem <= device.fast_memory_bytes:
            break
    else:
        from repro.analysis.diagnostics import budget_message
        raise PlanError(
            budget_message(f"policy {policy!r} for 3-D grid {shape} "
                           f"(one plane a block, t={t})", vmem, device)
            + " — lower t, or plan for a device with more fast memory")
    return ExecutionPlan(policy=policy, shape=shape, dtype=dtype, spec=spec,
                         bm=bp, t=t, window_rows=bp + sum(_halo_planes(r, t)),
                         vmem_bytes=vmem, device=device, masked=False)


@functools.lru_cache(maxsize=1024)
def _plan_cached(shape: tuple[int, ...], dtype: str, spec: StencilSpec,
                 policy: str, bm_req: int | None, t: int,
                 device: DeviceModel, masked: bool) -> ExecutionPlan:
    # Executed only on a cache miss (lru_cache body), so this counter plus
    # the request counter in plan_for gives the hit/miss split.
    _metrics.counter("engine.plan.miss").inc()
    r = spec.radius
    if spec.ndim not in (2, 3):
        raise PlanError(f"engine policies are 2-D or 3-D; spec has "
                        f"ndim={spec.ndim} (embed 1-D stencils as 2-D row "
                        f"stencils)")
    if len(shape) != spec.ndim:
        raise PlanError(f"grid {shape} has {len(shape)} axes; the spec has "
                        f"{spec.ndim}")
    if any(s <= 2 * r for s in shape):
        raise PlanError(f"grid {shape} too small for stencil radius {r}")
    if t < 1:
        raise PlanError(f"temporal depth t={t} must be >= 1")
    if spec.ndim == 3:
        return _plan_3d(shape, dtype, spec, policy, bm_req, t, device,
                        masked)
    h, w = shape
    if masked and policy != "temporal":
        raise PlanError(f"policy {policy!r} takes no pin mask; only the "
                        f"temporal kernel streams one")
    hi = h - 2 * r
    align = _row_align(policy, r, t, dtype)
    if bm_req is None:
        # The largest block up to DEFAULT_BM (so the grid keeps several
        # steps for the pipeline to overlap) that fits the budget: exact
        # tilings first, then tile multiples with a ragged last block
        # (the kernels drop its rows past the interior).
        cap = min(DEFAULT_BM, hi)
        cands = [c for c in bm_candidates(hi, align) if c <= cap]
        cands += [c for c in range(cap // align * align, 0, -align)
                  if c not in cands]
        cands = cands or [pick_bm(hi, cap, align)]
    else:
        cands = [pick_bm(hi, bm_req, align)]
    for bm in cands:
        win, vmem = _window_and_vmem(policy, shape, dtype, spec, bm, t,
                                     masked)
        if vmem <= device.fast_memory_bytes:
            break
    else:
        # Lazy import: diagnostics is stdlib-only, but keep the planner's
        # import graph free of repro.analysis on the happy path.
        from repro.analysis.diagnostics import budget_message
        raise PlanError(
            budget_message(f"policy {policy!r} for grid {shape} "
                           f"(bm={bm}, t={t})", vmem, device)
            + " — lower bm or t, or plan for a device with more fast memory")
    return ExecutionPlan(policy=policy, shape=shape, dtype=dtype, spec=spec,
                         bm=bm, t=t, window_rows=win, vmem_bytes=vmem,
                         device=device, masked=masked)


def plan_for(shape, dtype, spec: StencilSpec, policy: str, *,
             bm: int | None = None, t: int | None = None,
             device: str | DeviceModel | None = None,
             masked: bool = False) -> ExecutionPlan:
    """Resolve (and cache) an :class:`ExecutionPlan` for static arguments.

    ``bm``/``t`` are requests; the plan holds the realized values (``bm``
    is snapped by :func:`pick_bm` to the policy's row alignment, ``t`` is
    forced to 1 for non-temporal policies). ``bm=None`` takes the largest
    height up to ``DEFAULT_BM`` whose footprint fits the device, exact
    tilings first, then a ragged last block. ``device`` is a registry name or model; None
    plans against the detected host backend (``device.detect()``).
    ``masked`` plans the temporal kernel's explicit pin-mask stream (the
    distributed shard form).
    """
    t_eff = (t if t is not None else DEFAULT_T) if policy == "temporal" else 1
    misses0 = _metrics.counter("engine.plan.miss").value
    plan = _plan_cached(tuple(int(s) for s in shape), jnp.dtype(dtype).name,
                        spec, policy, None if bm is None else int(bm),
                        int(t_eff), get_device(device), bool(masked))
    if _metrics.counter("engine.plan.miss").value == misses0:
        _metrics.counter("engine.plan.hit").inc()
    return plan


def default_depth(shape, dtype, spec: StencilSpec,
                  device: str | DeviceModel | None = None) -> int:
    """The temporal depth a schedule fuses when none is asked for.

    :data:`DEFAULT_T` on a 2-D grid. A 3-D window carries its halo as
    whole planes, ``2 * t * r`` of them around each block, which the
    fast-memory budget has to hold beside the block: a deeper ``t`` buys
    fewer write-backs with a thinner block and more planes swept per
    plane kept. So a 3-D grid takes the deepest ``t <= DEFAULT_T`` at
    which its sweeps cover at most two planes per plane kept (``bm + (t -
    1) * r <= 2 * bm``, the rows per row kept of the paper's 2-D plan), or
    whose block is the whole interior; 1 where none is. At PolyBench's
    200^3 on a v5e that is ``t=6``, the fastest of ``t`` in 4-8 on the
    chip.
    """
    if spec.ndim != 3:
        return DEFAULT_T
    for t in range(DEFAULT_T, 1, -1):
        try:
            plan = plan_for(shape, dtype, spec, "temporal", t=t,
                            device=device)
        except PlanError:
            continue
        if plan.bm >= min((t - 1) * spec.radius, plan.interior_shape[0]):
            return t
    return 1


def plan_cache_info():
    """lru_cache statistics for the plan cache (hits/misses/currsize)."""
    return _plan_cached.cache_info()


def plan_cache_clear() -> None:
    _plan_cached.cache_clear()
