"""Pallas execution policies for arbitrary 2-D stencils.

The four kernel generations of the paper's §IV → §VI → Table I → future-work
arc, each generalized from the hard-coded 5-point Jacobi (0.25 x 4 taps) to
any 2-D :class:`~repro.core.stencil.StencilSpec` (any radius, any tap set):

  ``shifted``   — paper §IV *initial* design: one pre-shifted neighbour copy
      per tap is materialized in HBM and streamed in as a separate operand
      ("N CBs packed from a local buffer"). Memory traffic ≈ (taps+1)x the
      domain per sweep. Kept as the faithful baseline.

  ``rowchunk``  — paper §VI *optimized* design: one contiguous full-width
      row window (the block plus its halo rows) is streamed from HBM into
      VMEM per grid step; every tap is served by an in-VMEM shifted view
      of the same window (the paper's CB read-pointer aliasing). Traffic
      ≈ 1x + the halo rows per block, independent of tap count — the
      whole point of the §VI design. The window has one buffer: each
      step loads, computes and stores in turn.

  ``dbuf``      — rowchunk with the window double-buffered, so the next
      window loads while this one computes (the paper's Table I "double
      buffering" row, done by the TPU's block pipeline).

  ``temporal``  — beyond-paper: T sweeps fused per HBM round-trip. Each
      block streams a window with tile-rounded halos of at least
      (T+1)*r rows per side, advances it T sweeps locally (valid region
      shrinking by r rows per sweep) and writes back the central rows.
      HBM traffic per sweep drops ~Tx at the cost of O(T²r²) redundant
      halo compute — the right trade when the compute:bandwidth ratio
      dwarfs the stencil's arithmetic intensity.

Windows are built from Pallas blocks whose heights are multiples of the
dtype's sublane tile (8 rows for f32, 16 for bf16): the TPU's compiler
refuses a DMA row window of any other height, and refuses any manual row
slice of an HBM grid whose width is not a multiple of 128 lanes (the
paper's ringed width is 9218). So the grid operand stays in HBM and only
blocks enter VMEM; the planner (``engine.plan``) picks block heights that
tile the interior, or lets the last block run ragged.

All grids are "ringed": shape (H, W) with a fixed Dirichlet boundary ring of
width ``spec.radius``; only the interior is updated. Kernels accumulate in
f32 and store in the input dtype. Launch parameters come from
``engine.plan.plan_for`` (cached), never ad hoc; every entry point takes a
static ``device`` (registry name or frozen DeviceModel) so the plan is
validated against the fast-memory budget of the hardware being planned
for, not a constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import StencilSpec
from repro.engine.device import DeviceModel  # noqa: F401  (annotations)
from repro.engine.plan import plan_for
from repro.obs import metrics as _metrics


def _tap_sum(c, bm: int, r: int, w: int, offsets, weights):
    """Weighted sum of in-VMEM shifted views of a resident (bm+2r, w) window."""
    acc = None
    for (dy, dx), wt in zip(offsets, weights):
        # tap view: rows [r+dy, r+dy+bm), cols [r+dx, w-r+dx)
        tap = jax.lax.slice(c, (r + dy, r + dx), (r + dy + bm, w - r + dx))
        term = tap * jnp.float32(wt)
        acc = term if acc is None else acc + term
    return acc


def _interior_index(shape, r: int):
    return tuple(slice(r, s - r) for s in shape)


def _compiler_params(plan, interpret: bool):
    """Count the trace as compiled or interpreted (the
    ``engine.kernel.*`` counters a chip run checks), and hold Mosaic to
    the fast-memory budget the plan was validated against, so planner
    and compiler agree on what fits."""
    _metrics.counter("engine.kernel.interpret" if interpret
                     else "engine.kernel.compiled").inc()
    if interpret:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(plan.device.fast_memory_bytes))


def _window_specs(plan, buffers: int = 2) -> list:
    """BlockSpecs streaming one row window of the ringed grid per step.

    Step ``i`` gets its ``bm``-row main block (rows ``[i*bm, (i+1)*bm)``)
    plus the plan's tile-aligned halo blocks above and below it (``bm``
    is a multiple of their height, so they index as blocks of their
    own). The kernel concatenates them in order. A halo that would fall
    outside the grid is clamped onto its first or last block, and blocks
    may run past the last row: window rows outside the grid stand for
    cells beyond the ring, which the kernels pin or crop, so no kept cell
    ever reads them. One whole-grid block stands in for all of this when
    the plan has a single block. The grid operand stays in HBM; only
    these blocks enter VMEM.
    """
    h, w = plan.shape
    bm = plan.bm
    mode = {} if buffers == 2 else {"pipeline_mode": pl.Buffered(buffers)}
    if plan.nblocks == 1:
        return [pl.BlockSpec((h, w), lambda i: (0, 0), **mode)]
    top, bot = plan.halo_rows
    specs = []
    if top:
        specs.append(pl.BlockSpec(
            (top, w), lambda i: (jnp.maximum(i * (bm // top) - 1, 0), 0),
            **mode))
    specs.append(pl.BlockSpec((bm, w), lambda i: (i, 0), **mode))
    last = -(-h // bot) - 1
    specs.append(pl.BlockSpec(
        (bot, w), lambda i: (jnp.minimum((i + 1) * (bm // bot), last), 0),
        **mode))
    return specs


def _load_window(refs):
    """The streamed window as one f32 value (blocks are tile-aligned, so
    the concatenation is too)."""
    parts = [ref[...].astype(jnp.float32) for ref in refs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# shifted — materialized shifted copies, one HBM operand per tap (paper §IV)
# ---------------------------------------------------------------------------

def _shifted_kernel(*refs, weights):
    o_ref = refs[-1]
    acc = None
    for ref, wt in zip(refs[:-1], weights):
        term = ref[...].astype(jnp.float32) * jnp.float32(wt)
        acc = term if acc is None else acc + term
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "interpret", "device"))
def stencil_shifted(u: jax.Array, spec: StencilSpec, *, bm: int | None = None,
                    interpret: bool = False,
                    device: "str | DeviceModel | None" = None) -> jax.Array:
    """One sweep via one materialized shifted copy per tap (baseline)."""
    plan = plan_for(u.shape, u.dtype, spec, "shifted", bm=bm, device=device)
    r = plan.radius
    h, w = u.shape
    hi, wi = plan.interior_shape
    # One shifted interior view per tap. XLA materializes these as separate
    # HBM buffers feeding the kernel — deliberately reproducing the paper's
    # replicated-read traffic.
    views = [u[r + dy:h - r + dy, r + dx:w - r + dx]
             for (dy, dx) in spec.offsets]
    blk = pl.BlockSpec((plan.bm, wi), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_shifted_kernel, weights=spec.weights),
        grid=(plan.nblocks,),
        in_specs=[blk] * spec.taps,
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((hi, wi), u.dtype),
        compiler_params=_compiler_params(plan, interpret),
        interpret=interpret,
    )(*views)
    return u.at[_interior_index(u.shape, r)].set(out)


# ---------------------------------------------------------------------------
# rowchunk / dbuf — contiguous row-window load + in-VMEM tap views (§VI)
# ---------------------------------------------------------------------------

def _window_kernel(*refs, r: int, offsets, weights):
    *in_refs, o_ref = refs
    bm = o_ref.shape[0]  # derived from the block, not passed redundantly
    c = _load_window(in_refs)
    o_ref[...] = _tap_sum(c, bm, r, c.shape[1], offsets,
                          weights).astype(o_ref.dtype)


def _window_sweep(u, spec, policy: str, bm, interpret, device, buffers: int):
    plan = plan_for(u.shape, u.dtype, spec, policy, bm=bm, device=device)
    r = plan.radius
    hi, wi = plan.interior_shape
    in_specs = _window_specs(plan, buffers)
    out = pl.pallas_call(
        functools.partial(_window_kernel, r=r, offsets=spec.offsets,
                          weights=spec.weights),
        grid=(plan.nblocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((plan.bm, wi), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hi, wi), u.dtype),
        compiler_params=_compiler_params(plan, interpret),
        interpret=interpret,
    )(*[u] * len(in_specs))
    return u.at[_interior_index(u.shape, r)].set(out)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "interpret", "device"))
def stencil_rowchunk(u: jax.Array, spec: StencilSpec, *, bm: int | None = None,
                     interpret: bool = False,
                     device: "str | DeviceModel | None" = None) -> jax.Array:
    """One sweep via contiguous row-chunk loads + in-VMEM shifts.

    The window streams through a single buffer: each step loads, computes
    and stores in turn, the paper's §VI design before double buffering.
    """
    return _window_sweep(u, spec, "rowchunk", bm, interpret, device, 1)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "interpret", "device"))
def stencil_dbuf(u: jax.Array, spec: StencilSpec, *, bm: int | None = None,
                 interpret: bool = False,
                 device: "str | DeviceModel | None" = None) -> jax.Array:
    """One sweep with the row window double-buffered: the Pallas pipeline
    loads window ``i+1`` while window ``i`` computes (Table I's double
    buffering, done by the TPU's block pipeline)."""
    return _window_sweep(u, spec, "dbuf", bm, interpret, device, 2)


# ---------------------------------------------------------------------------
# temporal — T sweeps fused per HBM round-trip (beyond paper)
# ---------------------------------------------------------------------------

def _temporal_kernel(*refs, nin: int, bm: int, t: int, r: int, h: int,
                     top: int, offsets, weights, masked: bool):
    o_ref = refs[-1]
    c0 = _load_window(refs[:nin])
    win, w = c0.shape
    if masked:
        # Explicit pin mask (nonzero = Dirichlet): on a distributed shard
        # only the *global* ring is pinned — exchanged halo cells must
        # evolve with the fused sweeps or the fusion is fake.
        fixed = _load_window(refs[nin:2 * nin]) != 0
    else:
        # Mask pinning global Dirichlet cells: the r-deep ring of the grid
        # (and any window rows that stand for cells above or below it).
        ws = pl.program_id(0) * bm - top  # grid row of window row 0
        grow = ws + jax.lax.broadcasted_iota(jnp.int32, (win, w), 0)
        gcol = jax.lax.broadcasted_iota(jnp.int32, (win, w), 1)
        fixed = (grow < r) | (grow >= h - r) | (gcol < r) | (gcol >= w - r)

    def sweep(_, c):
        acc = None
        for (dy, dx), wt in zip(offsets, weights):
            # value at p + (dy, dx): roll by the negated offset
            term = c
            if dy:
                term = pltpu.roll(term, (-dy) % win, 0)
            if dx:
                term = pltpu.roll(term, (-dx) % w, 1)
            term = term * jnp.float32(wt)
            acc = term if acc is None else acc + term
        # Dirichlet cells keep their original value; roll wrap garbage only
        # ever lands in the t*r-deep halo that is discarded below.
        return jnp.where(fixed, c0, acc)

    c = jax.lax.fori_loop(0, t, sweep, c0)
    # The bm interior rows of this block sit r below the main block's
    # first row; they are exact after t sweeps.
    o_ref[...] = c[top + r:top + r + bm, r:w - r].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("spec", "t", "bm", "interpret", "device"))
def stencil_temporal(u: jax.Array, spec: StencilSpec, *, t: int | None = None,
                     bm: int | None = None, interpret: bool = False,
                     device: "str | DeviceModel | None" = None,
                     mask: jax.Array | None = None) -> jax.Array:
    """Advance the grid by exactly ``t`` sweeps in one HBM round-trip.

    ``mask`` (optional, same shape as ``u``, nonzero = pinned) overrides
    the default Dirichlet set: without it the grid's own radius-``r`` ring
    is re-pinned between sweeps; with it only the masked cells are. This
    is what lets a distributed shard run *true* fused sweeps — its block
    edge is mostly exchanged halo that must evolve, and only the slice of
    the global ring it owns stays fixed. Unmasked cells within ``t·r`` of
    an unpinned edge come back stale/garbage (their dependency cone left
    the block); callers crop them, exactly as they crop exchanged halo.
    """
    masked = mask is not None
    plan = plan_for(u.shape, u.dtype, spec, "temporal", bm=bm, t=t,
                    device=device, masked=masked)
    r = plan.radius
    h, w = u.shape
    hi, wi = plan.interior_shape
    specs = _window_specs(plan)
    operands = [u] * len(specs)
    if masked:
        # The mask rides the same block pipeline as the grid, cast to the
        # grid dtype so 0/1 survive any registry dtype exactly.
        operands += [mask.astype(u.dtype)] * len(specs)
    out = pl.pallas_call(
        functools.partial(_temporal_kernel, nin=len(specs), bm=plan.bm,
                          t=plan.t, r=r, h=h, top=plan.halo_rows[0],
                          offsets=spec.offsets, weights=spec.weights,
                          masked=masked),
        grid=(plan.nblocks,),
        in_specs=specs * (2 if masked else 1),
        out_specs=pl.BlockSpec((plan.bm, wi), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hi, wi), u.dtype),
        compiler_params=_compiler_params(plan, interpret),
        interpret=interpret,
    )(*operands)
    return u.at[_interior_index(u.shape, r)].set(out)
