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
      dwarfs the stencil's arithmetic intensity. The sweeps run in two
      f32 VMEM scratch copies of the window, ping-ponged: each sweep
      reads one and writes the other strip by strip, one column parity
      at a time, so no loop carries the window as a value (which
      the compiler would spill every sweep). In the scratch the rows
      are interleaved, so a row's vertical neighbours are whole strips
      away, and the columns split by parity, so its horizontal
      neighbours are in the other parity's tiles: one lane rotation
      serves a pair of horizontal taps. A window small enough for the
      register file (``plan.WHOLE_WINDOW_VREGS``; a narrow rind strip of
      a distributed shard) sweeps as one value instead.

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

Names on the device trace: every kernel's ``pallas_call`` is named
:data:`KERNEL_PREFIX` + its policy (``stencil_shifted``,
``stencil_rowchunk``, ``stencil_dbuf``, ``stencil_temporal``), which XLA
keeps as the device operation's name whatever Python function wraps the
call; the scatter of the kernel's interior back into the ringed grid runs
under the ``jax.named_scope`` ``stencil.writeback``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import StencilSpec
from repro.engine.device import DeviceModel  # noqa: F401  (annotations)
from repro.engine.plan import _round_up, plan_for, sublane_tile
from repro.obs import metrics as _metrics

#: Prefix of every kernel's name on the device trace.
KERNEL_PREFIX = "stencil_"


def _tap_sum(c, bm: int, r: int, w: int, offsets, weights):
    """Weighted sum of in-VMEM shifted views of a resident (bm+2r, w) window."""
    acc = None
    for (dy, dx), wt in zip(offsets, weights):
        # tap view: rows [r+dy, r+dy+bm), cols [r+dx, w-r+dx)
        tap = jax.lax.slice(c, (r + dy, r + dx), (r + dy + bm, w - r + dx))
        term = tap * jnp.float32(wt)
        acc = term if acc is None else acc + term
    return acc


def _writeback(u, out, r: int):
    """The kernel's interior written back into the ringed grid."""
    with jax.named_scope("stencil.writeback"):
        return u.at[tuple(slice(r, s - r) for s in u.shape)].set(out)


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
    ever reads them. One whole-grid block (``plan.kernel_rows`` high)
    stands in for all of this when the plan has a single block. The grid
    operand stays in HBM; only these blocks enter VMEM.
    """
    h, w = plan.shape
    bm = plan.bm
    mode = {} if buffers == 2 else {"pipeline_mode": pl.Buffered(buffers)}
    if plan.nblocks == 1:
        return [pl.BlockSpec((plan.kernel_rows, w), lambda i: (0, 0),
                             **mode)]
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
        name=KERNEL_PREFIX + "shifted",
    )(*views)
    return _writeback(u, out, r)


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
        name=KERNEL_PREFIX + policy,
    )(*[u] * len(in_specs))
    return _writeback(u, out, r)


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

_LANE = 128         # lanes in one vreg


def _lane_shift(x, dx: int, lane):
    """``x`` (tiles, rows, 128), a run of lane tiles in column order, read
    ``dx`` lanes to the right (``|dx| <= 128``). Each tile is rotated once
    and the wrapped lanes are taken from its neighbour tile; the run's end
    tiles wrap onto themselves (halo garbage, pinned or cropped)."""
    if not dx:
        return x
    y = pltpu.roll(x, (-dx) % _LANE, 2)
    n = x.shape[0]
    if dx > 0:
        nxt = jnp.concatenate([y[1:], y[n - 1:]], axis=0) if n > 1 else y
        return jnp.where(lane < _LANE - dx, y, nxt)
    prv = jnp.concatenate([y[:1], y[:n - 1]], axis=0) if n > 1 else y
    return jnp.where(lane >= -dx, y, prv)


def _set_tile(x, k: int, v):
    """``x`` (tiles, rows, 128) with tile ``k`` replaced by ``v``."""
    parts = [x[:k]] if k else []
    parts.append(v[None])
    if k + 1 < x.shape[0]:
        parts.append(x[k + 1:])
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _gather_lanes(x, idx):
    """``x`` (rows, 128) with lane ``i`` taken from lane ``idx[i]``."""
    return jnp.take_along_axis(x, idx, axis=1)


def _parity_lanes(shape):
    """The lane masks and gather indices of :func:`_unzip` and
    :func:`_zip` for (rows, 128) values, built once per kernel, outside
    the split and merge loops."""
    half = _LANE // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    low, even, k = lane < half, (lane & 1) == 0, lane >> 1
    return dict(
        low=low, even=even, swap=lane ^ 1,
        # a: even lanes to the low half, odd to the high; b: the other way
        ua=jnp.where(low, 2 * lane, 2 * lane - (_LANE - 1)),
        ub=jnp.where(low, 2 * lane + 1, 2 * lane - _LANE),
        # lane 2i takes e_i (o_{half+i}), lane 2i+1 takes e_{half+i} (o_i)
        ze=jnp.where(even, k, half + k), zo=jnp.where(even, half + k, k))


def _unzip(a, b, ix):
    """``a`` and ``b`` (rows, 128), whose rows hold 128 consecutive columns
    and the next 128 -> the rows' even columns and their odd columns
    (``ix``: the :func:`_parity_lanes` of their shape)."""
    pa, pb, low = _gather_lanes(a, ix["ua"]), _gather_lanes(b, ix["ub"]), \
        ix["low"]
    return (jnp.where(low, pa, pb),
            pltpu.roll(jnp.where(low, pb, pa), _LANE // 2, 1))


def _zip(e, o, ix):
    """Inverse of :func:`_unzip`: even and odd columns -> the first 128
    and the next 128 consecutive columns."""
    ge, go, even = _gather_lanes(e, ix["ze"]), _gather_lanes(o, ix["zo"]), \
        ix["even"]
    # the high tile's pairs come out swapped: swap them back
    return (jnp.where(even, ge, go),
            _gather_lanes(jnp.where(even, go, ge), ix["swap"]))


def _temporal_window_kernel(*refs, nin: int, bm: int, t: int, r: int,
                            h: int, top: int, offsets, weights, masked: bool):
    """``t`` sweeps of a small block window carried as one value: at most
    ``plan.WHOLE_WINDOW_VREGS`` vregs, where the strip form's fixed costs
    would outweigh the work."""
    o_ref = refs[-1]
    c0 = _load_window(refs[:nin])
    win, w = c0.shape
    if masked:
        # Explicit pin mask (nonzero = Dirichlet): on a distributed shard
        # only the *global* ring is pinned; exchanged halo cells evolve
        # with the fused sweeps.
        fixed = _load_window(refs[nin:2 * nin]) != 0
    else:
        # The r-deep ring of the grid, and any window rows that stand for
        # cells above or below it.
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


def _temporal_kernel(*refs, nin: int, bm: int, t: int, r: int, h: int,
                     w: int, top: int, offsets, weights, masked: bool):
    """``t`` sweeps of one block's window, strip by strip in VMEM.

    The window lives in two f32 scratch buffers shaped (lane tiles, rows,
    128) that the sweeps ping-pong between (a third holds the pin mask).
    Their rows are interleaved: the window is cut into ``ts`` bands of
    ``nm`` consecutive rows (``ts`` is the f32 sublane tile), and strip
    ``m`` holds row ``m`` of every band, one band per sublane. A row's
    neighbours ``dy`` away then sit in strip ``m + dy``, read with an
    aligned load; only the first and last strips take theirs from the
    next band over, with one sublane roll. Their columns are split by
    parity: the first ``nh`` tiles hold the even columns, the last ``nh``
    the odd ones, so a column's neighbours one away are in the other
    parity's tile at the same lane or, for one side, the next lane over:
    one lane rotation a tap pair where column order would take two.

    A sweep reads each strip's taps, one column parity at a time, and
    stores the pinned sum once. The strips are unrolled and no loop
    carries more than its index, so nothing the size of the window is
    spilled. The split into parities and its inverse move every tile pair
    of a strip at once (tile-strided loads and stores).
    """
    in_refs = refs[:nin]
    mask_refs = refs[nin:2 * nin] if masked else ()
    o_ref, buf_a, buf_b, *buf_m = refs[len(in_refs) + len(mask_refs):]
    nt, win, _ = buf_a.shape
    nh = nt // 2                          # tiles of one column parity
    ts = sublane_tile(buf_a.dtype)        # bands: rows per strip
    nm = win // ts                        # strips
    f32 = jnp.float32
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANE), 2)
    ix = _parity_lanes((nh * ts, _LANE))

    def flat(x):
        return x.reshape(nh * ts, _LANE)

    def strip_rows(m):
        return pl.ds(pl.multiple_of(m * ts, ts), ts)

    def interleave(refs_, dst):
        # Window rows in column order into buf_b, then each band's m-th
        # row (a sublane-strided load) into strip m of dst, by parity.
        row = 0
        for ref in refs_:
            rows = ref.shape[0]
            for q in range(-(-w // _LANE)):
                a, b = q * _LANE, min((q + 1) * _LANE, w)
                buf_b[q, row:row + rows, :b - a] = ref[:, a:b].astype(f32)
            row += rows

        def gather(m, carry):
            # every tile pair at once, as (tiles * rows, 128)
            rows = pl.ds(m, ts, stride=nm)
            e, o = _unzip(flat(buf_b[pl.ds(0, nh, stride=2), rows, :]),
                          flat(buf_b[pl.ds(1, nh, stride=2), rows, :]), ix)
            dst[:nh, strip_rows(m), :] = e.reshape(nh, ts, _LANE)
            dst[nh:, strip_rows(m), :] = o.reshape(nh, ts, _LANE)
            return carry
        jax.lax.fori_loop(0, nm, gather, 0)

    if masked:
        # Explicit pin mask (nonzero = Dirichlet): on a distributed shard
        # only the *global* ring is pinned; exchanged halo cells evolve
        # with the fused sweeps.
        interleave(mask_refs, buf_m[0])
    interleave(in_refs, buf_a)

    band = jax.lax.broadcasted_iota(jnp.int32, (1, ts, 1), 1)
    ws = pl.program_id(0) * bm - top  # grid row of window row 0

    def strip(src, dst, m: int):
        """Strip ``m`` of one sweep. Every index is static, so the
        compiler can schedule loads of one strip's rows for the next."""
        def rows_at(dy, qa, qb):
            # Tiles [qa, qb) of the strip whose sublane j holds the row dy
            # below this strip's: strip m+dy, or the next band's.
            k, p = divmod(m + dy, nm)
            x = src[qa:qb, p * ts:(p + 1) * ts, :]
            return pltpu.roll(x, (-k) % ts, 1) if k else x

        if not masked:
            # The r-deep ring of the grid, and any window rows that stand
            # for cells above or below it.
            grow = ws + band * nm + m
            row_fixed = (grow < r) | (grow >= h - r)
        for par in (0, 1):
            acc = c = None
            for (dy, dx), wt in zip(offsets, weights):
                # value at p + (dy, dx), weighted in the spec's order:
                # column parity sp, sh lanes over
                sp, sh = (par + dx) % 2, (par + dx) // 2
                x = _lane_shift(rows_at(dy, sp * nh, (sp + 1) * nh), sh,
                                lane)
                if (dy, dx) == (0, 0):
                    c = x
                term = x * f32(wt)
                acc = term if acc is None else acc + term
            qa, qb = par * nh, (par + 1) * nh
            if c is None:
                c = rows_at(0, qa, qb)
            if masked:
                fixed = buf_m[0][qa:qb, m * ts:(m + 1) * ts, :] != 0
            else:
                fixed = jnp.broadcast_to(row_fixed, acc.shape)
                for k in range(nh):
                    # the ring's columns: only in the end tiles
                    lo = 2 * k * _LANE + par
                    if lo < r or lo + 2 * (_LANE - 1) >= w - r:
                        col = lo + 2 * lane[0]
                        fixed = _set_tile(fixed, k, row_fixed[0]
                                          | (col < r) | (col >= w - r))
            # Pinned cells never change, so the centre is their original
            # value.
            dst[qa:qb, m * ts:(m + 1) * ts, :] = jnp.where(fixed, c, acc)

    def sweep(src, dst):
        for m in range(nm):
            strip(src, dst, m)

    def two_sweeps(_, carry):
        sweep(buf_a, buf_b)
        sweep(buf_b, buf_a)
        return carry

    jax.lax.fori_loop(0, t // 2, two_sweeps, 0)
    if t % 2:
        sweep(buf_a, buf_b)
    out, lin = (buf_b, buf_a) if t % 2 else (buf_a, buf_b)

    def scatter(m, carry):
        # Back to window rows and columns in order.
        rows = pl.ds(m, ts, stride=nm)
        lo, hi = _zip(flat(out[:nh, strip_rows(m), :]),
                      flat(out[nh:, strip_rows(m), :]), ix)
        lin[pl.ds(0, nh, stride=2), rows, :] = lo.reshape(nh, ts, _LANE)
        lin[pl.ds(1, nh, stride=2), rows, :] = hi.reshape(nh, ts, _LANE)
        return carry
    jax.lax.fori_loop(0, nm, scatter, 0)
    # The bm interior rows of this block sit r below the main block's
    # first row, and its columns r to the right of the window's; they are
    # exact after t sweeps.
    rows = _round_up(r + bm, ts)
    wi = o_ref.shape[1]
    nq = -(-wi // _LANE)
    y = _lane_shift(lin[:min(nq + 1, nt), top:top + rows, :], r, lane)
    for q in range(nq):
        a, b = q * _LANE, min((q + 1) * _LANE, wi)
        o_ref[:, a:b] = y[q, r:r + bm, :b - a].astype(o_ref.dtype)


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
    kw = dict(nin=len(specs), bm=plan.bm, t=plan.t, r=r, h=h,
              top=plan.halo_rows[0], offsets=spec.offsets,
              weights=spec.weights, masked=masked)
    if plan.strip_rows:
        kernel = functools.partial(_temporal_kernel, w=w, **kw)
        buf = pltpu.VMEM((2 * -(-w // (2 * _LANE)), plan.kernel_rows,
                          _LANE), jnp.float32)
        scratch = [buf] * (3 if masked else 2)
    else:
        kernel, scratch = functools.partial(_temporal_window_kernel, **kw), []
    out = pl.pallas_call(
        kernel,
        grid=(plan.nblocks,),
        in_specs=specs * (2 if masked else 1),
        out_specs=pl.BlockSpec((plan.bm, wi), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hi, wi), u.dtype),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(plan, interpret),
        interpret=interpret,
        name=KERNEL_PREFIX + "temporal",
    )(*operands)
    return _writeback(u, out, r)
