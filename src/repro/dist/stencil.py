"""Mesh-aware stencil decomposition: depth-``t`` halo exchange around *any*
local sweep function.

This generalizes :mod:`repro.core.halo` (which hard-codes the 5-point Jacobi
update) so the whole engine registry can run per shard: the local computation
is an arbitrary ``sweep(ext) -> ext`` callable obeying the engine's ringed
contract — update every cell at distance >= ``r`` from the block edge, copy
the outer radius-``r`` ring through. ``repro.engine.run_distributed`` plugs
engine policies (or the pure-jnp reference) in here.

Scheme per exchange, for ``t`` sweeps of a radius-``r`` spec:

* exchange depth-``d`` halos (``d = t*r``) with ``ppermute`` neighbours —
  rows first, then columns of the row-extended block so shard-corner halos
  ride along (needed once ``d > r``);
* on physical domain edges substitute the Dirichlet bands, replicated
  outward across the halo band (cells beyond the first ``r`` ring are pinned
  and never influence the valid region);
* advance the extended block ``t`` sweeps via a *block callable*
  ``block(ext, fixed, t)`` — either :func:`masked_block` (any single-sweep
  policy looped with Dirichlet re-pinning between sweeps) or a fused
  kernel that takes the pin mask itself (``engine.stencil_temporal`` with
  ``mask=``: all ``t`` sweeps in one fast-memory round-trip, the real
  communication-avoiding payoff);
* crop the exact central block.

In **overlap** mode the block launch splits in two: the shard's interior
(independent of any incoming halo) launches on the raw shard *before* the
``ppermute``s — no data dependence, so XLA's latency-hiding scheduler
computes it while the ``t*r``-deep exchange is in flight — and four rind
strips of width ``3*t*r`` launch on the arrived extended block, stitched
around the interior. The result is bit-identical to the serial round (the
kept cells' dependency cones and tap order are the same); what changes is
the wall-clock bill, ``max(exchange, interior) + rind`` instead of
``exchange + full block`` (:func:`repro.engine.schedule.price_exchange`).

One exchange per ``t`` sweeps is the communication-avoiding schedule the
paper's PCIe-isolated Grayskull cards could not run (§VII); over a real mesh
the halos travel on ICI/DCI and the answer is exact. How many exchanges a
full run costs comes from the shared :class:`~repro.engine.schedule.
SweepSchedule` — the same object ``engine.run`` executes — so the two
executors cannot drift.

Corners: shard-corner halos are transported by the two-phase exchange, and
the four ``r x r`` *physical* ring corners (which band decomposition drops)
travel as tiny replicated operands and are substituted on the corner shards
— so diagonal-tap specs are exact too, matching the single-device ring.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.decomp import check_divisible, split_ringed_bands
from repro.core.halo import exchange_cols, exchange_rows
from repro.core.stencil import StencilSpec
from repro.dist._compat import shard_map
from repro.engine.dispatch import cached_launch
from repro.engine.schedule import overlap_feasible


def _pad_outward(band: jax.Array, d: int, axis: int, leading: bool):
    """Grow a thickness-``r`` Dirichlet band to thickness ``d`` by
    replicating its outermost row/col on the outward (``leading``) side."""
    r = band.shape[axis]
    if d == r:
        return band
    outer = jax.lax.slice_in_dim(band, 0, 1, axis=axis) if leading else \
        jax.lax.slice_in_dim(band, r - 1, r, axis=axis)
    reps = [1, 1]
    reps[axis] = d - r
    pad = jnp.tile(outer, reps)
    parts = [pad, band] if leading else [band, pad]
    return jnp.concatenate(parts, axis=axis)


def masked_block(sweep: Callable) -> Callable:
    """Lift a single-sweep callable into the block contract.

    ``block(ext, fixed, t)`` advances the extended block ``t`` sweeps,
    re-pinning the ``fixed`` (global-Dirichlet) cells to their pre-sweep
    values between sweeps — one kernel launch per sweep, fast memory
    round-tripped every time. Fused policies skip this wrapper and take
    the mask directly, which is the whole point of temporal blocking.
    """
    def block(ext, fixed, t: int):
        orig = ext
        for _ in range(t):
            ext = jnp.where(fixed, orig, sweep(ext))
        return ext
    return block


def _shard_index(row_axis: str, col_axis: str, px: int, py: int):
    """This shard's (row, col) coordinate in the decomposition (0 on an
    unsplit axis)."""
    ix = jax.lax.axis_index(row_axis) if px > 1 else 0
    iy = jax.lax.axis_index(col_axis) if py > 1 else 0
    return ix, iy


def _assemble_ext(u, top, bottom, left, right, tl, tr, bl, br, *,
                  row_axis: str, col_axis: str, px: int, py: int,
                  r: int, d: int):
    """The exchange phase: build the depth-``d`` extended local block.

    Two-phase ``ppermute`` (rows first, then columns of the row-extended
    block so shard-corner halos ride along), Dirichlet bands substituted
    on physical domain edges, and the four ``r x r`` physical ring
    corners patched onto the corner shards. Pure function of the local
    shard + bands, shared by the serial and overlap rounds of
    :func:`_local_sweeps`.
    """
    hl, wl = u.shape
    ix, iy = _shard_index(row_axis, col_axis, px, py)

    # Phase 1 — row halos; Dirichlet bands on physical top/bottom edges.
    # The left/right Dirichlet bands span the halo rows too (their values
    # live on the row neighbours), so they ride the SAME ppermute pair as
    # the grid: one packed ``[left | grid | right]`` row exchange instead
    # of three separate ones (6 collectives per round down to 2). Slicing
    # the packed halos back apart commutes with the permute, so the
    # result is bit-identical to exchanging the three operands alone.
    lb, rb = left.astype(u.dtype), right.astype(u.dtype)  # (hl, r)
    packed = jnp.concatenate([lb, u, rb], axis=1)         # (hl, wl+2r)
    ph, pd = exchange_rows(packed, row_axis, px, d)       # (d, wl+2r)
    uh, dh = ph[:, r:r + wl], pd[:, r:r + wl]
    top_b = _pad_outward(top.astype(u.dtype), d, axis=0, leading=True)
    bot_b = _pad_outward(bottom.astype(u.dtype), d, axis=0, leading=False)
    uh = jnp.where(ix == 0, top_b, uh)
    dh = jnp.where(ix == px - 1, bot_b, dh)
    ext_r = jnp.concatenate([uh, u, dh], axis=0)          # (hl+2d, wl)

    left_ext = jnp.concatenate([ph[:, :r], lb, pd[:, :r]], axis=0)
    right_ext = jnp.concatenate([ph[:, r + wl:], rb, pd[:, r + wl:]],
                                axis=0)                   # (hl+2d, r)

    # Phase 2 — column halos of the row-extended block (corner transport).
    lh, rh = exchange_cols(ext_r, col_axis, py, d)        # (hl+2d, d)
    lef = _pad_outward(left_ext, d, axis=1, leading=True)
    rig = _pad_outward(right_ext, d, axis=1, leading=False)
    lh = jnp.where(iy == 0, lef, lh)
    rh = jnp.where(iy == py - 1, rig, rh)
    ext = jnp.concatenate([lh, ext_r, rh], axis=1)        # (hl+2d, wl+2d)

    # Physical ring corners (read by diagonal taps; the bands drop them):
    # substitute the true r x r corner blocks on the four corner shards.
    rows_top, rows_bot = slice(d - r, d), slice(hl + d, hl + d + r)
    cols_lef, cols_rig = slice(d - r, d), slice(wl + d, wl + d + r)
    for cond, corner, rs, cs in (
        ((ix == 0) & (iy == 0), tl, rows_top, cols_lef),
        ((ix == 0) & (iy == py - 1), tr, rows_top, cols_rig),
        ((ix == px - 1) & (iy == 0), bl, rows_bot, cols_lef),
        ((ix == px - 1) & (iy == py - 1), br, rows_bot, cols_rig),
    ):
        ext = jnp.where(cond, ext.at[rs, cs].set(corner.astype(u.dtype)), ext)
    return ext


def _pin_mask(hl: int, wl: int, d: int, ix, iy, px: int, py: int):
    """The pin mask on the extended block: physical Dirichlet bands stay
    fixed across all ``t`` sweeps; every other edge cell is exchanged halo
    that must evolve (its staleness grows ``r`` per sweep and is cropped
    by the caller)."""
    rr = jnp.arange(hl + 2 * d)[:, None]
    cc = jnp.arange(wl + 2 * d)[None, :]
    return (((ix == 0) & (rr < d)) | ((ix == px - 1) & (rr >= hl + d))
            | ((iy == 0) & (cc < d)) | ((iy == py - 1) & (cc >= wl + d)))


def _interior_keep(u, block: Callable, t: int, d: int):
    """The interior phase: advance the raw (un-haloed) shard ``t`` sweeps
    and keep the cells >= ``d`` from the shard edge — exact without any
    halo data (the near-edge cells are covered by the rind strips)."""
    hl, wl = u.shape
    inner = block(u, jnp.zeros(u.shape, bool), t)
    return inner[d:hl - d, d:wl - d]


def _rind_stitch(ext, fixed, inner_keep, *, block: Callable, t: int, d: int):
    """The rind phase: four strip launches on the arrived extended block,
    stitched around the interior result.

    Each strip is wide enough (``3d``) that its kept cells sit >= ``d``
    from every strip edge that is not ``ext``'s own (pinned or
    cropped-anyway) boundary. Top/bottom strips span the full width and
    keep the first/last ``d`` interior rows; left/right strips fill the
    remaining ``hl - 2d`` rows and keep the first/last ``d`` interior
    columns.
    """
    hl, wl = ext.shape[0] - 2 * d, ext.shape[1] - 2 * d
    strips = (
        (slice(0, 3 * d), slice(None)),                    # top
        (slice(hl - d, hl + 2 * d), slice(None)),          # bottom
        (slice(d, hl + d), slice(0, 3 * d)),               # left
        (slice(d, hl + d), slice(wl - d, wl + 2 * d)),     # right
    )
    outs = [block(ext[rs, cs], fixed[rs, cs], t) for rs, cs in strips]
    top_k = outs[0][d:2 * d, d:wl + d]
    bot_k = outs[1][d:2 * d, d:wl + d]
    lef_k = outs[2][d:hl - d, d:2 * d]
    rig_k = outs[3][d:hl - d, d:2 * d]
    mid = jnp.concatenate([lef_k, inner_keep, rig_k], axis=1)
    return jnp.concatenate([top_k, mid, bot_k], axis=0)


def _local_sweeps(u, top, bottom, left, right, tl, tr, bl, br, *,
                  block: Callable, row_axis: str, col_axis: str,
                  px: int, py: int, r: int, t: int,
                  overlap: bool = False):
    """Advance the local shard by ``t`` sweeps with one depth-``t*r``
    exchange. Bands are local slices of the global Dirichlet bands;
    ``tl``/``tr``/``bl``/``br`` are the replicated ``r x r`` ring corners.

    With ``overlap``, the shard splits into an **interior** launch on the
    raw (un-haloed) shard — no data dependence on the ppermutes, so XLA's
    latency-hiding scheduler computes it while the exchange is in flight —
    and four **rind** strip launches on the arrived extended block. After
    ``t`` sweeps of radius ``r``, every cell at distance >= ``d = t*r``
    from a strip edge has the same dependency cone (and the same f32 tap
    accumulation order) as in the one-block launch, so the stitched
    result is bit-identical to the serial path; cells nearer an edge are
    stale in *both* formulations and are exactly the ones cropped/covered.
    A shard too small for a nonempty interior (``hl <= 2d`` or
    ``wl <= 2d``) silently runs the serial round — same numbers, nothing
    left to hide the exchange behind.

    The phases run under ``jax.named_scope`` names that reach every device
    operation's ``op_name``: ``exchange.halo`` (the ``ppermute`` calls and
    the extended block's assembly), ``exchange.interior`` and
    ``exchange.rind`` (the overlap split).
    """
    hl, wl = u.shape
    d = t * r
    if d > min(hl, wl):
        raise ValueError(
            f"halo depth {d} (t={t} sweeps x radius {r}) exceeds local "
            f"block {u.shape}; lower t or use more rows/cols per shard")
    overlap = overlap and overlap_feasible(hl, wl, d)
    if overlap:
        # Interior launch, issued before the exchange: after t sweeps the
        # cells >= d from the shard edge are exact (the near-edge cells
        # would need halo data and are covered by the rind strips below).
        with jax.named_scope("exchange.interior"):
            inner_keep = _interior_keep(u, block, t, d)
    with jax.named_scope("exchange.halo"):
        ext = _assemble_ext(u, top, bottom, left, right, tl, tr, bl, br,
                            row_axis=row_axis, col_axis=col_axis, px=px,
                            py=py, r=r, d=d)
        ix, iy = _shard_index(row_axis, col_axis, px, py)
        fixed = _pin_mask(hl, wl, d, ix, iy, px, py)
    if overlap:
        with jax.named_scope("exchange.rind"):
            return _rind_stitch(ext, fixed, inner_keep, block=block, t=t,
                                d=d)
    ext = block(ext, fixed, t)
    return ext[d:-d, d:-d]


def make_sharded_step(mesh, spec: StencilSpec, block: Callable, *,
                      row_axis: str | None, col_axis: str | None,
                      t: int = 1, overlap: bool = False) -> Callable:
    """Build ``step(interior, bc) -> interior'`` advancing ``t`` sweeps of
    ``spec`` with one halo exchange, sharded over ``mesh``.

    ``block(ext, fixed, t)`` is the local computation on the extended
    (haloed) shard — wrap a plain single-sweep callable with
    :func:`masked_block`. ``overlap`` runs the interior/rind split so the
    halo-independent compute hides the exchange (bit-identical result;
    see :func:`_local_sweeps`).
    """
    px = mesh.shape[row_axis] if row_axis else 1
    py = mesh.shape[col_axis] if col_axis else 1
    row_axis = row_axis or "_row_unused"
    col_axis = col_axis or "_col_unused"

    fn = functools.partial(
        _local_sweeps, block=block, row_axis=row_axis, col_axis=col_axis,
        px=px, py=py, r=spec.radius, t=t, overlap=overlap)

    row = row_axis if px > 1 else None
    col = col_axis if py > 1 else None
    grid_spec = P(row, col)
    sharded = shard_map(
        fn, mesh=mesh,
        in_specs=(grid_spec, P(None, col), P(None, col),
                  P(row, None), P(row, None)) + (P(None, None),) * 4,
        out_specs=grid_spec,
        check_vma=False,
    )

    def step(interior: jax.Array, bc: Dict[str, jax.Array]) -> jax.Array:
        r = spec.radius
        zc = jnp.zeros((r, r), interior.dtype)
        corners = [bc.get(k, zc) for k in ("tl", "tr", "bl", "br")]
        return sharded(interior, bc["top"], bc["bottom"], bc["left"],
                       bc["right"], *corners)

    return step


def _execute_rounds(u, spec: StencilSpec, mesh, block: Callable, *,
                    schedule, row_axis, col_axis, remainder_block):
    """The executor body: band split, ``lax.scan`` over fused exchange
    rounds, remainder round, ring re-attach. Shared verbatim by the
    inline (traced) call and the cached jitted single launch, so the two
    are the same program by construction."""
    r = spec.radius
    with jax.named_scope("exchange.halo"):
        interior, bc = split_ringed_bands(u, r)
        bc = dict(bc, tl=u[:r, :r], tr=u[:r, -r:], bl=u[-r:, :r],
                  br=u[-r:, -r:])
    if schedule.fused_blocks:
        step = make_sharded_step(mesh, spec, block, row_axis=row_axis,
                                 col_axis=col_axis, t=schedule.t,
                                 overlap=schedule.overlap)

        def body(v, _):
            return step(v, bc), None

        interior, _ = jax.lax.scan(body, interior, None,
                                   length=schedule.fused_blocks)
    if schedule.remainder:
        step_rem = make_sharded_step(
            mesh, spec,
            remainder_block if remainder_block is not None else block,
            row_axis=row_axis, col_axis=col_axis, t=schedule.remainder,
            overlap=schedule.overlap)
        interior = step_rem(interior, bc)
    return _reattach_ring(u, interior, mesh, r)


def _reattach_ring(u, interior, mesh, r: int):
    """The full grid: ``u``'s ring around the mesh-sharded interior.

    The result is replicated over ``mesh``; naming that output sharding
    is what lets the scatter take a sharded update into an unsharded
    grid under explicit mesh axes.
    """
    with jax.named_scope("stencil.writeback"):
        return u.at[r:-r, r:-r].set(
            interior, out_sharding=NamedSharding(mesh, P()))


# Cached jitted single launches, keyed by everything that shaped the
# program (the caller's ``cache_key`` must pin whatever produced
# ``block``). Bounded in practice by the handful of (mesh, schedule)
# combinations a process runs.
_SCAN_LAUNCHES: dict = {}


def run_sharded_cache_clear() -> None:
    _SCAN_LAUNCHES.clear()


def resolve_axes(mesh, row_axis: str | None, col_axis: str | None):
    """Default decomposition axes: the mesh's first (rows) and second
    (columns, if any) axis names."""
    if row_axis is None and col_axis is None:
        names = tuple(mesh.axis_names)
        row_axis = names[0]
        col_axis = names[1] if len(names) > 1 else None
    return row_axis, col_axis


def extended_shard_shape(shape, mesh, spec: StencilSpec, *, t: int = 1,
                         row_axis: str | None = None,
                         col_axis: str | None = None) -> tuple[int, int]:
    """Static local block a sweep sees: shard interior + depth-``t*r`` halo.

    This is the shape per-shard execution plans must be validated against —
    a policy whose window fits the *global* grid's plan can still overflow
    a device's fast memory once the exchanged halo band is attached, and
    vice versa. Single source for ``engine.run_distributed`` and any
    caller that wants to pre-flight a distributed plan.
    """
    row_axis, col_axis = resolve_axes(mesh, row_axis, col_axis)
    r = spec.radius
    px = mesh.shape[row_axis] if row_axis else 1
    py = mesh.shape[col_axis] if col_axis else 1
    d = 2 * t * r
    return ((shape[0] - 2 * r) // px + d, (shape[1] - 2 * r) // py + d)


def run_sharded(u: jax.Array, spec: StencilSpec, mesh, block: Callable, *,
                schedule, row_axis: str | None = None,
                col_axis: str | None = None,
                remainder_block: Callable | None = None,
                cache_key=None, donate: bool = False) -> jax.Array:
    """Execute a :class:`~repro.engine.schedule.SweepSchedule` over ``mesh``.

    ``schedule.fused_blocks`` exchanges of depth ``schedule.halo_depth``
    each precede ``schedule.t`` local sweeps via ``block(ext, fixed, t)``;
    a non-empty remainder runs one more (shallower) exchange through
    ``remainder_block`` (default: ``block`` again). Same contract as
    ``engine.run``: returns the full grid, boundary ring copied through.
    The iters/t/remainder arithmetic lives in the schedule — this function
    only spends exchanges; ``schedule.overlap`` selects the interior/rind
    split that hides each exchange behind the halo-independent compute.

    Called untraced with a hashable ``cache_key`` (anything that pins how
    ``block``/``remainder_block`` were built — ``run_distributed`` passes
    its policy/bm/interpret/device tuple), the whole body — band split,
    every exchange round, remainder, ring re-attach — runs as ONE cached
    jitted launch instead of one Python dispatch per round; ``donate``
    additionally donates ``u``'s buffer to the launch (the caller's array
    is invalid afterwards). Without a key, rounds dispatch eagerly as
    before.
    """
    row_axis, col_axis = resolve_axes(mesh, row_axis, col_axis)
    r = spec.radius
    hi, wi = u.shape[0] - 2 * r, u.shape[1] - 2 * r
    px = mesh.shape[row_axis] if row_axis else 1
    py = mesh.shape[col_axis] if col_axis else 1
    check_divisible(hi, wi, px, py)

    if cache_key is not None and not isinstance(u, jax.core.Tracer):
        key = ("run_sharded", cache_key, mesh, spec, schedule, row_axis,
               col_axis, tuple(u.shape), str(u.dtype), bool(donate))
        return cached_launch(_SCAN_LAUNCHES, key, lambda: jax.jit(
            functools.partial(
                _execute_rounds, spec=spec, mesh=mesh, block=block,
                schedule=schedule, row_axis=row_axis, col_axis=col_axis,
                remainder_block=remainder_block),
            donate_argnums=(0,) if donate else ()), u)

    return _execute_rounds(u, spec, mesh, block, schedule=schedule,
                           row_axis=row_axis, col_axis=col_axis,
                           remainder_block=remainder_block)
