"""Measured autotuner: pick the policy by timing it, then never again.

``resolve_auto`` is a model; this module is the measurement. For a given
``(shape, dtype, spec, device)`` cell it times every registry policy whose
plan validates on that device (one warmup + a few timed reps of the jitted
single-call kernel, normalized per sweep for fused policies), picks the
fastest, and persists the winner to a JSON cache — the same
measure-and-cache discipline ``launch/tuning.py`` applies to model cells,
brought down to the stencil engine. The second request for the same cell
is a dict lookup; across processes it is a file read.

The cache file maps ``key -> {"policy", "us_per_sweep", "skipped"}``.
Keys fold in everything that changes the winner: grid shape, dtype, the
spec's taps/weights, the device model, the fusion depth bucket, the bm
request, and whether the measurement ran in interpret mode (interpret
walltimes bear no relation to compiled ones, so the two worlds must
never share winners). Entries are keyed by *device model*, not host
backend — a CPU process tuning for ``grayskull_e150`` produces
e150-keyed entries (the measurements are still taken on this host; like
every interpret-mode number in this repo they are relative, but the
*candidate set* is the device's own, because planning gates candidates
by its budget). Each cache file is loaded and saved as its own unit —
entries never migrate between files.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stencil import StencilSpec
from repro.engine.device import DeviceModel, get_device
from repro.engine.dispatch import _on_tpu, get_policy, registry
from repro.engine.plan import DEFAULT_T, PlanError, plan_for
from repro.engine.schedule import effective_depth
from repro.obs import metrics as _metrics
from repro.obs.trace import span as _obs_span

#: Default on-disk location; override per call or via $REPRO_TUNE_CACHE.
DEFAULT_CACHE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "engine_tune.json")

# One in-memory dict per cache file, loaded lazily; kept separate so
# saving one file never writes another file's entries into it.
_caches: dict[str, dict[str, dict]] = {}
_loaded_paths: set[str] = set()

#: Number of measurement passes taken since import (test/diagnostic hook:
#: a cache hit must not bump this).
measure_count = 0


def _cache_path(cache_path: str | None) -> str:
    return cache_path or os.environ.get("REPRO_TUNE_CACHE",
                                        DEFAULT_CACHE_PATH)


def tune_key(shape, dtype, spec: StencilSpec, device: DeviceModel, *,
             t: int | None, bm: int | None, interpret: bool = True,
             mesh: tuple | None = None, masked: bool = False,
             overlap: bool = False) -> str:
    """Stable cache key for one autotune cell.

    ``mesh`` is the decomposition shape when the caller is tuning a *shard*
    (``engine.run_distributed``): the same local shape can want a different
    winner under a different decomposition (halo bands change the window
    geometry), so single-device cells (``mesh=None`` -> ``mesh=local``)
    and per-mesh cells never share winners. ``masked`` separates cells
    whose fused candidates were gated by the masked (pin-mask-streaming)
    plan — a winner measured without that gate must never satisfy a
    lookup that will launch the masked form. ``overlap`` separates cells
    whose schedule runs the interior/rind exchange-hiding split: the
    overlapped executor launches the kernel on the raw shard plus four
    rind strips instead of one extended block, a different enough launch
    geometry that its winner must never alias the serial one.
    """
    return "|".join([
        "x".join(str(int(s)) for s in shape),
        jnp.dtype(dtype).name,
        f"taps={spec.offsets}w={spec.weights}",
        device.name,
        f"t={t if t is not None else DEFAULT_T}",
        f"bm={bm if bm is not None else 'auto'}",
        f"interpret={bool(interpret)}",
        "mesh=" + ("local" if mesh is None else
                   "x".join(str(int(m)) for m in mesh)),
        f"masked={bool(masked)}",
        f"overlap={bool(overlap)}",
    ])


def _cache_for(path: str) -> dict[str, dict]:
    """This file's in-memory view, seeded from disk once per path."""
    cache = _caches.setdefault(path, {})
    if path not in _loaded_paths:
        _loaded_paths.add(path)
        try:
            with open(path) as f:
                on_disk = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            on_disk = {}
        for k, v in on_disk.items():
            cache.setdefault(k, v)
    return cache


def _save(path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(_caches.get(path, {}), f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def clear(*, memory_only: bool = True) -> None:
    """Drop the in-memory caches (tests); on-disk files are left alone."""
    _caches.clear()
    _loaded_paths.clear()
    if not memory_only:
        path = _cache_path(None)
        if os.path.exists(path):
            os.remove(path)


def _time_policy(u, spec, name: str, *, bm, t, interpret: bool,
                 device: DeviceModel, reps: int = 3) -> float:
    """Median seconds per *sweep* of one jitted policy call."""
    p = get_policy(name)
    if p.fused:
        fn = jax.jit(lambda v: p.fn(v, spec, bm=bm, t=t, interpret=interpret,
                                    device=device))
        sweeps = t
    else:
        fn = jax.jit(lambda v: p.fn(v, spec, bm=bm, interpret=interpret,
                                    device=device))
        sweeps = 1
    jax.block_until_ready(fn(u))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(u))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) / sweeps


def measure(shape, dtype, spec: StencilSpec, *, t: int | None = None,
            bm: int | None = None, interpret: bool | None = None,
            device: str | DeviceModel | None = None,
            masked: bool = False) -> dict:
    """Time every policy that plans on ``device``; return the record.

    Candidates whose plan fails validation (budget, shape) are skipped —
    that is the device model doing its job, not an error. Fused candidates
    run at the effective depth ``t`` and are charged per sweep; with
    ``masked`` (distributed-shard cells) they are gated by the masked
    plan's larger footprint, since that is the form the distributed
    executor launches (the timing itself still runs the plain kernel —
    interpret-mode numbers are relative anyway).
    """
    global measure_count
    measure_count += 1
    if interpret is None:
        interpret = not _on_tpu()
    dev = get_device(device)
    t_eff = t if t is not None else DEFAULT_T
    u = jnp.zeros(tuple(int(s) for s in shape), jnp.dtype(dtype))
    timings: dict[str, float] = {}
    skipped: dict[str, str] = {}
    for p in registry():
        kw_t = t_eff if p.fused else None
        try:
            plan_for(shape, dtype, spec, p.name, bm=bm, t=kw_t, device=dev,
                     masked=masked and p.fused)
        except PlanError as e:
            skipped[p.name] = str(e)
            continue
        # the model object rides through whole so unregistered DeviceModel
        # instances work identically to registry names
        with _obs_span("tune.measure", policy=p.name, device=dev.name,
                       shape=tuple(int(s) for s in shape)) as sp:
            timings[p.name] = _time_policy(u, spec, p.name, bm=bm, t=kw_t,
                                           interpret=interpret, device=dev)
            sp.set(us_per_sweep=round(timings[p.name] * 1e6, 3))
    if not timings:
        raise PlanError(
            f"no policy plans for grid {tuple(shape)} ({jnp.dtype(dtype).name},"
            f" {spec.taps} taps) on {dev.name}: "
            + "; ".join(f"{k}: {v}" for k, v in skipped.items()))
    best = min(timings, key=timings.get)
    return {
        "policy": best,
        "us_per_sweep": {k: round(v * 1e6, 3) for k, v in timings.items()},
        "skipped": sorted(skipped),
        "device": dev.name,
    }


def best_policy(shape, dtype, spec: StencilSpec, *, iters: int = 1,
                t: int | None = None, bm: int | None = None,
                interpret: bool | None = None,
                device: str | DeviceModel | None = None,
                mesh: tuple | None = None, masked: bool = False,
                overlap: bool = False,
                cache_path: str | None = None) -> str:
    """The measured-fastest policy for this cell; measured at most once.

    Lookup order: in-memory cache -> JSON file -> measure (and persist).
    Fused winners are only eligible when ``iters`` can amortize them, so a
    single-sweep call re-buckets to ``t=1`` (matching ``run``'s remainder
    semantics) rather than inheriting a t=8 winner it cannot run. ``mesh``
    buckets distributed-shard cells by decomposition shape (the
    measurement itself still times the local shard kernel); ``masked``
    gates fused candidates by their masked-plan footprint and always
    rides with ``mesh`` in the distributed path, so the mesh bucket
    already separates the two candidate worlds in the key.
    ``interpret=None`` times compiled kernels on a TPU and the Pallas
    interpreter elsewhere, the same rule ``engine.run`` applies.
    """
    if interpret is None:
        interpret = not _on_tpu()
    dev = get_device(device)
    t_eff = effective_depth(iters, t)
    key = tune_key(shape, dtype, spec, dev, t=t_eff, bm=bm,
                   interpret=interpret, mesh=mesh, masked=masked,
                   overlap=overlap)
    path = _cache_path(cache_path)
    cache = _cache_for(path)
    rec = cache.get(key)
    if rec is None:
        _metrics.counter("engine.tune.miss").inc()
        rec = measure(shape, dtype, spec, t=t_eff, bm=bm,
                      interpret=interpret, device=dev, masked=masked)
        cache[key] = rec
        _save(path)
    else:
        _metrics.counter("engine.tune.hit").inc()
    return rec["policy"]


def warm(shapes, dtype, spec: StencilSpec, *, iters: int = 1,
         t: int | None = None, bm: int | None = None,
         interpret: bool | None = None,
         device: str | DeviceModel | None = None,
         mesh: tuple | None = None, masked: bool = False,
         overlap: bool = False,
         cache_path: str | None = None) -> dict[tuple, str]:
    """Populate the tune cache for a batch of shapes before traffic hits.

    Server startup (and tests) call this once per (bucket, device) so the
    first wave of requests never pays a measurement pass — every
    subsequent :func:`best_policy` lookup for these cells is a dict hit.
    ``shapes`` is an iterable of ringed grid shapes; every other knob is
    the :func:`best_policy` cell key. Returns ``{shape: winner}``.

    Warming is idempotent: a cell that is already cached (in memory or on
    disk) is **never re-measured** — ``measure_count`` does not move for
    it, which the regression tests pin.
    """
    out: dict[tuple, str] = {}
    for shape in shapes:
        key = tuple(int(s) for s in shape)
        out[key] = best_policy(key, dtype, spec, iters=iters, t=t, bm=bm,
                               interpret=interpret, device=device,
                               mesh=mesh, masked=masked, overlap=overlap,
                               cache_path=cache_path)
    return out


def cache_info() -> dict:
    """Diagnostics: entries resident in memory and measurements taken."""
    return {"entries": sum(len(c) for c in _caches.values()),
            "measure_count": measure_count}
