"""Spec-driven stencil execution engine.

One subsystem replaces the per-kernel zoo: any 2-D
:class:`~repro.core.stencil.StencilSpec` (any radius, any tap set) runs
under any of the paper's execution policies —

    ``shifted``  (§IV initial)  ·  ``rowchunk`` (§VI optimized)
    ``dbuf``     (Table I double buffering)  ·  ``temporal`` (beyond paper)

A 3-D spec on a (planes, rows, lanes) grid runs the temporal kernel's
3-D form (PolyBench heat-3d's shape) on one device; ``auto`` picks it.

Typical use::

    from repro import engine
    from repro.core.stencil import laplace_2d_9pt

    u1 = engine.run(u, laplace_2d_9pt(), policy="auto", iters=100)

Every entry point takes ``device=`` (a registry name such as
``"grayskull_e150"`` or a :class:`~repro.engine.device.DeviceModel`):
plans are validated against that device's fast-memory budget and the
``"auto"``/``"tuned"`` policies pick their winner for that device. With no
device the host backend is detected.

Layers: ``device`` (hardware models + registry), ``plan`` (block/window/
temporal-depth planning, cached per device), ``schedule`` (how ``iters``
sweeps become fused blocks + halo exchanges — shared by every executor),
``policies`` (the Pallas kernels), ``dispatch`` (registry + run/step),
``tune`` (measured autotuner behind ``policy="tuned"``).
"""
from repro.engine.device import (  # noqa: F401
    DeviceModel,
    available_devices,
    detect,
    device_registry,
    get_device,
    register_device,
)
from repro.engine.plan import (  # noqa: F401
    DEFAULT_BM,
    DEFAULT_T,
    ExecutionPlan,
    PlanError,
    pick_bm,
    plan_cache_clear,
    plan_cache_info,
    plan_for,
)
from repro.engine.policies import (  # noqa: F401
    stencil_dbuf,
    stencil_rowchunk,
    stencil_shifted,
    stencil_temporal,
)
from repro.engine.schedule import (  # noqa: F401
    DEFAULT_REMAINDER_POLICY,
    ExchangeBill,
    SweepSchedule,
    build_schedule,
    effective_depth,
    price_exchange,
)
from repro.engine.dispatch import (  # noqa: F401
    Policy,
    available_policies,
    get_policy,
    register_policy,
    registry,
    residual_for,
    resolve_auto,
    run,
    run_batched,
    run_converged,
    step,
)
from repro.engine.distributed import (  # noqa: F401
    local_sweep_for,
    plan_distributed,
    run_distributed,
)
