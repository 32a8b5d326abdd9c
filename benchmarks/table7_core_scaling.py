"""Paper Table VII/VIII-left analogue — scaling across cores/chips.

The paper decomposes over up to 108 Tensix cores (22.06 GPt/s) and 4 cards
(86.75 GPt/s) but cannot exchange halos card-to-card. We compile the real
shard_map halo-exchange solver for 1..8 host devices, extract per-step
halo traffic from the partitioned HLO (loop-aware), and model v5e scaling:
t_step = max(compute, memory, halo/ICI). The modeled numbers show
near-linear scaling because depth-t exchange amortizes latency — the fix
for the paper's stated multi-card limitation.
"""
import json
import os
import subprocess
import sys

from benchmarks.common import (dry_run, row, HBM_BW,  # noqa: F401
                               TXN_OVERHEAD_S, model_jacobi_gpts)
from repro.roofline import V5E

_SCRIPT = r"""
import jax, jax.numpy as jnp, json
from repro import engine
from repro.core.stencil import make_laplace_problem
from repro.hlo_analysis import analyze_hlo

out = []
u = make_laplace_problem(1024, 9216, dtype=jnp.bfloat16)  # paper's domain
for ndev in (1, 2, 4, 8):
    mesh = jax.make_mesh((ndev,), ("x",))
    for depth in (1, 8):
        sweeps = 16 if depth > 1 else 8
        fn = jax.jit(lambda v: engine.run_distributed(
            v, mesh=mesh, policy="reference", iters=sweeps, t=depth,
            row_axis="x"))
        comp = fn.lower(jax.eval_shape(lambda: u)).compile()
        la = analyze_hlo(comp.as_text(), ndev)
        out.append({"ndev": ndev, "depth": depth,
                    "coll_bytes_per_sweep": la.collective_bytes / sweeps,
                    "hbm_proxy_per_sweep": la.hbm_proxy_bytes / sweeps})
print(json.dumps(out))
"""


def _analytic_halo_bytes():
    """Dry-mode stand-in for the HLO-extracted collective bytes: a 1-D
    row decomposition exchanges two full-width depth-``d`` halo bands per
    shard per exchange (amortized over ``d`` sweeps), bf16."""
    w, db = 9216, 2
    out = []
    for ndev in (1, 2, 4, 8):
        for depth in (1, 8):
            per_sweep = 0 if ndev == 1 else 2 * w * db  # d rows / d sweeps
            out.append({"ndev": ndev, "depth": depth,
                        "coll_bytes_per_sweep": per_sweep,
                        "hbm_proxy_per_sweep": 1024 * 9216 * 2 * db / ndev})
    return out


def run():
    rows = []
    if dry_run():
        # modeled/smoke mode: skip the 8-device subprocess compile, price
        # the analytic halo traffic through the same modeling code below
        data = _analytic_halo_bytes()
    else:
        env = dict(os.environ)
        # CPU only: this child splits the host CPU into 8 devices. The
        # parent has already imported JAX (benchmarks.common), and on a TPU
        # host it holds the chip, so the child must never reach for it.
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.path.join(repo, "src")
        proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                              capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            return [row("table7_subprocess_failed", 0.0,
                        proc.stderr.strip().splitlines()[-1][:100])]
        data = json.loads(proc.stdout.strip().splitlines()[-1])
    npts = 1024 * 9216
    for rec in data:
        ndev, depth = rec["ndev"], rec["depth"]
        bw_t = (npts / ndev) * 4 / HBM_BW          # bf16 in+out per sweep
        halo_t = rec["coll_bytes_per_sweep"] / V5E["ici_bw"]
        t = max(bw_t, halo_t)
        gpts = npts / t / 1e9
        rows.append(row(f"v5e_chips{ndev}_depth{depth}",
                        rec["coll_bytes_per_sweep"],
                        f"model_GPt/s={gpts:.1f};halo_frac={halo_t/t:.3f}"))
    rows.extend(_fused_schedule_rows(npts))
    rows.append(row("paper_e150_108cores", 0.0, "paper_GPt/s=22.06"))
    rows.append(row("paper_4xe150_432cores", 0.0, "paper_GPt/s=86.75"))
    rows.append(row("paper_cpu_24cores", 0.0, "paper_GPt/s=21.61"))
    return rows


def _fused_schedule_rows(npts: int, w: int = 9216, db: int = 2,
                         sweeps: int = 16):
    """Fused-vs-unfused exchange tradeoff, priced from the real schedule.

    The depth rows above amortize *latency* but still pay full HBM traffic
    every sweep (the local kernel is non-fused). These rows run the
    ``temporal`` policy per shard: the same :class:`SweepSchedule` both
    executors use says how many exchanges a run costs, and the registry's
    traffic model says what fusion saves in DRAM bytes — so the table
    moves if either the schedule or the policy's traffic model changes.
    """
    from repro.core.stencil import jacobi_2d_5pt
    from repro.engine.dispatch import get_policy
    from repro.engine.schedule import build_schedule

    spec = jacobi_2d_5pt()
    temporal = get_policy("temporal")
    out = []
    for ndev in (1, 2, 4, 8):
        for tt in (1, 8):
            sched = build_schedule(
                sweeps, spec=spec, shape=(1024 // ndev + 2, w), dtype="bfloat16",
                policy="temporal", t=tt, device="tpu_v5e",
                exchange_cadence=True)
            bpp = temporal.bytes_per_point(spec, db, sched.t)
            hbm_t = (npts / ndev) * bpp / HBM_BW           # per sweep
            halo_bytes = 0 if ndev == 1 else \
                2 * sched.halo_depth * w * db              # per exchange
            halo_t = (sched.exchanges * halo_bytes / sweeps) / V5E["ici_bw"] \
                + (sched.exchanges / sweeps) * TXN_OVERHEAD_S
            step = max(hbm_t, halo_t)
            gpts = npts / step / 1e9
            out.append(row(
                f"v5e_chips{ndev}_fused_t{sched.t}", halo_bytes,
                f"model_GPt/s={gpts:.1f};exchanges={sched.exchanges};"
                f"halo_depth={sched.halo_depth};bytes_pt={bpp:.2f}"))
    out.extend(_overlapped_rows(spec, w=w, db=db, sweeps=sweeps))
    return out


def _overlapped_rows(spec, w: int, db: int, sweeps: int):
    """Exchange-hiding rows: the interior/rind split priced per device.

    ``price_exchange`` bills the same rounds ``run_distributed`` would run,
    serial (``exchange + compute``) vs overlapped (``max(exchange,
    interior) + rind``). The Grayskull rows are the paper's multi-card
    gap made concrete: four PCIe cards can't read each other's DRAM, so
    the halo rides the host link (``mesh_direct_links=False``) and hiding
    the deep exchange behind the halo-independent interior is where the
    modeled wall-clock comes back.
    """
    from repro.engine.schedule import build_schedule, price_exchange

    out = []
    for dev_tag, dev in (("v5e", "tpu_v5e"), ("e150", "grayskull_e150")):
        for ndev in (2, 4):
            for tt in (1, 8):
                sched = build_schedule(
                    sweeps, spec=spec, shape=(1024 // ndev + 2, w),
                    dtype="bfloat16", policy="temporal", t=tt, device=dev,
                    exchange_cadence=True)
                d = sched.halo_depth
                shard = (1024 // ndev + 2 * d, w + 2 * d)
                bill = price_exchange(sched, shard_shape=shard,
                                      dtype="bfloat16", spec=spec,
                                      device=dev, mesh_shape=(ndev,))
                out.append(row(
                    f"{dev_tag}_chips{ndev}_fused_t{sched.t}_overlapped",
                    bill.overlapped_s * 1e6,
                    f"model_serial_us={bill.serial_s * 1e6:.1f};"
                    f"model_overlapped_us={bill.overlapped_s * 1e6:.1f};"
                    f"wins={'overlap' if bill.wins else 'serial'}"))
    return out
