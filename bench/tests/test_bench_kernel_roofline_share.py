"""``kernel_roofline_share``: the least time of the window's useful work
over the time the stencil kernels ran, read by the kernels' own name, on
hand-worked traces and on a recorded chip trace."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

READ = harness.metric_reader("kernel_roofline_share.fixed")
PEAKS = {"vector_f32_ops_per_s": 4e12, "hbm_bytes_per_s": 1e12}


def _run(devices, ops, nbytes, window=(0.0, 10.0)):
    return types.SimpleNamespace(
        trace=tr.Trace(window=window, devices=devices, host=[]),
        work={"ops": ops, "bytes": nbytes, "sweeps": []},
        peaks=PEAKS, chips=len(devices))


def test_hand_worked_share_over_two_chips():
    """Kernels run (4 + 2) / 2 = 3 s a chip, whatever number XLA gives
    them; 1.2e13 operations on two chips at 4e12 each take at least 1.5
    s: a 50% share. Write-backs and fusions are no kernel time."""
    devices = {
        "/device:TPU:0": [("%stencil_temporal3d.1 = f32[8] custom-call()",
                           0, 4), ("%dynamic-update-slice.3", 4, 6)],
        "/device:TPU:1": [("%stencil_temporal.10", 1, 3),
                          ("%fusion.5", 3, 9)]}
    assert READ(_run(devices, 1.2e13, 1e9)) == pytest.approx(50.0)


def test_memory_bound_work_reads_the_hbm_floor():
    devices = {"/device:TPU:0": [("%stencil_temporal3d.2", 2, 6)]}
    # 1e8 operations take 25 us; 2e12 bytes take 2 s of the kernels' 4.
    assert READ(_run(devices, 1e8, 2e12)) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["renamed", "no_work"])
def test_nothing_to_read(case):
    name = "%kernel.1" if case == "renamed" else "%stencil_temporal3d.1"
    run = _run({"/device:TPU:0": [(name, 0, 4)]},
               0 if case == "no_work" else 1e12, 0)
    assert READ(run) is None


def test_recorded_tpu_trace_is_roofline_over_kernel_share():
    """On 0.3 s of a TPU v5 lite trace, with work made up for it, the
    kernels' share of their roofline is the busy share of the roofline
    over the kernels' share of busy time."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tpu_tol_window.json")) as f:
        rec = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    run = _run(devices, 5e10, 1e9, window=tuple(rec["window"]))
    roof = harness.metric_reader("roofline_share.fixed")(run)
    kshare = harness.metric_reader("kernel_share.fixed")(run)
    assert READ(run) == pytest.approx(roof * 100 / kshare, rel=1e-12)
    assert READ(run) == pytest.approx(
        100 * 5e10 / 4e12 / 0.2056962, rel=1e-6)
