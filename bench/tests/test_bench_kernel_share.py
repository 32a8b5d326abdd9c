"""``kernel_share``: the stencil kernels' share of device busy time, read
by the kernel's own name, on a recorded chip trace and on hand-worked
ones."""
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

READ = harness.metric_reader("kernel_share.fixed")


def _recorded(rename=None):
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tpu_tol_window.json")) as f:
        rec = json.load(f)
    devices = {k: [((rename(e[0]) if rename else e[0]), e[1], e[2])
                   for e in v] for k, v in rec["devices"].items()}
    return types.SimpleNamespace(trace=tr.Trace(
        window=tuple(rec["window"]), devices=devices,
        host=[tuple(h) for h in rec["host"]]))


def test_recorded_tpu_trace_reads_the_hand_worked_share():
    """0.3 s of the tolerance cell on a TPU v5 lite: 181 kernel calls
    (one cut by the window's start) take 0.2056962 s of the 0.2948062 s
    the device was busy; write-backs, residual fusions and copies take
    the rest."""
    assert READ(_recorded()) == pytest.approx(
        100 * 0.2056962 / 0.2948062, abs=1e-6)


def test_a_kernel_renamed_away_from_the_prefix_reads_zero():
    run = _recorded(lambda n: n.replace("%stencil_temporal", "%kernel"))
    assert READ(run) == 0.0


def test_averages_over_chips_and_ignores_xla_numbering():
    t = tr.Trace(window=(0.0, 10.0), devices={
        "/device:TPU:0": [("%stencil_temporal.3 = f32[8] custom-call()",
                           0, 6), ("%dynamic-update-slice.3", 6, 8)],
        "/device:TPU:1": [("%stencil_temporal.10", 0, 2),
                          ("%fusion.5", 2, 4)]}, host=[])
    # kernel (6 + 2) / 2 = 4 s of busy (8 + 4) / 2 = 6 s
    assert READ(types.SimpleNamespace(trace=t)) == pytest.approx(
        100 * 4 / 6)
