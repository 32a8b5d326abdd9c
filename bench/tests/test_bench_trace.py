"""The trace-to-metrics reduction and the work counts, on hand-worked
inputs."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402
from bench import workcount as wc  # noqa: E402


def _trace(devices, window=(0.0, 10.0), host=()):
    return tr.Trace(window=window, devices=devices, host=list(host))


def test_union_merges_overlaps_and_clips_to_the_window():
    got = tr.union([(5, 7), (-1, 1), (6, 8), (9, 12), (2, 2)], 0, 10)
    assert got == [(0, 1), (5, 8), (9, 10)]


@pytest.mark.parametrize("base, cut, want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [], [(0, 4)]),
    ([(0, 4)], [(-1, 5)], []),
])
def test_subtract(base, cut, want):
    assert tr.subtract(base, cut) == want


def test_busy_and_idle_average_over_devices():
    t = _trace({"/device:TPU:0": [("a", 0, 4), ("b", 3, 6)],
                "/device:TPU:1": [("a", 1, 3), ("c", 8, 12)]})
    # device 0 busy 6 s; device 1 busy 2 + 2 (clipped at 10) = 4 s.
    assert tr.busy_s(t) == pytest.approx(5.0)
    assert tr.idle_share(t) == pytest.approx(0.5)


def test_exposed_collective_time_is_what_no_other_op_covers():
    t = _trace({
        "/device:TPU:0": [("fusion.1", 0, 4),
                          ("collective-permute-start.2", 3, 5),
                          ("collective-permute-done.2", 5, 6)],
        "/device:TPU:1": [("fusion.1", 0, 2),
                          ("collective-permute-done.2", 1, 5)],
    })
    # device 0: collectives cover 3..6, compute 0..4 -> 2 s exposed;
    # device 1: 1..5 minus 0..2 -> 3 s exposed. Mean 2.5 s.
    assert tr.exposed_collective_s(t) == pytest.approx(2.5)
    assert tr.exposed_collective_s(
        _trace({"/device:TPU:0": [("fusion", 0, 1)]})) is None


def test_top_ops_sums_by_name_within_the_window():
    t = _trace({"/device:TPU:0": [("k", 0, 2), ("k", 3, 5), ("copy", 5, 6),
                                  ("k", 9, 11)]})
    assert tr.top_ops(t) == [["k", 5.0], ["copy", 1.0]]
    assert tr.top_ops(t, k=1) == [["k", 5.0]]


def test_idle_gaps_go_to_the_innermost_covering_annotation():
    t = _trace({"/device:TPU:0": [("k", 0, 2), ("k", 4, 9)]},
               host=[(tr.WINDOW, 0, 10), ("bench.step", 1, 10),
                     ("bench.submit", 2, 4)])
    # gaps 2..4 (under bench.submit) and 9..10 (under bench.step).
    assert tr.idle_gaps(t) == [["bench.submit", 2.0], ["bench.step", 1.0]]
    t = _trace({"/device:TPU:0": [("k", 0, 2)]}, window=(0, 3))
    assert tr.idle_gaps(t) == [["unannotated", 1.0]]


def test_work_counts_hand_worked():
    # The paper's grid: 1024 x 9216 interior, radius 1, float32.
    pts = wc.interior_points((1024, 9216))
    assert pts == 9_437_184
    assert wc.ringed_shape((1024, 9216), 1) == (1026, 9218)
    assert wc.sweep_ops(pts, 5000, 4) == 188_743_680_000
    assert wc.compulsory_bytes((1026, 9218), 4, 1) == 2 * 1026 * 9218 * 4
    assert wc.compulsory_bytes((1026, 9218), 4, 3) == 3 * 75_661_344
    # PolyBench heat-3d at EXTRALARGE: a 198^3 interior, 7 taps, float32.
    pts = wc.interior_points((198, 198, 198))
    assert pts == 7_762_392
    assert wc.ringed_shape((198, 198, 198), 1) == (200, 200, 200)
    assert wc.sweep_ops(pts, 2000, 7) == 108_673_488_000
    assert wc.compulsory_bytes((200, 200, 200), 4, 1) == 64_000_000


def test_least_time_takes_the_larger_bound_over_all_chips():
    t, bound = wc.least_time_s(8e12, 1e9, vector_ops_per_s=4e12,
                               hbm_bytes_per_s=1e12, chips=1)
    assert (t, bound) == (2.0, "vector")
    t, bound = wc.least_time_s(8e12, 1e13, vector_ops_per_s=4e12,
                               hbm_bytes_per_s=1e12, chips=4)
    assert (t, bound) == (2.5, "hbm")


def test_recorded_tpu_trace():
    """0.3 s recorded on a TPU v5 lite in the tolerance cell: the end of
    one solve's while loop, the host's turn-around, and the next loop."""
    import json
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tpu_tol_window.json")) as f:
        rec = json.load(f)
    t = tr.Trace(window=tuple(rec["window"]),
                 devices={k: [tuple(e) for e in v]
                          for k, v in rec["devices"].items()},
                 host=[tuple(h) for h in rec["host"]])
    loops = [e for e in t.devices["/device:TPU:0"] if tr.CONTAINER.search(e[0])]
    assert len(loops) == 2
    between = loops[1][1] - loops[0][2]      # the host's turn-around
    idle = t.window_s - tr.busy_s(t)
    assert 0 < idle <= between
    assert idle > 0.9 * between              # little runs outside the loops
    top = tr.top_ops(t)
    assert top[0][0].startswith("%stencil_temporal")
    assert not any(tr.CONTAINER.search(name) for name, _ in top)
    assert sum(s for _, s in top) <= tr.busy_s(t) + 1e-9
    assert tr.idle_gaps(t)[0][0] == "bench.solve"
    assert tr.exposed_collective_s(t) is None


def test_a_loop_around_a_collective_does_not_hide_it():
    t = _trace({"/device:TPU:0": [
        ("%while.1 = (f32[8]) while((f32[8]) %t), body=%b", 0, 10),
        ("%fusion.2 = f32[8] fusion(f32[8] %p)", 0, 4),
        ("%collective-permute-done.3 = f32[8] collective-permute-done(%s)",
         4, 6),
        ("%custom-call.4 = f32[8] custom-call(f32[8] %p)", 6, 9)]},
        window=(0, 10))
    assert tr.exposed_collective_s(t) == pytest.approx(2.0)
    assert [n for n, _ in tr.top_ops(t)] == [
        "%fusion.2", "%custom-call.4", "%collective-permute-done.3"]
