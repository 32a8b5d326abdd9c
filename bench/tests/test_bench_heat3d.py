"""The PolyBench heat-3d cell finds its configuration and traffic by name
and counts the work PolyBench's kernel does."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, loads, workcount  # noqa: E402


def test_heat3d_cell_counts_polybench_work():
    """PolyBench heat-3d at EXTRALARGE: 7 operations at each of 198^3
    interior points in each of 2000 sweeps a solve, and the ringed 200^3
    float32 grid read and written once."""
    c = harness.load_cell("heat3d-polybench-f32.fixed2000")
    assert c.chips == 1 and c.traffic["sweeps"] == 2000
    interior, ringed, _ = loads.grid_form(c.config, 1)
    pts = workcount.interior_points(interior)
    assert (interior, ringed, pts) == ((198,) * 3, (200,) * 3, 7_762_392)
    taps = len(c.config["offsets"])
    assert workcount.sweep_ops(pts, c.traffic["sweeps"], taps) == (
        7 * 7_762_392 * 2000)
    assert workcount.compulsory_bytes(ringed, 4, 1) == 2 * 200**3 * 4
    assert "kernel_roofline_share.fixed" in {m["name"] for m in c.per_layer}
