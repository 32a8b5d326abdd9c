"""``BENCHMARK.json`` and the files it names are whole: every cell finds
its configuration, traffic and metric readers by name, and reports
``setup_s``, another end-to-end metric and a per-layer metric."""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, loads, workcount  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CELLS_2D = [c for c in CELLS if "shape" not in harness.load_cell(c).config]


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


def _has_a_grid_form(config):
    """Either the 2-D form (``ny``, ``nx``, a ring of four sides) or the
    n-D one (``shape``, an affine ring with one coefficient per axis)."""
    ring = config["ring"]
    if "shape" in config:
        ndim = len(config["shape"])
        return (set(ring) == {"const", "coef"} and len(ring["coef"]) == ndim
                and all(len(o) == ndim for o in config["offsets"]))
    return ("ny" in config and "nx" in config
            and set(ring) == {"left", "right", "top", "bottom"}
            and all(len(o) == 2 for o in config["offsets"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = harness.load_cell(cell)
    assert c.traffic["kind"] in ("closed_fixed", "closed_tol", "open_serve")
    assert c.config["chips"] == c.chips
    for key in ("offsets", "weights", "ring", "dtype", "limits",
                "reference", "control_dtype"):
        assert key in c.config, key
    assert _has_a_grid_form(c.config)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS_2D)
def test_existing_configs_keep_their_2d_work_counts(cell):
    """The n-D form of the work counts gives every 2-D configuration the
    integers the 2-D formulas gave, so each roofline share keeps its
    yardstick."""
    cfg = harness.load_cell(cell).config
    ny, nx = cfg["ny"], cfg["nx"]
    r = max(abs(c) for o in cfg["offsets"] for c in o)
    interior, ringed, _ = loads.grid_form(cfg, r)
    assert ringed == (ny + 2 * r, nx + 2 * r)
    assert workcount.interior_points(interior) == ny * nx
    size = np.dtype(cfg["dtype"]).itemsize
    assert workcount.compulsory_bytes(ringed, size, 3) == (
        2 * (ny + 2 * r) * (nx + 2 * r) * size * 3)


def test_at_most_half_of_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_a_full_check_of_24_cells_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_every_bound_is_within_the_rules():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


def test_peaks_table_refuses_an_unknown_device():
    with pytest.raises(KeyError):
        harness.peaks_for("no such chip")
