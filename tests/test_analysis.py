"""repro.analysis: the static verifier's contracts.

Two load-bearing claims:

* **Soundness on real programs** — every lowering the registry can
  produce is accepted (the CI sweep repeats this at full cross-product
  scale in ``test_analysis_smoke.py``), and a verifier-accepted program
  runs through ``sim.run_program`` without a single CB protocol error
  (the guarantee the README states; fuzzed further with hypothesis in
  ``test_analysis_property.py``).
* **Sensitivity to broken programs** — a corpus of seeded mutants (an
  undersized CB, a dropped push, a swapped push/pop pair, an off-by-one
  block offset, ...) is rejected with *stable* diagnostic codes; the
  codes are API, so these assertions pin exact strings.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis, backends
from repro.analysis.diagnostics import CODES, Diagnostic, Report
from repro.backends import ir
from repro.backends.lower import LoweringError, lower, lowerable_policies
from repro.core.stencil import jacobi_2d_5pt, laplace_2d_9pt
from repro.engine.plan import PlanError

DEV = "grayskull_e150"
SHAPE = (34, 66)


def _prog(policy, *, t=2, tilized=None, spec=None, shape=SHAPE,
          masked=False):
    return lower(shape, jnp.float32, spec or jacobi_2d_5pt(), policy,
                 t=t, device=DEV, tilized=tilized, masked=masked)


def _push_tiles(prog, op):
    dev = prog.plan.device
    nty, ntx = ir.tile_grid(op.rows, op.cols, dev.tile_rows, dev.tile_cols)
    return nty * ntx


def _codes(report: Report) -> set:
    return {d.code for d in report.errors}


# ---------------------------------------------------------------------------
# Mutation corpus: seeded, one stable code each.
# ---------------------------------------------------------------------------

def _shrink_cb(prog):
    """Undersize the CB the first ReadBlock feeds -> CB-OVERFLOW."""
    rb = next(op for op in prog.reader if isinstance(op, ir.ReadBlock))
    need = _push_tiles(prog, rb)
    cbs = tuple(dataclasses.replace(cb, capacity_tiles=need - 1)
                if cb.name == rb.cb else cb for cb in prog.cbs)
    return dataclasses.replace(prog, cbs=cbs)


def _drop_push(prog):
    """Remove the first ReadBlock: its CB is popped but never fed."""
    rb = next(op for op in prog.reader if isinstance(op, ir.ReadBlock))
    reader = tuple(op for op in prog.reader if op is not rb)
    return dataclasses.replace(prog, reader=reader)


def _row_offset(prog):
    """Block row offset one past the halo ring -> AB-ROW at block 0.

    The first unclamped block access is shifted up by r+1 rows: dy was in
    [-r, 0], so the new window starts above the stream on block 0 — the
    off-by-one every halo-window refactor risks."""
    r = prog.plan.radius

    def shift(ops):
        done = False
        out = []
        for op in ops:
            if not done and isinstance(op, (ir.ReadBlock, ir.WriteBlock)) \
                    and not getattr(op, "clamp", False):
                op = dataclasses.replace(op, dy=op.dy - (r + 1))
                done = True
            out.append(op)
        return tuple(out), done

    reader, hit = shift(prog.reader)
    if hit:
        return dataclasses.replace(prog, reader=reader)
    writer, hit = shift(prog.writer)
    assert hit
    return dataclasses.replace(prog, writer=writer)


def _col_offset(prog):
    """Column window starting before the stream -> AB-COL."""
    wb = next(op for op in prog.writer if isinstance(op, ir.WriteBlock))
    writer = tuple(dataclasses.replace(op, col0=-1)
                   if op is wb else op for op in prog.writer)
    return dataclasses.replace(prog, writer=writer)


def _extra_pop(prog):
    """Duplicate the final WriteBlock: one push, two pops -> underflow."""
    return dataclasses.replace(prog, writer=prog.writer + (prog.writer[-1],))


_MUTATIONS = {
    "shrink-cb": (_shrink_cb, "CB-OVERFLOW"),
    "drop-push": (_drop_push, "CB-UNFED"),
    "row-offset": (_row_offset, "AB-ROW"),
    "col-offset": (_col_offset, "AB-COL"),
    "extra-pop": (_extra_pop, "CB-UNDERFLOW"),
}


@pytest.mark.parametrize("mutation", sorted(_MUTATIONS))
@pytest.mark.parametrize("policy", ["shifted", "rowchunk", "dbuf",
                                    "temporal"])
def test_mutant_rejected_with_stable_code(policy, mutation):
    # 4 policies x 5 mutation kinds = a 20-mutant corpus; every mutant
    # must be rejected and must carry its mutation's stable code.
    mutate, code = _MUTATIONS[mutation]
    prog = mutate(_prog(policy))
    report = analysis.verify_program(prog)
    assert not report.ok
    assert code in _codes(report), report.describe()


def test_mutant_swapped_push_pop_order():
    # Tilized reader: [ReadBlock stage, Tilize stage->tap]. Swapping the
    # pair makes the Tilize pop before the push lands.
    prog = _prog("shifted", tilized=True)
    i = next(i for i, op in enumerate(prog.reader)
             if isinstance(op, ir.Tilize))
    reader = list(prog.reader)
    reader[i - 1], reader[i] = reader[i], reader[i - 1]
    bad = dataclasses.replace(prog, reader=tuple(reader))
    report = analysis.verify_program(bad)
    assert "CB-UNDERFLOW" in _codes(report), report.describe()


def test_mutant_undeclared_cb_aborts_deeper_passes():
    prog = _prog("rowchunk")
    writer = (dataclasses.replace(prog.writer[-1], cb="nope"),)
    report = analysis.verify_program(
        dataclasses.replace(prog, writer=writer))
    assert _codes(report) >= {"CB-UNDECLARED"}
    assert analysis.occupancy_bounds(
        dataclasses.replace(prog, writer=writer)) is None


def test_mutant_cb_file_budget():
    prog = _prog("rowchunk")
    extras = tuple(dataclasses.replace(prog.cbs[0], name=f"pad{i}")
                   for i in range(prog.plan.device.cb_count))
    report = analysis.verify_program(
        dataclasses.replace(prog, cbs=prog.cbs + extras))
    assert "BUD-CBFILE" in _codes(report)


def test_mutant_sram_budget():
    prog = _prog("rowchunk")
    tiny = dataclasses.replace(prog.plan.device, name="sram_poor",
                               fast_memory_bytes=4096)
    plan = dataclasses.replace(prog.plan, device=tiny)
    report = analysis.verify_program(dataclasses.replace(prog, plan=plan))
    assert "BUD-SRAM" in _codes(report)
    msg = next(d for d in report.errors if d.code == "BUD-SRAM").message
    assert "MiB of fast memory" in msg and "sram_poor" in msg


def test_mutant_double_push_rate_drift():
    # A second identical ReadBlock doubles the push rate: with 1-slot CBs
    # the overflow fires immediately and the rate mismatch is an error.
    prog = _prog("rowchunk")
    rb = next(op for op in prog.reader if isinstance(op, ir.ReadBlock))
    bad = dataclasses.replace(prog, reader=prog.reader + (rb,))
    report = analysis.verify_program(bad)
    assert {"CB-OVERFLOW", "DL-RATE"} <= _codes(report), report.describe()


def test_counterexample_trace_names_op_and_iteration():
    report = analysis.verify_program(_shrink_cb(_prog("rowchunk")))
    diag = next(d for d in report.errors if d.code == "CB-OVERFLOW")
    assert "reader[0]" in diag.span and "read_block" in diag.span
    assert "iteration 0" in diag.message
    assert "capacity" in diag.message and diag.hint


# ---------------------------------------------------------------------------
# Acceptance: every unmutated registry lowering verifies clean.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["shifted", "rowchunk", "dbuf",
                                    "temporal"])
@pytest.mark.parametrize("spec", [jacobi_2d_5pt(), laplace_2d_9pt()],
                         ids=["jacobi5", "laplace9"])
def test_unmutated_lowerings_accepted(policy, spec):
    for tilized in (False, True):
        prog = _prog(policy, spec=spec, tilized=tilized)
        report = analysis.verify_program(prog)
        assert report.ok, report.describe()
        bounds = analysis.occupancy_bounds(prog)
        assert set(bounds) == {cb.name for cb in prog.cbs}
        for cb in prog.cbs:
            b = bounds[cb.name]
            assert 0 <= b.min_tiles <= b.max_tiles <= cb.capacity_tiles


@pytest.mark.parametrize("case", ["clipped", "unclipped", "past-end"])
def test_ragged_temporal_write_bounds(case):
    """At 258x258 the temporal blocks tile the ringed rows raggedly: the
    clipped write may run past the grid in its last block only. Without
    the clip, or with the last block starting past the grid, the bounds
    pass refuses it."""
    prog = _prog("temporal", t=4, shape=(258, 258))
    h, bm = prog.plan.shape[0], prog.plan.bm
    assert prog.plan.nblocks * bm > h
    wb = next(op for op in prog.writer if isinstance(op, ir.WriteBlock))
    assert wb.clip
    if case == "unclipped":
        wb2 = dataclasses.replace(wb, clip=False)
    elif case == "past-end":
        wb2 = dataclasses.replace(wb, dy=h - (prog.plan.nblocks - 1) * bm)
    else:
        wb2 = wb
    bad = dataclasses.replace(prog, writer=tuple(
        wb2 if op is wb else op for op in prog.writer))
    report = analysis.verify_program(bad)
    if case == "clipped":
        assert report.ok, report.describe()
    else:
        assert "AB-ROW" in _codes(report), report.describe()


def test_masked_temporal_accepted_and_described():
    prog = _prog("temporal", masked=True)
    assert analysis.verify_program(prog).ok
    dump = prog.describe()
    assert "<- mask stream" in dump          # the pin stream reads distinctly
    assert "occ[" in dump                    # static occupancy bounds render


# ---------------------------------------------------------------------------
# The guarantee: verifier-accepted => the simulator raises no CB errors.
# (Seeded sweep here; hypothesis widens it in test_analysis_property.py.)
# ---------------------------------------------------------------------------

def test_accepted_programs_run_clean_in_sim():
    rng = np.random.default_rng(7)
    cases = []
    for _ in range(12):
        ny = int(rng.integers(3, 40))
        nx = int(rng.integers(3, 50))
        policy = str(rng.choice(lowerable_policies()))
        t = int(rng.integers(1, 5))
        bm = int(rng.integers(1, 24))
        cases.append((ny + 2, nx + 2, policy, t, bm))
    ran = 0
    for ny, nx, policy, t, bm in cases:
        try:
            prog = lower((ny, nx), jnp.float32, jacobi_2d_5pt(), policy,
                         t=t, bm=bm, device=DEV)
        except (LoweringError, PlanError):
            continue
        assert analysis.verify_program(prog).ok
        u = rng.random((ny, nx)).astype(np.float32)
        mask = None
        if prog.plan.masked:
            mask = np.zeros((ny, nx), np.float32)
        backends.sim.run_program(u, prog, mask=mask)  # must not raise
        ran += 1
    assert ran >= 6  # the sweep must actually exercise the property


def test_rejected_program_refused_before_execution():
    bad = _shrink_cb(_prog("rowchunk"))
    u = np.zeros(SHAPE, np.float32)
    with pytest.raises(ir.CBOverflowError, match="overflow"):
        backends.simulate_program(u, bad)


# ---------------------------------------------------------------------------
# Diagnostics surface.
# ---------------------------------------------------------------------------

def test_diagnostic_codes_are_closed_vocabulary():
    with pytest.raises(ValueError, match="unknown diagnostic code"):
        Diagnostic("error", "NOT-A-CODE", "x", "y")
    with pytest.raises(ValueError, match="severity"):
        Diagnostic("fatal", "CB-OVERFLOW", "x", "y")
    assert len(CODES) >= 16


def test_report_surface():
    clean = Report()
    assert clean.ok and not clean and "clean" in clean.describe()
    clean.raise_if_errors(ir.BackendError)  # no-op
    rep = analysis.verify_program(_drop_push(_prog("dbuf")))
    assert rep and not rep.ok
    merged = clean.merged(rep)
    assert merged.errors == rep.errors
    with pytest.raises(ir.BackendError, match="CB-UNFED"):
        rep.raise_if_errors(ir.BackendError)
