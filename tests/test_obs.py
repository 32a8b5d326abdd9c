"""repro.obs: disabled-by-default tracing, metrics, reconciliation.

The load-bearing properties of the observability layer:

* **Off is free and invisible** — with no tracer installed and no
  profiler capturing, ``obs.span`` returns one shared no-op singleton (no
  allocation), and every instrumented path (``engine.run``,
  ``run_distributed``, the server) produces bit-identical output with obs
  on vs off, from the same cached launch.
* **On is honest** — spans carry their nesting path and attrs into a
  well-formed Chrome trace and into a JAX profiler capture, on the device
  trace's clock; ``reconcile`` joins measured durations against attached
  ``model_s`` predictions, firing structured ``OBS-DRIFT`` /
  ``OBS-UNMODELED`` diagnostics.
* **Device phases carry stable names** — every kernel is named with the
  program's prefix, and the launches' phases are named scopes that reach
  each operation's ``op_name``.
"""
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
from repro.obs import metrics
from repro.obs.trace import NULL_SPAN, Tracer, span_records, use_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Disabled path: no tracer installed
# ---------------------------------------------------------------------------

def test_null_span_is_a_shared_singleton():
    """No tracer -> obs.span allocates nothing: every call returns the
    same no-op instance, whatever the name or attrs."""
    assert obs.get_tracer() is None
    a = obs.span("engine.run", iters=3)
    b = obs.span("anything.else")
    assert a is b is NULL_SPAN
    with a as sp:
        assert sp.set(policy="temporal") is sp  # set is a no-op, chains
    obs.counter("sim.core_busy_s", {"core0": 1.0})  # no-op, no error
    with pytest.raises(RuntimeError):
        obs.write_trace("/tmp/never-written.json")


def test_engine_run_bit_identical_obs_on_vs_off():
    u = make_laplace_problem(18, 34, dtype=np.float32, left=1.0)
    from repro import engine

    def go():
        return np.asarray(engine.run(u, jacobi_2d_5pt(), policy="temporal",
                                     iters=8, t=4, interpret=True))

    off = go()
    tracer = Tracer()
    with use_tracer(tracer):
        on = go()
    np.testing.assert_array_equal(on, off)
    names = [e.name for e in tracer.events]
    assert "engine.run" in names and "engine.build_schedule" in names
    (run_ev,) = [e for e in tracer.events if e.name == "engine.run"]
    assert run_ev.attrs["policy"] == "temporal"
    assert run_ev.attrs["t"] == 4
    # The kernel's form: a window this small sweeps as one value (no
    # strips), one block of 24 tile-rounded rows for the 18 it keeps.
    assert run_ev.attrs["strip_rows"] == 0
    assert run_ev.attrs["recompute"] == round(24 / 18, 4)
    # build_schedule nests under engine.run in the span tree.
    (sched_ev,) = [e for e in tracer.events
                   if e.name == "engine.build_schedule"]
    assert sched_ev.path == ("engine.run", "engine.build_schedule")


def _host_events(log_dir: str, name: str) -> list:
    """``(stats, start_ns, end_ns)`` of every host event called ``name``
    in the one profiler capture under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(dict(ev.stats), ev.start_ns, ev.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_spans_land_in_a_profiler_capture_with_their_attrs(tmp_path):
    """Under a profiler session a span is live even with no tracer: it
    becomes a host annotation carrying its attrs, set-later ones too,
    on the profiler's clock around what it launched."""
    from repro import engine
    u = make_laplace_problem(18, 34, dtype=np.float32, left=1.0)
    assert obs.get_tracer() is None
    with jax.profiler.trace(str(tmp_path)):
        assert obs.span("engine.run") is not NULL_SPAN
        out = engine.run(u, jacobi_2d_5pt(), policy="temporal", iters=8,
                         t=4, interpret=True).block_until_ready()
    assert obs.span("engine.run") is NULL_SPAN      # capture over: off
    (run,) = _host_events(str(tmp_path), "engine.run")
    stats, start, end = run
    assert stats["iters"] == 8 and stats["requested_policy"] == "temporal"
    assert stats["policy"] == "temporal" and stats["t"] == 4
    (sched,) = _host_events(str(tmp_path), "engine.build_schedule")
    assert start <= sched[1] <= sched[2] <= end      # nested, one clock
    off = engine.run(u, jacobi_2d_5pt(), policy="temporal", iters=8, t=4,
                     interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(off))


def test_launch_cache_miss_compiles_in_a_span_once():
    """A launch-cache miss counts ``engine.launch.miss`` and puts its
    compile in an ``engine.compile`` span under the call; a hit adds
    neither."""
    from repro import engine
    u = make_laplace_problem(18, 42, dtype=np.float32, left=1.0)
    kw = dict(policy="temporal", iters=12, t=4, interpret=True)
    tracer = Tracer()
    before = metrics.counter("engine.launch.miss").value
    with use_tracer(tracer):
        engine.run(u, jacobi_2d_5pt(), **kw)
        engine.run(u, jacobi_2d_5pt(), **kw)
    assert metrics.counter("engine.launch.miss").value == before + 1
    (comp,) = [e for e in tracer.events if e.name == "engine.compile"]
    assert comp.path == ("engine.run", "engine.compile")
    assert comp.attrs["launch"] == "run"


@pytest.mark.parametrize("policy", ["shifted", "rowchunk", "dbuf",
                                    "temporal"])
def test_every_kernel_is_named_with_the_program_prefix(policy):
    """The device operation's name is the ``pallas_call``'s: each one is
    the documented prefix plus its policy, not the wrapping function's."""
    from repro import engine
    from repro.engine.policies import KERNEL_PREFIX

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    u = make_laplace_problem(18, 34, dtype=np.float32, left=1.0)
    jaxpr = jax.make_jaxpr(lambda v: engine.run(
        v, jacobi_2d_5pt(), policy=policy, iters=4, t=4,
        interpret=True))(u)
    names = {e.params["name"] for e in eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert KERNEL_PREFIX == "stencil_"
    assert names == {KERNEL_PREFIX + policy}


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def test_converged_launch_names_residual_and_writeback():
    """The compiled ``run_converged`` program carries its phases in every
    operation's ``op_name``: the residual and exit test, and the kernel's
    write-back."""
    from repro.engine import dispatch
    from repro.engine.schedule import build_schedule
    spec = jacobi_2d_5pt()
    u = make_laplace_problem(18, 34, dtype=np.float32, left=1.0)
    sched = build_schedule(4, spec=spec, shape=u.shape, dtype=u.dtype,
                           policy="temporal", t=4, interpret=True)
    hlo = dispatch._converged_program(
        sched, spec, None, True, None, 3, False).lower(
        u, jnp.float32(1e-3)).compile().as_text()
    names = _op_names(hlo)
    assert any("/solver.residual/reduce_max" in n for n in names), names
    assert any("/solver.residual/" in n and "while/cond" in n
               for n in names), names               # the exit test
    assert any("/stencil.writeback/" in n for n in names), names


# ---------------------------------------------------------------------------
# Span tree + Chrome trace export
# ---------------------------------------------------------------------------

def test_span_tree_chrome_export_and_reload(tmp_path):
    tracer = Tracer()
    with use_tracer(tracer):
        with obs.span("outer", which="a"):
            with obs.span("inner") as sp:
                sp.set(found=3)
            with obs.span("inner"):
                pass
        tracer.counter("track", {"x": 1.0, "y": 2.0})

    chrome = tracer.to_chrome()
    assert set(chrome) == {"traceEvents", "displayTimeUnit"}
    evs = chrome["traceEvents"]
    assert len(evs) == 4  # 3 spans + 1 counter sample
    for ev in evs:  # the well-formedness CI validates on real traces
        assert ev["ph"] in ("X", "C")
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["pid"], int)
    inner = [e for e in evs if e["name"] == "inner"]
    assert all(e["args"]["_path"] == "outer/inner" for e in inner)
    assert inner[0]["args"]["found"] == 3

    # Reloading from disk must normalize to the same span records.
    path = str(tmp_path / "trace.json")
    tracer.write_trace(path)
    live = span_records(tracer)
    reloaded = span_records(path)
    assert [r["name"] for r in reloaded] != []
    assert {(r["name"], r["path"]) for r in reloaded} == \
        {(r["name"], r["path"]) for r in live}

    summary = tracer.summary()
    assert summary[("outer", "inner")]["count"] == 2
    assert "inner" in tracer.describe()


def test_sink_sees_every_finished_span():
    seen = []
    tracer = Tracer(sink=seen.append)
    with use_tracer(tracer):
        with obs.span("a"):
            with obs.span("b"):
                pass
    assert [e.name for e in seen] == ["b", "a"]  # close order


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_histogram_percentiles_match_numpy():
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (50, 95, 99):
        assert metrics.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    reg = metrics.MetricsRegistry()
    h = reg.histogram("lat")
    for x in xs:
        h.observe(x)
    s = h.summary()
    assert s["count"] == len(xs) and s["min"] == 1.0 and s["max"] == 9.0
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    reg.gauge("depth").set(7)
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 3.0
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat"]["count"] == len(xs)
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_plan_cache_counters_count_hit_and_miss():
    from repro.engine.plan import plan_for
    u_shape, spec = (20, 36), jacobi_2d_5pt()
    kw = dict(t=3, device="grayskull_e150", masked=False)
    plan_for(u_shape, jnp.float32, spec, "temporal", **kw)  # prime
    before = dict(metrics.snapshot()["counters"])
    plan_for(u_shape, jnp.float32, spec, "temporal", **kw)
    after = metrics.snapshot()["counters"]
    assert after["engine.plan.hit"] == before.get("engine.plan.hit", 0) + 1
    assert after.get("engine.plan.miss", 0) == before.get(
        "engine.plan.miss", 0)


def test_time_fn_routes_samples_through_metrics(monkeypatch):
    from benchmarks.common import time_fn
    monkeypatch.delenv("REPRO_BENCH_DRY", raising=False)
    name = "test.obs.time_fn_s"
    metrics.REGISTRY.histograms.pop(name, None)
    out = time_fn(lambda: jnp.zeros(()), iters=4, warmup=1, metric=name)
    assert out > 0.0
    assert metrics.histogram(name).summary()["count"] == 4
    # Dry mode times nothing and therefore observes nothing.
    monkeypatch.setenv("REPRO_BENCH_DRY", "1")
    assert time_fn(lambda: jnp.zeros(()), iters=4, metric=name) == 0.0
    assert metrics.histogram(name).summary()["count"] == 4


# ---------------------------------------------------------------------------
# Reconciliation
# ---------------------------------------------------------------------------

def _rec(name, dur_us, **attrs):
    """A synthetic Chrome-trace complete event, as reconcile consumes."""
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us,
            "pid": 1, "tid": 1, "args": dict(attrs, _path=name)}


def test_reconcile_fires_obs_drift_on_perturbed_duration():
    """A span whose measured duration matches its model is clean; the
    same span with its duration perturbed 10x fires OBS-DRIFT."""
    clean = [_rec("exchange", 1000.0, model_s=1e-3)]
    rep = obs.reconcile(clean, tolerance=2.0)
    assert rep.report.ok and not rep.drifting
    (comp,) = rep.components
    assert comp.ratio == pytest.approx(1.0)

    perturbed = [_rec("exchange", 10_000.0, model_s=1e-3)]
    rep = obs.reconcile(perturbed, tolerance=2.0)
    (comp,) = rep.drifting
    assert comp.ratio == pytest.approx(10.0)
    assert [d.code for d in rep.report.warnings] == ["OBS-DRIFT"]
    assert rep.report.ok  # warning severity: drift reports, never gates
    assert "x10.00" in rep.describe()


def test_reconcile_unmodeled_trace_is_visible_not_silent():
    rep = obs.reconcile([_rec("serve.block", 500.0)])
    assert not rep.components
    assert [d.code for d in rep.report.diagnostics] == ["OBS-UNMODELED"]
    # Non-positive models are called out per component, too.
    rep = obs.reconcile([_rec("exchange", 500.0, model_s=0.0)])
    assert [d.code for d in rep.report.diagnostics] == ["OBS-UNMODELED"]


def test_reconcile_distributed_codes_are_registered():
    from repro.analysis.diagnostics import CODES
    assert "OBS-DRIFT" in CODES and "OBS-UNMODELED" in CODES


# ---------------------------------------------------------------------------
# Instrumented surfaces: serve + sim
# ---------------------------------------------------------------------------

def test_serve_records_block_spans_and_counters():
    from repro.serve import SolveRequest, SolveServer
    spec = jacobi_2d_5pt()
    tracer = Tracer()
    srv = SolveServer(max_slots=2, interpret=True, tracer=tracer)
    reqs = [SolveRequest(grid=make_laplace_problem(16, 16, left=1.0),
                         spec=spec, tol=3e-3, max_iters=96,
                         policy="temporal", t=8)
            for _ in range(3)]
    before = metrics.snapshot()["counters"].get("serve.admitted", 0)
    srv.solve(reqs)
    blocks = [e for e in tracer.events if e.name == "serve.block"]
    assert blocks, "serve.step must span every bucket launch"
    for e in blocks:
        assert 0 < e.attrs["active"] <= 2
        assert e.attrs["max_residual"] >= 0.0
    assert len([e for e in tracer.events if e.name == "serve.submit"]) == 3
    after = metrics.snapshot()
    assert after["counters"]["serve.admitted"] == before + 3
    assert after["gauges"]["serve.active_slots"] == 0.0  # drained
    slots = [c for c in tracer.counters if c.name == "serve.slots"]
    assert slots and set(slots[0].values) == {"active", "queue"}


def test_sim_simulate_span_carries_model_and_core_tracks():
    from repro import backends
    u = make_laplace_problem(18, 34, left=1.0)
    tracer = Tracer()
    with use_tracer(tracer):
        res = backends.simulate(u, jacobi_2d_5pt(), policy="rowchunk",
                                iters=2, device="grayskull_e150")
    (sim_ev,) = [e for e in tracer.events if e.name == "sim.simulate"]
    assert sim_ev.attrs["model_s"] == pytest.approx(res.model_time_s)
    tracks = {c.name for c in tracer.counters}
    assert {"sim.core_busy_s", "sim.cb_occupancy"} <= tracks
    # And the whole simulation is bit-identical with the tracer off.
    res_off = backends.simulate(u, jacobi_2d_5pt(), policy="rowchunk",
                                iters=2, device="grayskull_e150")
    np.testing.assert_array_equal(np.asarray(res.grid),
                                  np.asarray(res_off.grid))


# ---------------------------------------------------------------------------
# Distributed: bit-exact with obs on vs off (forced host devices)
# ---------------------------------------------------------------------------

DIST_SCRIPT = """
import numpy as np, jax
from repro import engine
from repro.core.stencil import jacobi_2d_5pt, make_laplace_problem
from repro.dist import stencil as dstencil
from repro.obs import metrics, reconcile
from repro.obs.trace import Tracer, use_tracer

u = make_laplace_problem(34, 130, dtype=np.float32, left=1.0)
spec = jacobi_2d_5pt()
mesh = jax.make_mesh((2,), ("x",))
kw = dict(mesh=mesh, policy="temporal", iters=10, t=4, interpret=True)

for overlap in (False, True):
    off = np.asarray(engine.run_distributed(u, spec, overlap=overlap, **kw))
    launches = dict(dstencil._SCAN_LAUNCHES)
    misses = metrics.counter("engine.launch.miss").value
    tracer = Tracer()
    with use_tracer(tracer):
        on = np.asarray(engine.run_distributed(u, spec, overlap=overlap,
                                               **kw))
    assert (on == off).all(), f"overlap={overlap}: traced run diverged"
    # The one executor: the traced call reuses the untraced launch.
    assert dstencil._SCAN_LAUNCHES == launches
    assert metrics.counter("engine.launch.miss").value == misses
    names = [e.name for e in tracer.events]
    assert "engine.compile" not in names, names
    (run,) = [e for e in tracer.events if e.name == "dist.run"]
    assert run.attrs["overlap"] is overlap
    assert run.attrs["exchanges"] == 3      # 2 fused rounds + remainder
    assert run.attrs["strip_rows"] == 0 and run.attrs["recompute"] >= 1
    assert run.attrs["model_s"] > 0 and run.attrs["halo_bytes"] > 0
    assert run.attrs["model_s"] == (run.attrs["model_overlapped_s"]
                                    if overlap else
                                    run.attrs["model_serial_s"])
    rep = reconcile(tracer)
    comps = {c.component: c for c in rep.components}
    assert comps["dist.run"].spans == 1, comps
    assert comps["dist.run"].modeled_s == run.attrs["model_s"]
    # Interpret-mode CPU vs a modeled chip: drift is the information.
    assert rep.report.ok
    print(f"overlap={overlap} ok: {sorted(comps)}")
print("OBS DIST OK")
"""

DIST_HLO_SCRIPT = """
import re
import numpy as np, jax
from repro import engine
from repro.core.stencil import make_laplace_problem

u = make_laplace_problem(34, 130, dtype=np.float32, left=1.0)
mesh = jax.make_mesh((2,), ("x",))
for overlap in (False, True):
    hlo = jax.jit(lambda v: engine.run_distributed(
        v, mesh=mesh, policy="temporal", iters=10, t=4, interpret=True,
        overlap=overlap)).lower(u).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = {s for n in names for s in n.split("/")
              if s.startswith(("exchange.", "stencil."))}
    want = {"exchange.halo", "stencil.writeback"} | (
        {"exchange.interior", "exchange.rind"} if overlap else set())
    assert want <= scopes, (overlap, scopes)
    permutes = [l for l in hlo.splitlines() if "collective-permute" in l
                and "op_name=" in l]
    assert permutes and all("/exchange.halo/" in l for l in permutes)
    print(f"overlap={overlap} ok: {sorted(scopes)}")
print("DIST HLO OK")
"""


def _run_on_two_devices(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_run_distributed_bit_identical_obs_on_vs_off():
    """One executor: with a tracer installed ``run_distributed`` runs the
    same cached one-launch program, bit-identical, as one ``dist.run``
    span that carries the launch's modeled bill for ``reconcile``."""
    assert "OBS DIST OK" in _run_on_two_devices(DIST_SCRIPT)


@pytest.mark.slow
def test_run_distributed_hlo_names_its_phases():
    """The compiled distributed program carries the exchange, overlap and
    write-back phases in its operations' ``op_name``; every
    ``collective-permute`` sits under ``exchange.halo``."""
    assert "DIST HLO OK" in _run_on_two_devices(DIST_HLO_SCRIPT)
