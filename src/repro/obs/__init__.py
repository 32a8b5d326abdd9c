"""repro.obs — spans, metrics, and model-vs-measured reconciliation.

Zero-dependency, disabled-by-default observability for the whole stack:

* :mod:`repro.obs.trace` — nested ``span(name, **attrs)`` context
  managers. Under a JAX profiler session each span is a
  ``TraceAnnotation`` on the device trace's clock; with a contextvar-
  scoped :class:`Tracer` installed it is recorded for Chrome-trace JSON
  and a structured summary tree. With neither, ``obs.span`` returns a
  shared no-op — instrumentation costs nothing.
* :mod:`repro.obs.metrics` — process-local counters/gauges/histograms
  (p50/p95/p99) with a JSON snapshot.
* :mod:`repro.obs.compare` — ``reconcile(trace)`` joins measured span
  durations against the modeled bills attached to them and reports
  per-component drift as structured ``OBS-*`` diagnostics.

Instrumented surfaces: ``engine.run`` / ``run_converged`` /
``build_schedule`` / ``plan_for`` / ``tune`` (spans + cache hit/miss
counters), ``engine.compile`` spans and the ``engine.launch.miss`` counter
on every launch-cache miss, ``run_distributed`` (one ``dist.run`` span
carrying the launch's :class:`~repro.engine.schedule.ExchangeBill`),
``serve.SolveServer`` (per-block spans, slot/queue/residual gauges,
admission counters), and ``backends.sim`` (per-core busy + per-CB
occupancy counter tracks). Drive it with ``launch/solve.py --trace
out.json`` and inspect with ``python -m repro.obs summarize out.json``;
for the spans beside the device's operations, wrap a call in
``jax.profiler.trace(dir)`` and open the capture in xprof or Perfetto.
"""
from repro.obs import metrics  # noqa: F401
from repro.obs.compare import (ComponentDrift, DriftReport,  # noqa: F401
                               reconcile)
from repro.obs.trace import (NULL_SPAN, CounterEvent, Span,  # noqa: F401
                             SpanEvent, Tracer, counter, counter_records,
                             get_tracer, load_trace, set_tracer, span,
                             span_records, summarize_spans, use_tracer,
                             write_trace)

__all__ = [
    "ComponentDrift", "CounterEvent", "DriftReport", "NULL_SPAN", "Span",
    "SpanEvent", "Tracer", "counter", "counter_records", "get_tracer",
    "load_trace", "metrics", "reconcile", "set_tracer", "span",
    "span_records", "summarize_spans", "use_tracer", "write_trace",
]
