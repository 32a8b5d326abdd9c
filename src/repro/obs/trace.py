"""Contextvar-scoped tracing: nested spans on the profiler's clock.

Stdlib only at import, and **disabled by default**: with no
:class:`Tracer` installed and no JAX profiler session capturing, the
module-level :func:`span` returns one shared no-op singleton — no
allocation, no clock read; its only cost beyond the contextvar lookup is
one ``TraceAnnotation.is_enabled()`` check — so instrumented hot paths
(``engine.run``, ``run_distributed``, ``serve.step``) cost nothing when
nobody is watching. Tests pin both properties: ``obs.span("a") is
obs.span("b")`` with nothing watching, and bit-identical engine output
with obs on vs off.

A span has two observers, either or both of which may be live:

* **The JAX profiler** (``jax.profiler.trace`` / ``start_trace``): the
  span opens a ``jax.profiler.TraceAnnotation`` of the same name carrying
  its attrs as metadata, so it lands in the capture's host plane on the
  same clock as the device operations it launched — the view to open in
  xprof or Perfetto. ``jax`` is imported only when the first span asks.
* **A Tracer** (``set_tracer`` / ``use_tracer``): ``with span(name,
  **attrs) as sp`` records a frozen :class:`SpanEvent` on exit (start and
  duration in microseconds since the tracer's epoch, the nesting path,
  and the attrs — ``sp.set(...)`` adds more mid-span, e.g. a resolved
  policy or a modeled bill). Counter *tracks* (:meth:`Tracer.counter`)
  record time series like per-core busy seconds.
  :meth:`Tracer.write_trace` exports Chrome-trace JSON and
  :meth:`Tracer.describe` a summary tree aggregated by span path.

Spans attach model predictions via the ``model_s`` attr (seconds the
pricing layer expected the span to take); :func:`repro.obs.compare.
reconcile` joins those against the measured durations. A ``sink``
callable receives every finished :class:`SpanEvent` as it closes —
``launch/solve.py --serve`` uses this for live per-block progress lines.

A span entered *inside* a ``jax.jit`` trace measures trace time (schedule
resolution, lowering), and a span around an asynchronous launch measures
its dispatch; what the device did in a phase of a launch is read from
the profiler's device trace, under the ``jax.named_scope`` names the
engine gives its phases.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import json
import os
import threading
import time

_TRACER: contextvars.ContextVar["Tracer | None"] = contextvars.ContextVar(
    "repro_obs_tracer", default=None)


def _jsonable(v):
    """Coerce an attr value into something json.dump accepts verbatim."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One finished span: what ran, where in the tree, for how long."""

    name: str
    path: tuple[str, ...]     # names from root to this span
    ts_us: float              # start, microseconds since tracer epoch
    dur_us: float
    pid: int
    tid: int
    attrs: dict


@dataclasses.dataclass(frozen=True)
class CounterEvent:
    """One sample of a counter track (Chrome ``ph: "C"``)."""

    name: str
    ts_us: float
    values: dict              # series name -> numeric value
    pid: int
    tid: int


class _NullSpan:
    """The disabled path: one shared instance, every method a no-op."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


@functools.cache
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    ``repro.obs`` stays importable from the engine's lowest layers."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def _profiling() -> bool:
    """Whether a JAX profiler session is capturing host events."""
    return _annotation().is_enabled()


class Span:
    """A live span: a profiler annotation while ``annotate``, and a
    :class:`SpanEvent` recorded on ``__exit__`` when ``tracer`` is set."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_annotate",
                 "_annotation")

    def __init__(self, tracer: "Tracer | None", name: str, attrs: dict,
                 annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._annotate = annotate
        self._annotation = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self

    def __enter__(self) -> "Span":
        if self._annotate:
            self._annotation = _annotation()(self.name, **self.attrs)
            self._annotation.__enter__()
        if self._tracer is not None:
            self._tracer._stack.append(self.name)
            self._t0 = self._tracer._now_us()
        return self

    def __exit__(self, *exc) -> bool:
        tracer = self._tracer
        if tracer is not None:
            t1 = tracer._now_us()
            path = tuple(tracer._stack)
            tracer._stack.pop()
            tracer._emit(SpanEvent(
                name=self.name, path=path, ts_us=self._t0,
                dur_us=t1 - self._t0, pid=tracer.pid,
                tid=threading.get_ident() & 0x7FFFFFFF,
                attrs={k: _jsonable(v) for k, v in self.attrs.items()}))
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        return False


class Tracer:
    """Collects span + counter events; export via :meth:`write_trace`.

    ``sink``, if given, is called with every :class:`SpanEvent` as it
    closes (live progress reporting); sink exceptions propagate — a
    broken sink is a caller bug, not something to swallow silently.
    """

    def __init__(self, *, sink=None):
        self.events: list[SpanEvent] = []
        self.counters: list[CounterEvent] = []
        self.sink = sink
        self.pid = os.getpid()
        self._epoch = time.perf_counter()
        self._stack: list[str] = []

    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def _emit(self, event: SpanEvent) -> None:
        self.events.append(event)
        if self.sink is not None:
            self.sink(event)

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs, _profiling())

    def counter(self, name: str, values: dict, *,
                ts_us: float | None = None) -> None:
        """Record one sample of a counter track (``values`` is
        ``{series: number}`` — multiple series share one track)."""
        self.counters.append(CounterEvent(
            name=name, ts_us=self._now_us() if ts_us is None else ts_us,
            values={str(k): float(v) for k, v in values.items()},
            pid=self.pid, tid=threading.get_ident() & 0x7FFFFFFF))

    # ------------------------------------------------------------ export

    def to_chrome(self) -> dict:
        """The Chrome-trace JSON object (``traceEvents`` array format)."""
        evs = []
        for e in self.events:
            evs.append({"name": e.name, "cat": "repro", "ph": "X",
                        "ts": round(e.ts_us, 3), "dur": round(e.dur_us, 3),
                        "pid": e.pid, "tid": e.tid,
                        "args": dict(e.attrs, _path="/".join(e.path))})
        for c in self.counters:
            evs.append({"name": c.name, "cat": "repro", "ph": "C",
                        "ts": round(c.ts_us, 3), "pid": c.pid, "tid": c.tid,
                        "args": dict(c.values)})
        evs.sort(key=lambda ev: ev["ts"])
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def write_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
            f.write("\n")

    def summary(self) -> dict:
        """Aggregate stats per span path: ``{path_tuple: {count,
        total_us, min_us, max_us}}`` — the structured summary tree."""
        return summarize_spans(span_records(self))

    def describe(self) -> str:
        return describe_summary(self.summary())


# ---------------------------------------------------------------- module API

def get_tracer() -> Tracer | None:
    return _TRACER.get()


def set_tracer(tracer: Tracer | None) -> Tracer | None:
    """Install ``tracer`` for the current context (None disables)."""
    _TRACER.set(tracer)
    return tracer


@contextlib.contextmanager
def use_tracer(tracer: Tracer | None):
    """Scoped install: spans inside the ``with`` record into ``tracer``."""
    token = _TRACER.set(tracer)
    try:
        yield tracer
    finally:
        _TRACER.reset(token)


def span(name: str, **attrs):
    """A span against the installed tracer and any capturing profiler
    session — or the shared no-op when neither is watching (the disabled
    path allocates nothing)."""
    tracer = _TRACER.get()
    annotate = _profiling()
    if tracer is None and not annotate:
        return NULL_SPAN
    return Span(tracer, name, attrs, annotate)


def counter(name: str, values: dict) -> None:
    """Record a counter-track sample on the installed tracer (no-op
    when none is installed)."""
    tracer = _TRACER.get()
    if tracer is not None:
        tracer.counter(name, values)


def write_trace(path: str) -> None:
    """Write the installed tracer's Chrome trace to ``path``."""
    tracer = _TRACER.get()
    if tracer is None:
        raise RuntimeError("obs.write_trace: no tracer installed "
                           "(set_tracer/use_tracer first)")
    tracer.write_trace(path)


# ------------------------------------------------------- trace normalization

def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def span_records(source) -> list[dict]:
    """Normalize a trace into span records.

    ``source`` may be a live :class:`Tracer`, a Chrome-trace dict, a raw
    ``traceEvents`` list, or a path to a trace file. Returns
    ``[{"name", "path", "dur_us", "attrs"}, ...]`` — the shape
    :func:`repro.obs.compare.reconcile` and the CLI summarize consume,
    identical whether the trace is in memory or reloaded from disk.
    """
    if isinstance(source, Tracer):
        return [{"name": e.name, "path": e.path, "dur_us": e.dur_us,
                 "attrs": dict(e.attrs)} for e in source.events]
    if isinstance(source, str):
        source = load_trace(source)
    events = source.get("traceEvents", []) if isinstance(source, dict) \
        else source
    recs = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        attrs = dict(ev.get("args") or {})
        path = tuple(str(attrs.pop("_path", ev.get("name", ""))).split("/"))
        recs.append({"name": ev.get("name", ""), "path": path,
                     "dur_us": float(ev.get("dur", 0.0)), "attrs": attrs})
    return recs


def counter_records(source) -> list[dict]:
    """Counter-track samples from a trace (same sources as
    :func:`span_records`): ``[{"name", "ts_us", "values"}, ...]``."""
    if isinstance(source, Tracer):
        return [{"name": c.name, "ts_us": c.ts_us, "values": dict(c.values)}
                for c in source.counters]
    if isinstance(source, str):
        source = load_trace(source)
    events = source.get("traceEvents", []) if isinstance(source, dict) \
        else source
    return [{"name": ev.get("name", ""), "ts_us": float(ev.get("ts", 0.0)),
             "values": dict(ev.get("args") or {})}
            for ev in events if ev.get("ph") == "C"]


def summarize_spans(records: list[dict]) -> dict:
    """Aggregate span records per path (the structured summary tree)."""
    agg: dict[tuple, dict] = {}
    for rec in records:
        node = agg.setdefault(rec["path"], {
            "count": 0, "total_us": 0.0, "min_us": float("inf"),
            "max_us": 0.0})
        node["count"] += 1
        node["total_us"] += rec["dur_us"]
        node["min_us"] = min(node["min_us"], rec["dur_us"])
        node["max_us"] = max(node["max_us"], rec["dur_us"])
    return agg


def describe_summary(summary: dict) -> str:
    """Render a path-aggregated summary as an indented tree."""
    if not summary:
        return "trace: no spans recorded"
    lines = ["span tree (count, total, mean):"]
    for path in sorted(summary):
        node = summary[path]
        mean = node["total_us"] / max(node["count"], 1)
        indent = "  " * (len(path) - 1)
        lines.append(f"  {indent}{path[-1]:<{max(28 - len(indent), 1)}s} "
                     f"x{node['count']:<4d} {node['total_us'] / 1e3:10.2f} ms "
                     f"(mean {mean / 1e3:8.3f} ms)")
    return "\n".join(lines)
