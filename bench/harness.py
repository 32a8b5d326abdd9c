"""One run of one benchmark cell.

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``bench/configs/<config>.json``) and a traffic file
(``bench/traffic/<traffic>.json``); each per-layer metric is read by the
module ``bench/metrics/<metric name>.py``, or, for a metric split by the
end-to-end metric it moves (``idle_share.fixed``), by the module named
before the first dot (``idle_share.py``). Adding a cell, a configuration,
a traffic mix or a metric adds files and entries; no code here changes.

A run: check the chips, build the system, make the inputs from the seed
and warm every program (set-up), run the window (traced with ``--trace
1``), read the peak device memory, compare what the window produced with
the plain reference, and print one JSON line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_from_files(name: str) -> Cell:
    """Cell ``<config>.<traffic>`` from its configuration and traffic
    files alone, with no metrics: for the one-off scripts, and for a cell
    that ``BENCHMARK.json`` does not list yet."""
    config, traffic = name.split(".", 1)
    cfg = load_json(os.path.join(BENCH, "configs", config + ".json"))
    return Cell(name=name, chips=cfg["chips"], config=cfg,
                traffic=load_json(os.path.join(BENCH, "traffic",
                                               traffic + ".json")),
                end_to_end=[], per_layer=[])


def load_cell(name: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(os.path.join(ROOT, cfg["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def metric_reader(name: str):
    """The ``read(run)`` function of a per-layer metric, found by name."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench.metrics.{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{os.path.join(BENCH, 'metrics')}")


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(has {sorted(table)}): measure its peaks first")
    return table[kind]


def use_chips(chips: int):
    """JAX's devices, after checking there are ``chips`` TPU chips, with
    the persistent compilation cache at its fixed place in the checkout."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"needs a TPU; JAX found {backend!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chips; found {len(devs)}")
    return devs


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    inside its ``with`` block."""

    def __init__(self):
        import jax.monitoring as mon
        from jax._src import dispatch
        self.compiles = 0
        self.loads = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self._mon = mon

    def __enter__(self):
        self._mon.register_event_duration_secs_listener(self._on_duration)
        self._mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._event:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    @property
    def fresh(self) -> int:
        return self.compiles - self.loads


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader may read."""

    trace: object            # trace_reduce.Trace
    work: dict               # ops, bytes, sweeps of each solve
    counters: dict
    peaks: dict
    chips: int


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices=None) -> dict:
    """One run; returns the result object (the last line printed).
    ``devices`` skips the look for chips (tests pass the CPU's)."""
    devs = use_chips(cell.chips) if devices is None else devices
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax
    from bench import loads, trace_reduce, verify

    system = loads.System(cell.config, devs)
    load = loads.LOADS[cell.traffic["kind"]](system, cell.traffic)
    load.setup(seed, seconds)
    setup_s = time.perf_counter() - t_start

    tr = None
    with CompileCounter() as counter:
        if trace:
            with tempfile.TemporaryDirectory() as d:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(d, profiler_options=opts)
                try:
                    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                        load.window(seconds)
                finally:
                    jax.profiler.stop_trace()
                tr = trace_reduce.read_xplane(
                    d, {dv.id for dv in system.devices})
        else:
            load.window(seconds)
    print(f"window: {load.window_s:.4f} s, {load.attempted} attempted, "
          f"{load.failed} failed; programs compiled in it "
          f"{counter.fresh}, loaded from the cache {counter.loads}; "
          f"generator late by up to {load.lateness_s:.4f} s",
          file=sys.stderr)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in system.devices)
    answers = load.answers
    load.release()
    nums = verify.numbers(answers, cell.config, system.devices[0],
                          missing=load.failed)
    correct, compared = verify.judge(nums, cell.config["limits"])

    metrics: dict = {}
    if trace:
        view = RunView(trace=tr, work=load.work, counters=load.counters,
                       peaks=peaks_for(devs[0].device_kind),
                       chips=cell.chips)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = load.end_to_end()
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": load.attempted,
              "failed": load.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                               "idle_gaps": trace_reduce.idle_gaps(tr)}
    for k, v in compared.items():
        print(f"compared {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    result["compared"] = compared
    return result
