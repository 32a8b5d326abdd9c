"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced first to a plain ``Trace``: for each device, the
intervals in which an operation ran on it, with the operation's name; the
host's annotations; and the measured window. Everything after that is
interval arithmetic on those lists, so it is tested on small synthetic
traces without a chip.

* busy: the union of a device's operation intervals inside the window,
  averaged over the devices used; idle share is 1 - busy / window.
* exposed collective time: the part of the union of a device's collective
  operations during which no other operation runs on that device.
* breakdown: the operations that took most device time, and the longest
  idle gaps by the host annotation that covers them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: Device operations that move data between chips.
COLLECTIVE = re.compile(
    r"collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all",
    re.IGNORECASE)

#: Device operations that only contain others (a loop, a branch, a call):
#: their time is their children's, so they neither count as a separate
#: operation in the breakdown nor hide a collective that runs inside them.
CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")

#: Host annotation that marks the measured window.
WINDOW = "bench.window"

Interval = tuple[float, float]


@dataclasses.dataclass
class Trace:
    """Times in seconds on one clock. ``devices`` maps a device name to
    its ``(op, start, end)`` events, ``op`` being the operation's HLO text;
    ``asyncs`` the same for the device's asynchronous operations (copies
    and transfers in flight beside the others); ``host`` lists the
    benchmark's own ``(annotation, start, end)`` spans."""

    window: Interval
    devices: dict[str, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]
    asyncs: dict[str, list[tuple[str, float, float]]] = dataclasses.field(
        default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def union(intervals, lo: float, hi: float) -> list[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: list[Interval], cut: list[Interval]) -> list[Interval]:
    """``base`` minus ``cut``; both sorted and disjoint."""
    out = []
    j = 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        cur = a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window
    if not trace.devices:
        return 0.0
    return sum(length(union(((s, e) for _, s, e in evs), lo, hi))
               for evs in trace.devices.values()) / len(trace.devices)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def exposed_collective_s(trace: Trace, pattern=COLLECTIVE) -> float | None:
    """Seconds in which a collective runs on a device and nothing else
    does, averaged over the devices; None where no collective ran."""
    lo, hi = trace.window
    total, seen = 0.0, False
    for dev, evs in trace.devices.items():
        coll = [(s, e) for n, s, e in evs + trace.asyncs.get(dev, [])
                if pattern.search(n)]
        if not coll:
            continue
        seen = True
        other = union(((s, e) for n, s, e in evs
                       if not pattern.search(n) and not CONTAINER.search(n)),
                      lo, hi)
        total += length(subtract(union(coll, lo, hi), other))
    if not seen:
        return None
    return total / len(trace.devices)


def op_name(hlo: str) -> str:
    """``%stencil_temporal.3 = f32[...] custom-call(...)`` ->
    ``%stencil_temporal.3``."""
    return hlo.split(" = ", 1)[0]


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """``[[op name, seconds], ...]``: device time by operation name,
    clipped to the window and averaged over devices, largest first.
    Containers are left out: their time is their children's."""
    lo, hi = trace.window
    acc: dict[str, float] = {}
    for evs in trace.devices.values():
        for n, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0 and not CONTAINER.search(n):
                key = op_name(n)
                acc[key] = acc.get(key, 0.0) + d / len(trace.devices)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """``[[host annotation, seconds], ...]``: the device's idle time inside
    the window, attributed to the innermost benchmark annotation that
    covers the middle of each gap ("unannotated" where none does), summed
    per annotation and averaged over devices, largest first."""
    lo, hi = trace.window
    acc: dict[str, float] = {}
    host = [h for h in trace.host if h[0] != WINDOW]
    for evs in trace.devices.values():
        gaps = subtract([(lo, hi)], union(((s, e) for _, s, e in evs),
                                          lo, hi))
        for a, b in gaps:
            mid = (a + b) / 2
            covering = [h for h in host if h[1] <= mid <= h[2]]
            name = (min(covering, key=lambda h: h[2] - h[1])[0]
                    if covering else "unannotated")
            acc[name] = acc.get(name, 0.0) + (b - a) / len(trace.devices)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


# ------------------------------------------------------------ reading


def read_xplane(log_dir: str, used: set[int]) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``log_dir`` to a :class:`Trace`.

    Device planes are those named ``/device:<KIND>:<n>`` with ``n`` in
    ``used``; their operations are the events of the line named ``XLA
    Ops``, their asynchronous ones those of ``Async XLA Ops``. The window
    is the host annotation :data:`WINDOW`. Raises when the trace lacks it
    or a used device.
    """
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}; "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    devices: dict[str, list] = {}
    asyncs: dict[str, list] = {}
    host: list = []
    window = None
    for plane in pd.planes:
        m = re.match(r"/device:(TPU|GPU):(\d+)$", plane.name)
        if m and int(m.group(2)) in used:
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                into = {"XLA Ops": evs, "Async XLA Ops": asyncs.setdefault(
                    plane.name, [])}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    into.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    s = ev.start_ns * 1e-9
                    span = (ev.name, s, s + ev.duration_ns * 1e-9)
                    if ev.name == WINDOW:
                        window = span[1:]
                    host.append(span)
    if window is None:
        raise RuntimeError(f"trace has no {WINDOW!r} annotation")
    if len(devices) != len(used):
        raise RuntimeError(f"trace holds devices {sorted(devices)}; the "
                           f"run used {sorted(used)}")
    return Trace(window=window, devices=devices, host=host, asyncs=asyncs)
