"""Reduction of a profiler trace to device numbers.

A trace is flattened to ``Event(plane, line, name, t0, t1, stats)`` with
times in seconds on the profiler's clock. Device planes are the
``/device:<kind>:<n>`` planes; on each, the ``XLA Ops`` line holds one event
per executed operation and the ``XLA Modules`` line one per executed
program. Host spans that the benchmark annotates (``bench.<name>``) lie on
the host planes, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

OPS = "XLA Ops"
MODULES = "XLA Modules"
CONTAINER = re.compile(r"%?(while|conditional|call)\b")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    t0: float
    t1: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def stat(self, key: str) -> Optional[str]:
        for k, v in self.stats:
            if k == key:
                return v
        return None


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                stats = tuple((str(k), str(v)) for k, v in e.stats)
                t0 = e.start_ns * 1e-9
                out.append(Event(plane.name, line.name, e.name, t0,
                                 t0 + e.duration_ns * 1e-9, stats))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if is_device(e.plane)})


def window(events: Sequence[Event], name: str = "bench.window"
           ) -> Tuple[float, float]:
    spans = [e for e in events if e.name == name and not is_device(e.plane)]
    if not spans:
        raise ValueError(f"no {name} span in the trace")
    w = max(spans, key=lambda e: e.dur)
    return w.t0, w.t1


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        a, b = max(e.t0, lo), min(e.t1, hi)
        if b > a:
            out.append(dataclasses.replace(e, t0=a, t1=b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def ops(events: Sequence[Event], plane: str, lo: float, hi: float
        ) -> List[Event]:
    return clip((e for e in events if e.plane == plane and e.line == OPS),
                lo, hi)


def busy_s(events: Sequence[Event], lo: float, hi: float) -> float:
    """Seconds in which some operation ran, averaged over device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = 0.0
    for p in planes:
        tot += sum(b - a for a, b in union((e.t0, e.t1)
                                           for e in ops(events, p, lo, hi)))
    return tot / len(planes)


def modules(events: Sequence[Event], pattern: str, lo: float, hi: float
            ) -> List[Event]:
    """Executions of the programs whose name matches ``pattern`` that start
    inside the window, on the first device plane."""
    planes = device_planes(events)
    if not planes:
        return []
    rx = re.compile(pattern)
    return [e for e in events if e.plane == planes[0] and e.line == MODULES
            and rx.search(e.name) and lo <= e.t0 < hi]


def kernel_calls(events: Sequence[Event], pattern: str, lo: float,
                 hi: float) -> List[Event]:
    """Operation events whose name (or ``long_name``) matches ``pattern``,
    starting inside the window, on the first device plane."""
    planes = device_planes(events)
    if not planes:
        return []
    rx = re.compile(pattern)
    return [e for e in events if e.plane == planes[0] and e.line == OPS
            and lo <= e.t0 < hi
            and (rx.search(e.name) or rx.search(e.stat("long_name") or ""))]


def short(name: str) -> str:
    """An operation's HLO name without its instruction text."""
    return name.split(" = ", 1)[0]


def top_ops(events: Sequence[Event], lo: float, hi: float, n: int = 10
            ) -> List[list]:
    """Device operations that took most time (summed by name, averaged over
    the device planes). Loops, conditionals and calls contain other
    operations of the line, so they are left out."""
    planes = device_planes(events)
    tot = {}
    for p in planes:
        for e in ops(events, p, lo, hi):
            name = short(e.name)
            if CONTAINER.match(name):
                continue
            tot[name] = tot.get(name, 0.0) + e.dur / len(planes)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], lo: float, hi: float, n: int = 10
              ) -> List[list]:
    """The longest stretches of the first device with no operation running,
    each named by the innermost benchmark span open on the host at its
    middle (``host`` when none is)."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = union((e.t0, e.t1) for e in ops(events, planes[0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = [e for e in events if not is_device(e.plane)
            and e.name.startswith("bench.") and e.name != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: -(g[1] - g[0]))[:n]:
        mid = (a + b) / 2
        open_ = [e for e in host if e.t0 <= mid < e.t1]
        name = min(open_, key=lambda e: e.dur).name if open_ else "host"
        out.append([name, b - a])
    return out
