"""Reductions of the serving engine's own spans, shared by the metric
readers in ``bench/metrics``.

``repro.launch.engine`` writes ``engine.<name>`` spans with
``jax.profiler``: they lie on the trace's host planes, on the device
trace's clock, and carry the engine's counters as arguments
(``Event.stat``, read back as strings). Each function takes a run's
context and returns a number, or None where the trace holds no such span
in the window (a program that writes none).
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from yardstick import trace as tr
from yardstick.window import nearest_rank

Intervals = List[Tuple[float, float]]


def engine_spans(ctx, name: str) -> List[tr.Event]:
    """Every ``name`` span (``engine.<...>``) on the host planes, in time
    order. One pass over the trace gathers them all; the context keeps
    them for the next reader."""
    if ctx.events is None:
        return []
    by_name = getattr(ctx, "_engine_spans", None)
    if by_name is None:
        by_name = {}
        for e in ctx.events:
            if e.name.startswith("engine.") and not tr.is_device(e.plane):
                by_name.setdefault(e.name, []).append(e)
        for found in by_name.values():
            found.sort(key=lambda e: e.t0)
        ctx._engine_spans = by_name
    return by_name.get(name, [])


def spans(ctx, name: str, **args: str) -> List[tr.Event]:
    """The ``name`` spans that start inside the trace window and carry the
    given arguments."""
    if ctx.events is None:
        return []
    lo, hi = ctx.trace_window
    return [e for e in engine_spans(ctx, name) if lo <= e.t0 < hi
            and all(e.stat(k) == v for k, v in args.items())]


def _length(iv: Intervals) -> float:
    return sum(b - a for a, b in iv)


def _overlap(x: Intervals, y: Intervals) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            tot += b - a
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_in_step(ctx) -> Optional[float]:
    """Share of the trace window with no operation running on the device
    while an ``engine.step`` is open, %. Averaged over the device planes as
    ``trace.busy_s`` is, so it is the part of the window's device idle
    share that lies inside the engine's steps."""
    if ctx.events is None:
        return None
    lo, hi = ctx.trace_window
    steps = tr.union((e.t0, e.t1) for e in
                     tr.clip(engine_spans(ctx, "engine.step"), lo, hi))
    planes = tr.device_planes(ctx.events)
    if not steps or not planes:
        return None
    idle = 0.0
    for p in planes:
        busy = tr.union((e.t0, e.t1) for e in tr.ops(ctx.events, p, lo, hi))
        idle += _length(steps) - _overlap(busy, steps)
    return 100.0 * idle / (len(planes) * (hi - lo))


def ms_per_working_step(ctx, name: str) -> Optional[float]:
    """Time in ``name`` spans inside the steps that prefilled or decoded,
    per such step, ms."""
    steps = spans(ctx, "engine.step")
    if not steps:
        return None
    starts = [s.t0 for s in steps]
    kids = {}
    for kind in ("engine.prefill", "engine.decode", name):
        for e in engine_spans(ctx, kind):
            i = bisect.bisect_right(starts, e.t0) - 1
            if i >= 0 and e.t1 <= steps[i].t1:
                kids.setdefault(i, []).append(e)
    working = [k for k in kids.values()
               if any(e.name in ("engine.prefill", "engine.decode")
                      for e in k)]
    if not working:
        return None
    return 1e3 * sum(e.dur for k in working for e in k
                     if e.name == name) / len(working)


def mean_ms(ctx, name: str, **args: str) -> Optional[float]:
    """Mean duration of the matching spans, ms."""
    found = spans(ctx, name, **args)
    if not found:
        return None
    return 1e3 * sum(e.dur for e in found) / len(found)


def mean_arg(ctx, name: str, key: str) -> Optional[float]:
    """Mean of one argument over the matching spans."""
    vals = [float(e.stat(key)) for e in spans(ctx, name)
            if e.stat(key) is not None]
    return sum(vals) / len(vals) if vals else None


def arg_percentile(ctx, name: str, key: str, q: float) -> Optional[float]:
    """The q-th percentile (nearest rank) of one argument over the
    matching spans."""
    vals = sorted(float(e.stat(key)) for e in spans(ctx, name)
                  if e.stat(key) is not None)
    return nearest_rank(vals, q) if vals else None
