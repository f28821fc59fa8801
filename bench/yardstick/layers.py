"""Per-layer numbers, shared by the metric readers in ``bench/metrics``.

Each takes the run's context and returns a number, or None where the run
has nothing to read (no such span, program or kernel in the window).
"""
from __future__ import annotations

from yardstick import flops as flops_lib
from yardstick import trace as tr


def _in_window(ctx, spans, name):
    o, c = ctx.outcome.window
    return [s for s in spans if s.name == name and o <= s.t0 < c]


def _busy_steps(ctx):
    spans = ctx.outcome.spans
    steps = _in_window(ctx, spans, "step")
    inner = [s for s in spans if s.name in ("prefill", "decode",
                                             "charge_reads")]
    out = []
    for st in steps:
        kids = [s for s in inner if st.t0 <= s.t0 and s.t1 <= st.t1]
        if any(k.name in ("prefill", "decode") for k in kids):
            out.append((st, kids))
    return out


def host_ms_per_step(ctx):
    """Engine step wall minus the prefill, decode and ECC spans inside it."""
    steps = _busy_steps(ctx)
    if not steps:
        return None
    self_s = sum((st.t1 - st.t0) - sum(k.t1 - k.t0 for k in kids)
                 for st, kids in steps)
    return 1e3 * self_s / len(steps)


def ecc_ms_per_step(ctx):
    steps = _busy_steps(ctx)
    if not steps:
        return None
    ecc = sum(k.t1 - k.t0 for _, kids in steps for k in kids
              if k.name == "charge_reads")
    return 1e3 * ecc / len(steps)


def program_ms(ctx, pattern):
    """Mean device time per execution of the matching program."""
    if ctx.events is None:
        return None
    lo, hi = ctx.trace_window
    runs = tr.modules(ctx.events, pattern, lo, hi)
    if not runs:
        return None
    return 1e3 * sum(e.dur for e in runs) / len(runs)


def device_idle(ctx):
    if ctx.events is None:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - tr.busy_s(ctx.events, lo, hi) / (hi - lo))


def roofline(ctx, pattern, m, k, j):
    """Least time of the kernel's calls over their summed device time, %.
    Least time of one call: the larger of its operations over the peak
    rate and its bytes over the memory bandwidth."""
    if ctx.events is None:
        return None
    lo, hi = ctx.trace_window
    calls = tr.kernel_calls(ctx.events, pattern, lo, hi)
    if not calls:
        return None
    f, b = flops_lib.cim_read(m, k, j)
    least = max(f / ctx.peak["bf16_flops"], b / ctx.peak["hbm_bytes_s"])
    return 100.0 * least * len(calls) / sum(e.dur for e in calls)


def mfu(ctx):
    """Model FLOPs of every token processed in the window over the window
    times the bf16 peak, %."""
    spans = ctx.outcome.spans
    if not spans:
        return None
    positions = []
    for s in _in_window(ctx, spans, "prefill"):
        positions.extend(range(s.info["pos"], s.info["pos"]
                               + s.info["length"]))
    for s in _in_window(ctx, spans, "decode"):
        positions.extend(s.info["pos"])
    if not positions:
        return None
    fl = flops_lib.tokens(ctx.conf["model"], ctx.conf["reference"],
                          positions)
    return 100.0 * fl / (ctx.outcome.seconds * ctx.peak["bf16_flops"])
