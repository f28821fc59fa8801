"""The measured window: the benchmark's own loop around ``Engine.step``.

Open loop (``poisson``): every request is submitted at its due time, however
far behind the engine is. Backlog: ``outstanding`` requests are kept queued
or in flight, a new one submitted whenever one finishes; the slots are
filled before the window opens.

Times are seconds on the host clock from the window's opening. A request's
first token reaches the host when the engine's admission returns; a decode
token when the step that made it returns.

With ``spans`` on, the engine instance's ``step``, ``_admit``, ``_prefill``,
``_decode`` and ``_charge_reads`` are wrapped to record host spans, which
also go to the profiler as ``bench.<name>`` annotations. The prefill and
decode wrappers wait for their program to finish, so a span covers its
device work.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from yardstick import system as sys_lib


@dataclasses.dataclass
class Record:
    rid: int
    due: float
    first: float = float("nan")        # first token on the host
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: dict = dataclasses.field(default_factory=dict)


class Hooks:
    """Wrappers on one engine instance (the program is not edited)."""

    def __init__(self, eng, spans: bool, clock):
        self.eng = eng
        self.clock = clock
        self.spans: List[Span] = []
        self.first: Dict[int, float] = {}
        self.on = spans
        admit = eng._admit

        def _admit(req, slot_idx, submit_t):
            with self._span("admit", rid=req.rid):
                admit(req, slot_idx, submit_t)
            self.first[req.rid] = clock()

        eng._admit = _admit
        if not spans:
            return
        for name in ("step", "_charge_reads"):
            setattr(eng, name, self._wrap(name.strip("_"),
                                          getattr(eng, name)))
        prefill, decode = eng._prefill, eng._decode

        def _prefill(*a):
            with self._span("prefill", length=int(a[5]), pos=int(a[4])):
                return jax.block_until_ready(prefill(*a))

        def _decode(*a):
            active = np.asarray(a[3])
            pos = [s.prompt_len + len(s.tokens) - 1
                   for s in eng.slots if s is not None]
            with self._span("decode", active=int(active.sum()), pos=pos):
                return jax.block_until_ready(decode(*a))

        eng._prefill, eng._decode = _prefill, _decode

    def _span(self, name, **info):
        hooks = self

        class _S:
            def __enter__(self):
                if hooks.on:
                    self.ann = jax.profiler.TraceAnnotation(f"bench.{name}")
                    self.ann.__enter__()
                self.t0 = hooks.clock()

            def __exit__(self, *exc):
                if hooks.on:
                    hooks.spans.append(Span(name, self.t0, hooks.clock(),
                                            info))
                    self.ann.__exit__(*exc)
        return _S()

    def _wrap(self, name, fn):
        def wrapped(*a, **k):
            with self._span(name):
                return fn(*a, **k)
        return wrapped


@dataclasses.dataclass
class Outcome:
    seconds: float
    records: Dict[int, Record]
    lateness: List[float]
    steps: int
    finished: dict                     # rid -> prompt, tokens, ecc
    spans: List[Span]
    window: tuple                      # (open, close) on the host clock


def run(eng, gen, seconds: float, spans: bool = False,
        drain_s: float = 60.0) -> Outcome:
    t_origin = time.perf_counter()
    clock = lambda: time.perf_counter() - t_origin   # noqa: E731
    hooks = Hooks(eng, spans, clock)
    records: Dict[int, Record] = {}
    prompts = {}
    lateness: List[float] = []

    def submit(req, due):
        records[req.rid] = Record(req.rid, due)
        prompts[req.rid] = req.tokens
        eng.submit(sys_lib.request(req.rid, req.tokens, req.max_new,
                                   arrival=due), now=due)
        lateness.append(clock() - due)

    backlog = gen.mode == "backlog"
    eng.start(t0=t_origin)
    opened = 0.0
    if backlog:
        want = gen.mix["arrivals"]["outstanding"]
        for _ in range(want):
            submit(gen.make(0.0), 0.0)
        while eng.queue and eng.free_slots():
            _step(eng, records, hooks, clock)
        opened = clock()
        pending = collections.deque()
    else:
        pending = collections.deque(gen.schedule())
    steps0 = eng.steps
    close = opened + seconds
    live = set(records)
    mark = jax.profiler.TraceAnnotation("bench.window") if spans else None
    if mark is not None:
        mark.__enter__()
    while True:
        t = clock()
        if t >= close:
            break
        while pending and pending[0].due <= t:
            req = pending.popleft()
            submit(req, req.due)
        if backlog:
            for rid in [r for r in live if r in eng.results]:
                live.discard(rid)
            while len(live) < want:
                req = gen.make(t)
                submit(req, t)
                live.add(req.rid)
        if not eng.busy:
            if pending:
                time.sleep(max(0.0, min(pending[0].due, close) - clock()))
            continue
        _step(eng, records, hooks, clock)
    steps = eng.steps - steps0
    if mark is not None:
        mark.__exit__(None, None, None)
    if not backlog:
        # answers due in the window that came late are late, not missing
        deadline = clock() + drain_s
        while (any(np.isnan(r.first) for r in records.values())
               and eng.busy and clock() < deadline):
            _step(eng, records, hooks, clock)
    finished = {rid: {"prompt": prompts[rid], "tokens": list(res.tokens),
                      "ecc": dict(res.ecc)}
                for rid, res in eng.results.items() if rid in records}
    return Outcome(seconds=seconds, records=records, lateness=lateness,
                   steps=steps, finished=finished, spans=hooks.spans,
                   window=(opened, close))


def _step(eng, records, hooks, clock):
    ev = eng.step()
    t = clock()
    for rid in ev["admitted"]:
        r = records.get(rid)
        if r is not None:
            r.first = hooks.first[rid]
            r.times.append(r.first)
    for rid in ev["decoded"]:
        r = records.get(rid)
        if r is not None:
            r.times.append(t)
    return ev


def metrics(out: Outcome) -> dict:
    """End-to-end numbers of one window."""
    o, c = out.window
    n_tok = 0
    gaps = []
    for r in out.records.values():
        inside = [t for t in r.times if o <= t < c]
        n_tok += len(inside)
        gaps.extend(np.diff(inside).tolist())
    due = [r for r in out.records.values() if o <= r.due < c]
    ttft = sorted((r.first - r.due) if not np.isnan(r.first)
                  else float("inf") for r in due)
    served = [r for r in out.records.values()
              if any(o <= t < c for t in r.times)]
    return {"tok_s": n_tok / out.seconds,
            "tokens": n_tok,
            "ttft": ttft,
            "itl": sorted(gaps),
            "served": len(served),
            "due": len(due),
            "failed": sum(1 for v in ttft if not np.isfinite(v))}


def nearest_rank(sorted_vals, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at least
    q% of the sample at or below it."""
    if not sorted_vals:
        return float("nan")
    k = max(int(np.ceil(q / 100.0 * len(sorted_vals))) - 1, 0)
    return float(sorted_vals[k])
