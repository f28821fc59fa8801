"""The serving engine's own spans, read back from a ``jax.profiler`` trace.

``repro.launch.engine`` writes ``engine.<name>`` spans with counters as
their arguments. Recorded around a few admitted and decoded requests and
read with ``ProfileData``, they must nest as the engine's calls do, their
counters must match the engine's own state, and tracing must not change a
single result.
"""
import glob
import math
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.launch import engine as engine_lib
from repro.launch import serve as serve_lib
from repro.models import lm

CHUNK = 8
SLOTS = 3
MAX_LEN = 40


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same requests served twice, with the profiler on, then off."""
    cfg = get_config("olmo-1b").reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    sparams = serve_lib.deploy_fused(
        params, ber=1e-3, protect="one4n", n_group=8, index=2,
        key=jax.random.fold_in(key, 1), inject_mode="static", field="full")
    # a shared 16-token prefix: later requests hit the trie for two chunks
    reqs = engine_lib.LoadGen(n_requests=5, prompt_lens=(3, 12),
                              gen_lens=(2, 5), vocab_size=256, seed=3,
                              prefix_len=2 * CHUNK).requests()

    def serve():
        eng = engine_lib.Engine(cfg, sparams, n_slots=SLOTS, max_len=MAX_LEN,
                                chunk=CHUNK, collect_logits=True,
                                prefix_cache=True)
        eng.start()
        for r in reqs:
            eng.submit(r, now=0.0)
        events = []
        while eng.busy:
            events.append(eng.step(now=float("inf")))
        return eng, events

    serve()                      # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        traced = serve()
    finally:
        jax.profiler.stop_trace()
    plain = serve()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1, path
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path[0]).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    return traced, plain, spans


def _named(spans, name):
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_is_written(served):
    _, _, spans = served
    names = {s[0] for s in spans}
    assert names == {"engine.step", "engine.admit", "engine.prefill",
                     "engine.decode", "engine.wait", "engine.copy",
                     "engine.charge_reads", "engine.evict"}


def test_spans_nest_as_the_engine_calls(served):
    (eng, _), _, spans = served
    steps = _named(spans, "engine.step")
    admits = _named(spans, "engine.admit")
    for name in ("engine.decode", "engine.wait", "engine.copy",
                 "engine.admit", "engine.evict", "engine.charge_reads"):
        for s in _named(spans, name):
            assert sum(_within(s, st) for st in steps) == 1, s
    for s in _named(spans, "engine.prefill"):
        (a,) = [a for a in admits if _within(s, a)]
        assert a[3]["rid"] == s[3]["rid"]
    # waits and copies of a prefill close their admission
    for name in ("engine.wait", "engine.copy"):
        for s in _named(spans, name):
            assert any(_within(s, a) for a in admits) == (
                s[3]["of"] == "prefill"), s
    # an admission's charges: one per prefix hit and one per cold chunk,
    # which it prefills
    charges = _named(spans, "engine.charge_reads")
    prefills = _named(spans, "engine.prefill")
    for a in admits:
        mine = [c for c in charges if _within(c, a)]
        assert {c[3]["rid"] for c in mine} == {a[3]["rid"]}
        res = eng.results[a[3]["rid"]]
        cold = math.ceil((res.prompt_len - res.prefix_tokens) / CHUNK)
        assert len(mine) == res.prefix_tokens // CHUNK + cold
        assert [(p[3]["pos"], p[3]["length"]) for p in prefills
                if _within(p, a)] == [
            (c0, min(CHUNK, res.prompt_len - c0))
            for c0 in range(res.prefix_tokens, res.prompt_len, CHUNK)]
    assert sum(r.prefix_tokens for r in eng.results.values()) > 0


def test_counters_match_the_engine(served):
    (eng, events), _, spans = served
    steps = _named(spans, "engine.step")
    assert len(steps) == len(events)
    for st, ev in zip(steps, events):
        admits = [a for a in _named(spans, "engine.admit") if _within(a, st)]
        assert [a[3]["rid"] for a in admits] == ev["admitted"]
        decodes = [d for d in _named(spans, "engine.decode")
                   if _within(d, st)]
        assert [d[3]["active"] for d in decodes] == (
            [len(ev["decoded"])] if ev["decoded"] else [])
        evicts = [e for e in _named(spans, "engine.evict") if _within(e, st)]
        assert sorted(e[3]["rid"] for e in evicts) == sorted(ev["evicted"])
    for a in _named(spans, "engine.admit"):
        res = eng.results[a[3]["rid"]]
        assert a[3]["queue_ms"] == pytest.approx(1e3 * res.queue_s,
                                                 rel=1e-9, abs=1e-9)
    charges = _named(spans, "engine.charge_reads")
    for rid, res in eng.results.items():
        mine = [c[3] for c in charges if c[3]["rid"] == rid]
        assert len(mine) == res.ecc["reads"]
        assert [c["pos"] for c in mine] == [w["pos"]
                                            for w in res.ecc_window]
    evicted = [e[3]["rid"] for e in _named(spans, "engine.evict")]
    assert sorted(evicted) == sorted(eng.results)


def test_results_bitwise_equal_with_profiler_on_and_off(served):
    (on, ev_on), (off, ev_off), _ = served
    assert ev_on == ev_off
    assert sorted(on.results) == sorted(off.results)
    for rid, a in on.results.items():
        b = off.results[rid]
        assert a.tokens == b.tokens
        assert np.array_equal(a.logits, b.logits)
        assert a.ecc == b.ecc and a.ecc_window == b.ecc_window
        assert a.prefix_tokens == b.prefix_tokens
    assert on.store_ecc == off.store_ecc
