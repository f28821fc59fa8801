"""Continuous-batching engine: batch-invariance contract + scheduler edges.

The acceptance contract of ``repro.launch.engine``:

* **batch invariance** — a request's decoded tokens, logits, and
  injected-fault streams (via per-request ECC accounting) are bit-identical
  whether it is served alone or continuously co-batched with other requests,
  for static and per-read dynamic injection, on the fused and hbm serve
  paths, on one device and (subprocess) under a forced-8-device "model"
  mesh. Seeds are keyed by (leaf, request, position) — never slot index or
  engine step — and decode math is row-independent across slots for every
  slot-state kind. The scenario matrix asserts this for all five kinds the
  slot-state protocol serves: attn (KV rows), local (rolling-window ring),
  rwkv / rec (recurrent folds with inactive-slot freezing), and drop-free
  moe (capacity never binds at these shapes — ``moe.drop_free``).
* **scheduler edges** — empty-queue idle steps are no-ops, evicted slots are
  reused lowest-index-first, prompts longer than the prefill chunk split
  raggedly without changing results, and a single-slot engine degenerates
  bit-identically to the lock-step ``lm.prefill``/``lm.decode`` serve path.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import engine as engine_lib
from repro.launch import serve as serve_lib
from repro.models import lm

CHUNK = 8
SLOTS = 4
MAX_LEN = 24


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("olmo-1b").reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    dkey = jax.random.fold_in(key, 1)
    return cfg, params, dkey


def _requests(n=4, seed=5, plens=(3, 14), gens=(3, 5)):
    load = engine_lib.LoadGen(n_requests=n, prompt_lens=plens, gen_lens=gens,
                              vocab_size=256, seed=seed)
    return load.requests()


def _serving_params(params, dkey, *, inject, serve_path, ber=1e-3):
    if serve_path == "hbm":
        out, _ = serve_lib.deploy(params, ber=ber, protect="one4n",
                                  n_group=8, index=2, key=dkey)
        return out
    return serve_lib.deploy_fused(params, ber=ber, protect="one4n",
                                  n_group=8, index=2, key=dkey,
                                  inject_mode=inject, field="full")


def _run(cfg, sparams, reqs, *, n_slots=SLOTS, chunk=CHUNK,
         max_len=MAX_LEN, **kw):
    eng = engine_lib.Engine(cfg, sparams, n_slots=n_slots, max_len=max_len,
                            chunk=chunk, collect_logits=True, **kw)
    results, agg = eng.run(reqs)
    assert sorted(results) == sorted(r.rid for r in reqs)
    return results, agg


@pytest.mark.parametrize("inject,serve_path", [
    ("static", "fused"), ("dynamic", "fused"), ("static", "hbm")])
def test_batch_invariance(setup, inject, serve_path):
    """Solo == co-batched, bitwise: tokens, every logit vector, and the
    per-request ECC stream accounting."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject=inject,
                              serve_path=serve_path)
    reqs = _requests()
    co, _ = _run(cfg, sparams, reqs)
    for rid in (0, 2):
        solo, _ = _run(cfg, sparams, [r for r in reqs if r.rid == rid])
        assert co[rid].tokens == solo[rid].tokens, (inject, serve_path, rid)
        assert np.array_equal(co[rid].logits, solo[rid].logits), \
            (inject, serve_path, rid)
        assert co[rid].ecc == solo[rid].ecc, (inject, serve_path, rid)


class _NoDevice:
    """Stands in for ``jax`` / ``jax.numpy``: any use is a failure."""

    def __getattr__(self, name):
        raise AssertionError(f"static ECC charge touched jax ({name})")


def test_static_charge_runs_no_device_program(setup, monkeypatch):
    """A static image's ECC counts are constants of the image: a charge is
    host arithmetic alone — it reaches no ``jax``/``jnp`` name of the engine
    and moves nothing to the device — and adds the per-store
    ``store_stats`` constants once per read, for decode and prefill reads."""
    from repro.core import cim as cim_lib
    from repro.core import deployment as dep_lib
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    eng = engine_lib.Engine(cfg, sparams, n_slots=SLOTS, max_len=MAX_LEN,
                            chunk=CHUNK)
    const = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            sparams, is_leaf=cim_lib._is_store)[0]:
        if cim_lib._is_store(leaf):
            st = cim_lib.store_stats(leaf)
            const[dep_lib.path_str(path)] = (int(st["corrected"]),
                                             int(st["uncorrectable"]))
    assert const and [p for p, _ in eng._ecc_fns] == list(const)
    rsalt = np.uint32(dep_lib.request_salt(7))
    csalt = np.uint32(dep_lib.prefix_salt(np.arange(CHUNK, dtype=np.int32)))
    slot = engine_lib._Slot(rid=7, prompt_len=CHUNK, max_new=4, submit_t=0.0,
                            admit_t=0.0, salt=int(rsalt))
    reads = [(csalt, 0), (rsalt, CHUNK), (rsalt, CHUNK + 1)]
    monkeypatch.setattr(engine_lib, "jnp", _NoDevice())
    monkeypatch.setattr(engine_lib, "jax", _NoDevice())
    with jax.transfer_guard("disallow"):
        for salt, pos in reads:
            eng._charge_reads(slot, salt, pos)
    corr = sum(c for c, _ in const.values())
    unc = sum(u for _, u in const.values())
    n = len(reads)
    assert slot.ecc == {"reads": n, "corrected": n * corr,
                        "uncorrectable": n * unc}
    assert slot.ecc_window == [{"pos": pos, "reads": 1, "corrected": corr,
                                "uncorrectable": unc} for _, pos in reads]
    assert eng.store_ecc == {p: {"reads": n, "corrected": n * c,
                                 "uncorrectable": n * u}
                             for p, (c, u) in const.items()}


# per request: ecc, then (pos, corrected, uncorrectable) per charged read of
# the dynamic image — counts the accountant gave when it was fed device
# scalars; host scalars must leave every one bit-identical
_DYNAMIC_ECC = {
    0: ({"reads": 6, "corrected": 605, "uncorrectable": 45},
        [(0, 92, 11), (8, 89, 8), (11, 111, 6), (12, 102, 4), (13, 95, 6),
         (14, 116, 10)]),
    1: ({"reads": 3, "corrected": 314, "uncorrectable": 9},
        [(0, 101, 5), (7, 110, 2), (8, 103, 2)]),
    2: ({"reads": 4, "corrected": 410, "uncorrectable": 18},
        [(0, 99, 4), (8, 108, 3), (12, 97, 6), (13, 106, 5)]),
    3: ({"reads": 6, "corrected": 603, "uncorrectable": 39},
        [(0, 100, 8), (8, 101, 1), (10, 110, 6), (11, 98, 8), (12, 81, 6),
         (13, 113, 10)]),
}


def test_dynamic_ecc_counts_pinned(setup):
    """The dynamic accountant's per-request and per-store counts on a fixed
    image and load, pinned to recorded values."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="dynamic",
                              serve_path="fused")
    res, agg = _run(cfg, sparams, _requests())
    got = {rid: (r.ecc, [(w["pos"], w["corrected"], w["uncorrectable"])
                         for w in r.ecc_window])
           for rid, r in res.items()}
    assert got == _DYNAMIC_ECC
    assert agg["store_ecc"] == {
        "embed": {"reads": 19, "corrected": 951, "uncorrectable": 55},
        "unembed": {"reads": 19, "corrected": 981, "uncorrectable": 56}}


def test_invariance_across_slot_assignment(setup):
    """The slot a request lands on must not enter its fault streams: reverse
    the submission order (so every request gets a different slot) and demand
    identical tokens/logits per request."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="dynamic",
                              serve_path="fused")
    reqs = _requests()
    fwd, _ = _run(cfg, sparams, reqs)
    # same arrival time, reversed tiebreak order -> different slots
    rev = [engine_lib.Request(rid=r.rid, tokens=r.tokens, max_new=r.max_new,
                              arrival=float(len(reqs) - r.rid))
           for r in reqs]
    bwd, _ = _run(cfg, sparams, rev)
    moved = [r.rid for r in reqs if fwd[r.rid].slot != bwd[r.rid].slot]
    assert moved, "reversed order should shuffle slot assignment"
    for r in reqs:
        assert fwd[r.rid].tokens == bwd[r.rid].tokens
        assert np.array_equal(fwd[r.rid].logits, bwd[r.rid].logits)


def test_single_slot_degenerate_matches_serve_path(setup):
    """n_slots=1 engine == the existing lock-step prefill/decode serve path,
    bitwise, including the chunked prefill's first-token logits."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    req = _requests(n=1, seed=9, plens=(11, 11), gens=(5, 5))[0]
    res, _ = _run(cfg, sparams, [req], n_slots=1)

    tokens = jnp.asarray(req.tokens)[None]
    logits, caches = lm.prefill(sparams, cfg, {"tokens": tokens})
    plen = req.tokens.size
    caches = jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 3)
                          + [(0, req.max_new), (0, 0), (0, 0)])
        if a.ndim >= 4 and a.shape[-3] == plen else a, caches)
    ref_tokens, ref_logits = [], []
    toks = jnp.argmax(logits, -1)[:, None]
    ref_tokens.append(int(toks[0, 0]))
    ref_logits.append(np.asarray(logits)[0])
    for _ in range(req.max_new - 1):
        logits, caches = lm.decode(sparams, cfg, caches, toks)
        toks = jnp.argmax(logits, -1)[:, None]
        ref_tokens.append(int(toks[0, 0]))
        ref_logits.append(np.asarray(logits)[0])
    assert res[req.rid].tokens == ref_tokens
    assert np.array_equal(res[req.rid].logits, np.stack(ref_logits))


def test_prompt_longer_than_chunk(setup):
    """A prompt spanning several ragged chunks decodes identically to a
    single-chunk prefill (static image: the read chain has no chunk-shape
    dependence)."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    req = _requests(n=1, seed=11, plens=(19, 19), gens=(4, 4))[0]
    fine, _ = _run(cfg, sparams, [req], chunk=4)       # 19 -> 4+4+4+4+3
    coarse, _ = _run(cfg, sparams, [req], chunk=32)    # one ragged chunk
    assert fine[req.rid].tokens == coarse[req.rid].tokens
    assert np.array_equal(fine[req.rid].logits, coarse[req.rid].logits)


def test_empty_queue_idle_step(setup):
    """Stepping an empty engine is a no-op: idle event, no position drift,
    and run([]) returns cleanly."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    eng = engine_lib.Engine(cfg, sparams, n_slots=2, max_len=MAX_LEN,
                            chunk=CHUNK)
    before = np.asarray(eng.caches["pos"])
    ev = eng.step()
    assert ev["idle"] and not ev["admitted"] and not ev["decoded"]
    assert np.array_equal(np.asarray(eng.caches["pos"]), before)
    assert eng.idle_steps == 1 and eng.steps == 0
    results, agg = eng.run([])
    assert results == {} and agg["n_requests"] == 0


def test_slot_eviction_reuse_ordering(setup):
    """With more requests than slots, a finished slot frees and the next
    queued request reuses the lowest free index; everything completes."""
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    reqs = [engine_lib.Request(rid=0, tokens=np.arange(4) % 256, max_new=2),
            engine_lib.Request(rid=1, tokens=np.arange(5) % 256, max_new=6),
            engine_lib.Request(rid=2, tokens=np.arange(6) % 256, max_new=3)]
    res, agg = _run(cfg, sparams, reqs, n_slots=2)
    assert res[0].slot == 0 and res[1].slot == 1
    # rid 0 (2 tokens) finishes before rid 1 (6 tokens): slot 0 frees first
    # and rid 2 must land there
    assert res[2].slot == 0
    assert [len(res[i].tokens) for i in range(3)] == [2, 6, 3]
    assert all(r.finish == "length" for r in res.values())
    assert agg["total_tokens"] == 11
    for r in res.values():
        # closed-loop runs gate admission with now=inf — that must never
        # leak into the latency record or the JSON artifact
        assert np.isfinite(r.queue_s) and r.queue_s >= 0
        assert r.finite is True
        json.dumps(r.to_json(), allow_nan=False)


def test_request_exceeding_max_len_rejected(setup):
    cfg, params, dkey = setup
    sparams = _serving_params(params, dkey, inject="static",
                              serve_path="fused")
    eng = engine_lib.Engine(cfg, sparams, n_slots=1, max_len=16, chunk=CHUNK)
    big = engine_lib.Request(rid=0, tokens=np.zeros(12, np.int32), max_new=8)
    with pytest.raises(engine_lib.EngineError, match="exceeds"):
        eng.run([big])


def test_engine_accepts_all_slot_state_kinds():
    """The slot-state protocol serves every registered block kind — the old
    token-by-token rejection of recurrent / rolling-window architectures is
    gone. ``check_engine_kinds`` returns the per-block specs the engine
    schedules from, and each spec advertises the fields scheduling needs."""
    expect = {
        "olmo-1b": {"attn"},
        "rwkv6-1.6b": {"rwkv"},
        "recurrentgemma-9b": {"rec", "local"},
        "qwen3-moe-235b-a22b": {"moe"},
    }
    for name, kinds in expect.items():
        specs = lm.check_engine_kinds(get_config(name).reduced())
        assert {s.kind for s in specs} == kinds, name
    rwkv_spec, = set(lm.check_engine_kinds(get_config("rwkv6-1.6b").reduced()))
    assert rwkv_spec.advance == "scan" and rwkv_spec.cache_unit == "state"
    assert rwkv_spec.fold_state and not rwkv_spec.window_bound
    moe_spec, = set(lm.check_engine_kinds(
        get_config("qwen3-moe-235b-a22b").reduced()))
    assert moe_spec.capacity_coupled and moe_spec.cache_unit == "rows"


# --------------------------------------------------------------------------
# Scenario matrix: the batch-invariance contract for every slot-state kind.
# --------------------------------------------------------------------------

KINDS = ("attn", "local", "rwkv", "rec", "moe")


def _kind_cfg(kind):
    if kind == "attn":
        return get_config("olmo-1b").reduced()
    if kind == "local":
        # synthetic pure-local model: rolling-window ring with a window
        # smaller than max_len so eviction/wraparound is actually exercised
        return dataclasses.replace(get_config("olmo-1b").reduced(),
                                   block_pattern=("local",), local_window=16)
    if kind == "rwkv":
        return get_config("rwkv6-1.6b").reduced()
    if kind == "rec":
        return get_config("recurrentgemma-9b").reduced()
    return get_config("qwen3-moe-235b-a22b").reduced()


_KIND_CACHE = {}


def _kind_setup(kind):
    if kind not in _KIND_CACHE:
        cfg = _kind_cfg(kind)
        key = jax.random.PRNGKey(0)
        _KIND_CACHE[kind] = (cfg, lm.init_lm(key, cfg),
                             jax.random.fold_in(key, 1))
    return _KIND_CACHE[kind]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("inject", ["static", "dynamic"])
def test_scenario_matrix_batch_invariance(kind, inject):
    """For each architecture class the engine serves, a request's tokens,
    logits, and ECC stream accounting are bit-identical solo vs co-batched,
    under static and per-read dynamic injection. MoE runs drop-free at these
    shapes, so it carries the full guarantee with no capacity warning."""
    cfg, params, dkey = _kind_setup(kind)
    sparams = _serving_params(params, dkey, inject=inject, serve_path="fused")
    reqs = _requests(n=3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no capacity-coupling warning allowed
        eng = engine_lib.Engine(cfg, sparams, n_slots=SLOTS, max_len=MAX_LEN,
                                chunk=CHUNK, collect_logits=True)
    assert eng.capacity_coupled is False
    co, _ = eng.run(reqs)
    assert sorted(co) == sorted(r.rid for r in reqs)
    for rid in (0, 2):
        solo_eng = engine_lib.Engine(cfg, sparams, n_slots=SLOTS,
                                     max_len=MAX_LEN, chunk=CHUNK,
                                     collect_logits=True)
        solo, _ = solo_eng.run([r for r in reqs if r.rid == rid])
        assert co[rid].tokens == solo[rid].tokens, (kind, inject, rid)
        assert np.array_equal(co[rid].logits, solo[rid].logits), \
            (kind, inject, rid)
        assert co[rid].ecc == solo[rid].ecc, (kind, inject, rid)
        assert np.isfinite(co[rid].logits).all(), (kind, inject, rid)


def test_load_gen_open_loop_poisson():
    """Arrivals are monotone, lengths within range, and deterministic per
    seed (the CI soak artifact must be reproducible)."""
    load = engine_lib.LoadGen(n_requests=16, rate=100.0, prompt_lens=(4, 9),
                              gen_lens=(2, 5), vocab_size=64, seed=7)
    a, b = load.requests(), load.requests()
    arr = [r.arrival for r in a]
    assert arr == sorted(arr) and arr[-1] > 0
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.tokens, rb.tokens)
        assert (ra.arrival, ra.max_new) == (rb.arrival, rb.max_new)
        assert 4 <= ra.tokens.size <= 9 and 2 <= ra.max_new <= 5
        assert ra.tokens.max() < 64


_KIND_CFG_SNIPPET = {
    "attn": 'cfg = get_config("olmo-1b").reduced()',
    "local": ('import dataclasses\n'
              'cfg = dataclasses.replace(get_config("olmo-1b").reduced(), '
              'block_pattern=("local",), local_window=16)'),
    "rwkv": 'cfg = get_config("rwkv6-1.6b").reduced()',
    "rec": 'cfg = get_config("recurrentgemma-9b").reduced()',
    "moe": 'cfg = get_config("qwen3-moe-235b-a22b").reduced()',
}

_MESH_INVARIANCE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.distributed import sharding as shlib
    from repro.launch import engine as engine_lib
    from repro.launch import serve as serve_lib
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm

    {cfg_snippet}
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    dkey = jax.random.fold_in(key, 1)
    dep = serve_lib.make_deployment(params, ber=1e-3, protect="one4n",
                                   n_group=8, index=2, key=dkey,
                                   inject_mode="dynamic", field="full")
    mesh = make_host_mesh(model_axis=8)
    dep = dep.shard(mesh, axis="model", dim="j")
    sparams = serve_lib._serving_params(dep, ber=1e-3, key=dkey,
                                        inject_mode="dynamic", field="full")
    load = engine_lib.LoadGen(n_requests=3, prompt_lens=(3, 10),
                              gen_lens=(2, 3), vocab_size=256, seed=5)
    reqs = load.requests()
    with shlib.use_mesh(mesh):
        co, _ = engine_lib.Engine(cfg, sparams, n_slots=3, max_len=16,
                                  chunk=4, collect_logits=True).run(reqs)
        solo, _ = engine_lib.Engine(cfg, sparams, n_slots=3, max_len=16,
                                    chunk=4, collect_logits=True).run(
            [r for r in reqs if r.rid == 1])
    print(json.dumps({
        "tokens_equal": co[1].tokens == solo[1].tokens,
        "logits_equal": bool(np.array_equal(co[1].logits, solo[1].logits)),
        "ecc_equal": co[1].ecc == solo[1].ecc,
        "n_done": len(co),
        "finite": bool(np.isfinite(co[1].logits).all()),
    }))
""")


@pytest.mark.parametrize("kind", KINDS)
def test_batch_invariance_on_8_device_mesh(tmp_path, kind):
    """Dynamic-inject fused serving through the shard_map'd kernel on a
    forced-8-device "model" mesh: solo == co-batched, bitwise, for every
    slot-state kind."""
    path = tmp_path / f"mesh_engine_{kind}.py"
    path.write_text(_MESH_INVARIANCE_SCRIPT.replace(
        "{cfg_snippet}", _KIND_CFG_SNIPPET[kind]))
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(path)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=560)
    assert out.returncode == 0, (kind, out.stderr[-3000:])
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"tokens_equal": True, "logits_equal": True,
                   "ecc_equal": True, "n_done": 3, "finite": True}
