"""Decoded-row cache on CIM stores (fused static serving fast path).

Acceptance contract:

* a warmed cache serves ``dispatch_linear`` / ``dispatch_read_rows`` through
  the ``"cached"`` route, **bit-identical** to the fused kernel on the packed
  planes (autotuned grids are single-K-tile, i.e. a plain matmul);
* per-read dynamic injection (``scalars``/``seeds``) always bypasses the
  cache — per-request streams are keyed per read, never against a
  materialized image;
* ``CIMDeployment.inject`` invalidates: every store it rebuilds is
  cache-less, and re-warming decodes the NEW fault image. Derived
  deployments never bleed a stale cache back into their base;
* warming obeys ``PolicyRule.row_cache`` (embed tables opt out — sparse
  row-gather serving is the packed image's whole point) and the
  ``serving_params(row_cache=False)`` override; dynamic serving never warms;
* the serving engine returns bitwise-identical tokens/logits with and
  without the cache, solo and co-batched.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import align, cim
from repro.core import deployment as dep_lib
from repro.kernels.cim_read import ops as cr_ops
from repro.kernels.fault_inject.ops import ber_to_threshold
from repro.launch import engine as engine_lib
from repro.launch import serve as serve_lib
from repro.launch.mesh import auto_mesh
from repro.models import lm


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(
        jnp.asarray(a, jnp.float32), jnp.uint32))


def _dep(k=256, j=128, ber=1e-3, seed=0, **rule_kw):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, j)) * 0.1
    policy = dep_lib.ReliabilityPolicy(default=dep_lib.PolicyRule(**rule_kw))
    dep = policy.deploy({"w": w})
    if ber:
        dep = dep.inject(jax.random.PRNGKey(3), ber)
    return dep


def test_cache_hit_route_bitwise_identical_to_kernel():
    dep = _dep()
    store_c = dep.serving_params()["w"]
    assert store_c.cache is not None
    store_u = cim.drop_row_cache(store_c)
    assert store_u.cache is None
    assert (_bits(store_c.cache) == _bits(cim.read(store_u)[0])).all()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 256))
    out_c, info_c = dep_lib.dispatch_linear(x, store_c, with_info=True)
    assert info_c["route"] == "cached" and not info_c["used_kernel"]
    out_u, info_u = dep_lib.dispatch_linear(x, store_u, with_info=True)
    assert info_u["used_kernel"]
    assert (_bits(out_c) == _bits(out_u)).all()


def test_read_rows_cache_hit_bitwise():
    dep = _dep()
    store_c = dep.serving_params()["w"]
    idx = jnp.asarray([0, 5, 255, 17, 5])
    rows_c = dep_lib.dispatch_read_rows(store_c, idx)
    rows_u = dep_lib.dispatch_read_rows(cim.drop_row_cache(store_c), idx)
    assert (_bits(rows_c) == _bits(rows_u)).all()


def test_dynamic_injection_bypasses_cache():
    dep = _dep(ber=0)
    store_c = dep.serving_params()["w"]
    seeds = cim.plane_seeds(jax.random.PRNGKey(9))
    thr = ber_to_threshold(0.01)
    sc = cr_ops.make_scalars(seeds, thr, thr)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 256))
    dyn_c, info = dep_lib.dispatch_linear(x, store_c, scalars=sc,
                                          with_info=True)
    assert info.get("route") != "cached" and info["used_kernel"]
    dyn_u = dep_lib.dispatch_linear(x, cim.drop_row_cache(store_c),
                                    scalars=sc)
    assert (_bits(dyn_c) == _bits(dyn_u)).all()
    static = dep_lib.dispatch_linear(x, store_c)
    assert (np.asarray(dyn_c) != np.asarray(static)).any(), \
        "dynamic faults must actually land"
    idx = jnp.asarray([3, 200, 3])
    rows_d = dep_lib.dispatch_read_rows(store_c, idx, seeds=seeds,
                                        thr_man=thr, thr_meta=thr)
    rows_u = dep_lib.dispatch_read_rows(cim.drop_row_cache(store_c), idx,
                                        seeds=seeds, thr_man=thr,
                                        thr_meta=thr)
    assert (_bits(rows_d) == _bits(rows_u)).all()


def test_inject_invalidates_and_rewarm_tracks_new_image():
    dep = _dep(ber=0)
    sp1 = dep.serving_params()
    c1 = sp1["w"].cache
    dep2 = dep.inject(jax.random.PRNGKey(5), 0.01)
    for _, _, s in dep2.store_leaves():
        assert s.cache is None, "inject must rebuild stores cache-less"
    sp2 = dep2.serving_params()
    c2 = sp2["w"].cache
    assert (_bits(c2) ==
            _bits(cim.read(cim.drop_row_cache(sp2["w"]))[0])).all()
    assert (np.asarray(c1) != np.asarray(c2)).any(), \
        "re-warmed cache must reflect the injected faults"
    # no bleed into the base deployment: its clean cache still decodes clean
    (_, _, base_store), = dep.store_leaves()
    assert (_bits(c1) == _bits(cim.read(base_store)[0])).all()


def test_policy_row_cache_opt_out_and_overrides():
    policy = dep_lib.ReliabilityPolicy(
        rules=(dep_lib.PolicyRule(pattern="embed", row_cache=False),),
        default=dep_lib.PolicyRule())
    w1 = jax.random.normal(jax.random.PRNGKey(0), (128, 64)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(1), (128, 64)) * 0.1
    dep = policy.deploy({"embed": w1, "unembed": w2})
    sp = dep.serving_params()
    assert sp["embed"].cache is None, "row_cache=False rule must not warm"
    assert sp["unembed"].cache is not None
    sp_off = dep.serving_params(row_cache=False)
    assert sp_off["embed"].cache is None and sp_off["unembed"].cache is None
    sp_dyn = dep.serving_params(dynamic_key=jax.random.PRNGKey(2), ber=1e-3)
    assert sp_dyn["embed"].cache is None and sp_dyn["unembed"].cache is None


def test_serving_policy_embed_packed_unembed_cached():
    """The launch-level fused policy: the embed table stays packed (row
    gathers decode on read), the unembed projection carries the cache."""
    cfg = get_config("olmo-1b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    stores = serve_lib.deploy_fused(params, ber=1e-4, protect="one4n",
                                    n_group=8, index=2,
                                    key=jax.random.PRNGKey(1),
                                    inject_mode="static", field="full")
    assert stores["embed"].cache is None
    assert stores["unembed"].cache is not None


def test_shard_and_derived_copies_no_stale_cache():
    dep = _dep()
    sp = dep.serving_params()
    mesh = auto_mesh((1,), ("model",))
    dep_sh = dep.shard(mesh)
    for _, _, s in dep_sh.store_leaves():
        assert s.cache is None, "shard() must not inherit a serving cache"
    # a warmed store survives explicit placement with a cache sharding
    placed = dep_lib.place_stores({"w": sp["w"]}, mesh)
    assert placed["w"].cache is not None
    assert (_bits(placed["w"].cache) == _bits(sp["w"].cache)).all()
    # cache is excluded from the SRAM image accounting
    assert sp["w"].stored_bytes == dep_sh.store_leaves()[0][2].stored_bytes


def test_engine_cached_vs_uncached_bitwise():
    """Solo and co-batched engine runs return bit-identical tokens, logits
    and ECC accounting whether the unembed cache is warmed or dropped."""
    cfg = get_config("olmo-1b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    cached = serve_lib.deploy_fused(params, ber=1e-3, protect="one4n",
                                    n_group=8, index=2,
                                    key=jax.random.fold_in(
                                        jax.random.PRNGKey(0), 1),
                                    inject_mode="static", field="full")
    uncached = jax.tree_util.tree_map(
        lambda s: cim.drop_row_cache(s) if cim._is_store(s) else s,
        cached, is_leaf=cim._is_store)
    assert any(s.cache is not None for s in jax.tree_util.tree_leaves(
        cached, is_leaf=cim._is_store) if cim._is_store(s))
    load = engine_lib.LoadGen(n_requests=3, prompt_lens=(3, 12),
                              gen_lens=(3, 5), vocab_size=256, seed=5)
    reqs = load.requests()

    def run(sparams, rs, n_slots=3):
        eng = engine_lib.Engine(cfg, sparams, n_slots=n_slots, max_len=24,
                                chunk=8, collect_logits=True)
        results, _ = eng.run(rs)
        return results

    co_c = run(cached, reqs)
    co_u = run(uncached, reqs)
    solo_c = run(cached, [reqs[0]], n_slots=1)
    solo_u = run(uncached, [reqs[0]], n_slots=1)
    for rid in (r.rid for r in reqs):
        assert co_c[rid].tokens == co_u[rid].tokens
        assert np.array_equal(co_c[rid].logits, co_u[rid].logits)
        assert co_c[rid].ecc == co_u[rid].ecc
    rid0 = reqs[0].rid
    assert solo_c[rid0].tokens == solo_u[rid0].tokens \
        == co_c[rid0].tokens
    assert np.array_equal(solo_c[rid0].logits, co_c[rid0].logits)
    assert np.array_equal(solo_u[rid0].logits, co_u[rid0].logits)
