"""Unified deployment API: policy grammar, mixed-protection bit-identity,
dispatch parity, deprecation shims.

Acceptance contracts of the ``repro.core.deployment`` redesign:

* a mixed-protection :class:`ReliabilityPolicy` deployment is **bit-identical**
  — stores, inject streams, decoded reads, ECC stats — to manually composing
  per-leaf ``deploy_pytree`` calls with the same per-rule configs, on a single
  device and (subprocess, 8 forced host devices) on a "model" mesh;
* ``CIMDeployment.linear`` dispatch parity: fused kernel, shard_map'd mesh
  route, GSPMD fallback and the explicit hbm route all agree;
* enum-like config fields fail at construction with the allowed vocabulary;
* the legacy ``cim.deploy_pytree`` / ``inject_pytree`` / ``read_pytree``
  free functions forward with a ``DeprecationWarning``.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import (CIMDeployment, PolicyRule, ReliabilityConfig,
                   ReliabilityPolicy, dispatch_linear)
from repro.core import align, cim
from repro.core import deployment as dep_lib


def _rand_w(key, k, j, scale=0.1):
    w = jax.random.normal(key, (k, j)) * scale
    return jnp.asarray(jnp.asarray(w, jnp.float16), jnp.float32)


def _params():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return {"embed": _rand_w(ks[0], 64, 32),
            "unembed": _rand_w(ks[1], 32, 64),
            "mlp": {"w1": _rand_w(ks[2], 32, 48), "w2": _rand_w(ks[3], 48, 32)},
            "norm": jnp.ones((32,))}


THREE_RULES = ReliabilityPolicy(
    rules=(PolicyRule("unembed", protect="one4n"),
           PolicyRule("embed", protect="per_weight"),
           PolicyRule("mlp/*", protect="none", field="mantissa",
                      ber_scale=0.5)),
    default=PolicyRule(deploy=False))


# ---------------------------------------------------------------- policy

def test_rule_matching_grammar():
    glob = PolicyRule("groups/*/attn/*")
    assert glob.matches("groups/blk0/attn/wq")
    assert not glob.matches("groups/blk0/mlp/w1")
    regex = PolicyRule(r"re:.*mlp/(w1|w2)")
    assert regex.matches("tail/0/mlp/w1")
    assert not regex.matches("tail/0/mlp/w3")
    # a wildcard-free pattern matches whole paths and path segments — but
    # never substrings ("embed" must not hit "unembed")
    seg = PolicyRule("embed")
    assert seg.matches("embed")
    assert seg.matches("vision/embed")
    assert not seg.matches("unembed")


def test_first_match_wins_and_default():
    policy = ReliabilityPolicy(
        rules=(PolicyRule("a", protect="one4n"),
               PolicyRule("*", protect="none")),
        default=PolicyRule(deploy=False))
    assert policy.rule_for("a").protect == "one4n"
    assert policy.rule_for("b").protect == "none"
    assert ReliabilityPolicy().rule_for("anything").deploy
    assert ReliabilityPolicy().uniform and not policy.uniform


def test_enum_validation_errors():
    with pytest.raises(ValueError, match="one4N.*one4n"):
        PolicyRule(protect="one4N")
    with pytest.raises(ValueError, match="field"):
        PolicyRule(field="exponent")        # Fig. 2 axis, not a cell class
    with pytest.raises(ValueError, match="serve_path"):
        PolicyRule(serve_path="fussed")
    with pytest.raises(ValueError, match="ber_scale"):
        PolicyRule(ber_scale=-1.0)
    with pytest.raises(ValueError, match="mode"):
        ReliabilityConfig(mode="onn")
    with pytest.raises(ValueError, match="protect"):
        ReliabilityConfig(protect="one4N")
    with pytest.raises(ValueError, match="inject"):
        ReliabilityConfig(inject="dynamyc")
    with pytest.raises(ValueError, match="serve_path"):
        ReliabilityConfig(serve_path="hmb")
    with pytest.raises(ValueError, match="fmt_name"):
        ReliabilityConfig(fmt_name="fp17")
    with pytest.raises(TypeError):
        ReliabilityPolicy(rules=("not a rule",))


def test_reliability_config_is_single_rule_policy_factory():
    rel = ReliabilityConfig(mode="cim", protect="per_weight", n_group=4)
    policy = rel.policy
    assert policy.uniform
    rule = policy.rule_for("whatever/leaf")
    assert rule.protect == "per_weight" and rule.n_group == 4
    assert rule.cim_cfg == cim.CIMConfig(n_group=4, index=rel.index,
                                         protect="per_weight", fmt=rel.fmt)
    # Fig. 2 characterization axes map to the exponent/sign CELL class (the
    # packed image stores sign and exponent in one protected class) — never
    # silently widening onto mantissa cells
    for axis in ("exponent", "sign"):
        assert ReliabilityConfig(field=axis).policy.default.field == \
            "exponent_sign"
    # a policy_override replaces the single-rule bridge wholesale
    override = ReliabilityPolicy(rules=(PolicyRule("embed", protect="none"),))
    assert ReliabilityConfig(policy_override=override).policy is override
    with pytest.raises(TypeError, match="policy_override"):
        ReliabilityConfig(policy_override="one4n")


# ------------------------------------------------- mixed-policy bit-identity

def _manual_compose(params, policy):
    """Per-leaf ``deploy_pytree`` composition of a policy: one deploy call
    per leaf with that leaf's rule config (the pre-redesign idiom)."""
    leaves_wp, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in leaves_wp:
        p = dep_lib.path_str(path)
        rule = policy.rule_for(p)
        if rule.deploy and dep_lib._deployable(path, leaf):
            only_this = lambda q, l, p=p: dep_lib.path_str(q) == p
            stores, _ = cim.deploy_pytree_impl(params, rule.cim_cfg,
                                               predicate=only_this)
            out.append([s for s in jax.tree_util.tree_leaves(
                stores, is_leaf=cim._is_store) if cim._is_store(s)][0])
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _stores_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))
               for x, y in zip(fa, fb))


def test_mixed_policy_bit_identical_to_manual_composition():
    params = _params()
    dep = CIMDeployment.deploy(params, THREE_RULES)
    manual = _manual_compose(params, THREE_RULES)
    assert _stores_equal(dep.stores, manual)

    # inject streams: the deployment splits its key across the flat leaves
    # exactly like inject_pytree; each leaf then draws at ber*scale in its
    # rule's field
    key = jax.random.PRNGKey(3)
    faulty = dep.inject(key, 1e-3)
    flat, treedef = jax.tree_util.tree_flatten(manual, is_leaf=cim._is_store)
    keys = jax.random.split(key, len(flat))
    paths = [dep_lib.path_str(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    manual_faulty = []
    for k, leaf, p in zip(keys, flat, paths):
        if cim._is_store(leaf):
            rule = THREE_RULES.rule_for(p)
            leaf = cim.inject(k, leaf, 1e-3 * rule.ber_scale, rule.field)
        manual_faulty.append(leaf)
    manual_faulty = jax.tree_util.tree_unflatten(treedef, manual_faulty)
    assert _stores_equal(faulty.stores, manual_faulty)

    # decoded reads + ECC stats
    got_params, got_stats = faulty.read()
    want_params, want_stats = cim.read_pytree_impl(manual_faulty)
    assert _stores_equal(got_params, want_params)
    for k_ in ("corrected", "uncorrectable"):
        assert int(got_stats[k_]) == int(want_stats[k_])
    # and the deployment accumulated them
    for k_ in ("corrected", "uncorrectable"):
        assert int(faulty.ecc_stats[k_]) == int(got_stats[k_])


def test_mixed_policy_over_lm_pytree():
    """The 3-rule policy applied to a real (reduced) LM parameter pytree is
    bit-identical to manual per-leaf composition — stores and decoded reads."""
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("olmo-1b").reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    policy = ReliabilityPolicy(
        rules=(PolicyRule("unembed", protect="one4n"),
               PolicyRule("embed", protect="per_weight"),
               PolicyRule("*", protect="none")),
        default=PolicyRule(deploy=False))
    dep = CIMDeployment.deploy(params, policy)
    manual = _manual_compose(params, policy)
    assert _stores_equal(dep.stores, manual)
    deployed = {p for p, _, _ in dep.store_leaves()}
    assert {"embed", "unembed"} <= deployed
    faulty = dep.inject(jax.random.PRNGKey(1), 1e-3)
    got, gstats = faulty.read()
    flatm, td = jax.tree_util.tree_flatten(manual, is_leaf=cim._is_store)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flatm))
    manual_faulty = jax.tree_util.tree_unflatten(
        td, [cim.inject(k, s, 1e-3, "full") if cim._is_store(s) else s
             for k, s in zip(keys, flatm)])
    want, wstats = cim.read_pytree_impl(manual_faulty)
    assert _stores_equal(got, want)
    assert int(gstats["corrected"]) == int(wstats["corrected"])
    assert int(gstats["uncorrectable"]) == int(wstats["uncorrectable"])


# ---------------------------------------------------------------- dispatch

def test_linear_dispatch_fused_and_fallback_and_hbm():
    params = _params()
    dep = CIMDeployment.deploy(params, THREE_RULES)
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 32))

    # fused kernel route (one4n, fp16)
    out, info = dep.linear(x, "unembed", with_info=True)
    assert info["used_kernel"]
    w, _ = cim.read(dep._leaf("unembed")[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)

    # GSPMD reference fallback (per_weight cannot tile the kernel)
    xe = jax.random.normal(jax.random.PRNGKey(10), (4, 64))
    out, info = dep.linear(xe, "embed", with_info=True)
    assert not info["used_kernel"]

    # passthrough leaf: plain matmul against the raw array
    h48 = jax.random.normal(jax.random.PRNGKey(12), (4, 48))
    out, info = dep.linear(h48, "mlp/w2", with_info=True)
    assert info.get("route", "store") != "hbm"

    # explicit hbm rule: decode once, matmul the fp16 copy; ECC stats fold
    # into the cumulative counters
    hbm_policy = ReliabilityPolicy(
        default=PolicyRule(protect="one4n", serve_path="hbm"))
    dep2 = CIMDeployment.deploy({"proj": params["unembed"]}, hbm_policy)
    out, info = dep2.linear(x, "proj", with_info=True)
    assert info["route"] == "hbm"
    w2, _ = cim.read(dep2._leaf("proj")[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        x.astype(jnp.float32) @ w2), rtol=1e-6, atol=1e-6)

    with pytest.raises(KeyError, match="no leaf at path"):
        dep.linear(x, "does/not/exist")
    # dynamic scalars have no meaning on decode-once / passthrough routes —
    # silently serving a clean image would fake resilience
    from repro.kernels.cim_read import ops as cr_ops
    sc = cr_ops.make_scalars(cim.plane_seeds(jax.random.PRNGKey(0)), 1, 1)
    with pytest.raises(ValueError, match="hbm"):
        dep2.linear(x, "proj", scalars=sc)
    with pytest.raises(ValueError, match="passthrough"):
        dep.linear(jnp.ones((2, 32)), "norm", scalars=sc)
    # a Fig. 2 axis passed to inject would silently inject nothing
    with pytest.raises(ValueError, match="field"):
        dep.inject(jax.random.PRNGKey(0), 1e-3, field="exponent")


def test_linear_dispatch_sharded_on_one_device_mesh():
    """Mesh placement routes ``linear`` through the shard_map'd fused kernel
    (1-device mesh degrades to a single-shard program, logits unchanged)."""
    from repro.launch.mesh import make_host_mesh
    params = {"proj": _rand_w(jax.random.PRNGKey(1), 64, 128)}
    dep = CIMDeployment.deploy(params, ReliabilityPolicy())
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 64))
    ref = dep.linear(x, "proj")
    mesh = make_host_mesh(model_axis=1)
    placed = dep.shard(mesh)
    assert placed.placement == (mesh, "model", "j")
    out, info = placed.linear(x, "proj", with_info=True)
    assert info["sharded"] and info["used_kernel"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # module-level dispatch picks the mesh up from the ambient context too
    from repro.distributed import sharding as shlib
    store = placed._leaf("proj")[0]
    with shlib.use_mesh(mesh):
        out2, info2 = dispatch_linear(x, store, with_info=True)
    assert info2["sharded"]
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_read_rows_and_runtime_roundtrip():
    params = _params()
    dep = CIMDeployment.deploy(params, THREE_RULES)
    idx = jnp.asarray([0, 3, 17])
    rows = dep.read_rows(idx, "embed")
    full, _ = cim.read(dep._leaf("embed")[0])
    np.testing.assert_allclose(np.asarray(rows), np.asarray(full[idx]),
                               rtol=0, atol=0)
    rt = dep.runtime(jax.random.PRNGKey(0), 1e-3, field="exponent_sign")
    assert int(rt["thr_man"]) == 0 and int(rt["thr_meta"]) > 0
    assert set(rt["seeds"]) == {"man", "meta", "cw"}
    with pytest.raises(ValueError, match="field"):
        dep.runtime(jax.random.PRNGKey(0), 1e-3, field="exponent")


def test_serving_params_hbm_decode_and_dynamic_runtime():
    params = _params()
    policy = ReliabilityPolicy(
        rules=(PolicyRule("unembed", protect="one4n", serve_path="fused"),),
        default=PolicyRule(protect="one4n", serve_path="hbm"))
    dep = CIMDeployment.deploy(params, policy)
    served = dep.serving_params(dynamic_key=jax.random.PRNGKey(5), ber=1e-4)
    assert cim._is_store(served["unembed"])          # fused: stays packed
    assert not cim._is_store(served["embed"])        # hbm: decoded fp16
    assert "_cim" in served and int(served["_cim"]["thr_meta"]) > 0
    # no dynamic key -> no runtime entry
    assert "_cim" not in dep.serving_params()


def test_ecc_stats_accumulate_across_reads():
    params = {"w": _rand_w(jax.random.PRNGKey(0), 64, 64)}
    base = CIMDeployment.deploy(params, ReliabilityPolicy())
    dep = base.inject(jax.random.PRNGKey(1), 5e-3, field="exponent_sign")
    _, s1 = dep.read()
    _, s2 = dep.read()
    assert int(s1["corrected"]) == int(s2["corrected"]) > 0
    assert int(dep.ecc_stats["corrected"]) == 2 * int(s1["corrected"])
    # derived deployments own their counters: reads on one branch must not
    # bleed into siblings or the base
    assert int(base.ecc_stats["corrected"]) == 0
    sibling = base.inject(jax.random.PRNGKey(2), 5e-3, field="exponent_sign")
    assert int(sibling.ecc_stats["corrected"]) == 0


def test_deployment_passes_through_jit():
    params = _params()
    dep = CIMDeployment.deploy(params, THREE_RULES)

    @jax.jit
    def gap(d, key):
        restored, stats = d.inject(key, 1e-3).read()
        return restored["unembed"].sum(), stats

    total, stats = gap(dep, jax.random.PRNGKey(2))
    eager, estats = dep.inject(jax.random.PRNGKey(2), 1e-3).read()
    np.testing.assert_allclose(float(total), float(eager["unembed"].sum()),
                               rtol=1e-6)
    assert int(stats["corrected"]) == int(estats["corrected"])


# ------------------------------------------------------------- shims

def test_legacy_free_functions_are_deprecated_shims():
    params = {"w": _rand_w(jax.random.PRNGKey(0), 32, 16)}
    with pytest.deprecated_call():
        stores, _ = cim.deploy_pytree(params, cim.CIMConfig())
    with pytest.deprecated_call():
        faulty = cim.inject_pytree(jax.random.PRNGKey(1), stores, 1e-3)
    with pytest.deprecated_call():
        restored, _ = cim.read_pytree(faulty)
    # and the shims forward to the same implementation the deployment uses
    dep = CIMDeployment.deploy(params, ReliabilityPolicy())
    want, _ = dep.inject(jax.random.PRNGKey(1), 1e-3, field="full").read()
    assert np.array_equal(np.asarray(restored["w"]), np.asarray(want["w"]))


# ------------------------------------------------------------- policy sweeps

def test_run_policies_one_compile_and_manual_parity():
    from repro.core import resilience
    from repro.core.sweep import SweepEngine, SweepPlan, _split_schedule
    params = {"w": _rand_w(jax.random.PRNGKey(0), 64, 64)}
    target = params["w"].sum()

    def eval_fn(p):
        return -jnp.abs(p["w"].sum() - target)

    bers = (1e-4, 1e-3)
    arms = {"mixed": ReliabilityPolicy(
        rules=(PolicyRule("w", protect="one4n"),),
        default=PolicyRule(deploy=False))}
    plan = SweepPlan(bers=bers, n_trials=3, shard_trials=False)
    engine = SweepEngine(plan)
    results = resilience.characterize_policies(
        jax.random.PRNGKey(7), params, eval_fn, bers, arms, n_trials=3,
        engine=engine)
    assert [r.protect for r in results] == ["mixed", "mixed"]
    assert all(v == 1 for v in engine.compiles().values())

    # manual parity: same key schedule, per-trial inject through the
    # deployment, same accuracies
    dep = CIMDeployment.deploy(params, arms["mixed"])
    key, subs = _split_schedule(jax.random.PRNGKey(7), len(bers) * 3)
    subs = subs.reshape(len(bers), 3, -1)
    for i, ber in enumerate(bers):
        want = [float(eval_fn(dep.inject(subs[i, t], jnp.float32(ber))
                              .read()[0])) for t in range(3)]
        np.testing.assert_allclose(results[i].accuracies, want, rtol=1e-6)


# ------------------------------------------------------- training schedule

def test_training_fault_schedule_uniform_matches_legacy_streams():
    from repro.core import fault as fault_lib
    from repro.core.deployment import training_fault_schedule
    rel = ReliabilityConfig(mode="cim", ber=1e-3, protect="one4n",
                            inject="dynamic")
    corrupt = training_fault_schedule(rel)
    params = _params()
    key = jax.random.PRNGKey(4)
    got = corrupt(params, key)
    k1, k2 = jax.random.split(key)
    want = fault_lib.inject_pytree(
        k1, params, fault_lib.FaultModel(ber=rel.residual_exp_ber,
                                         field="exponent_sign", fmt=rel.fmt))
    want = fault_lib.inject_pytree(
        k2, want, fault_lib.FaultModel(ber=rel.ber, field="mantissa",
                                       fmt=rel.fmt))
    assert _stores_equal(got, want)
    assert training_fault_schedule(
        ReliabilityConfig(mode="cim", ber=0.0)) is None


def test_training_fault_schedule_respects_policy_rules():
    from repro.core.deployment import training_fault_schedule

    # the public path: a run's ReliabilityConfig carries the per-layer
    # policy via policy_override, and the training schedule applies it
    rel = ReliabilityConfig(
        mode="cim", ber=1e-2, inject="dynamic",
        policy_override=ReliabilityPolicy(
            rules=(PolicyRule("mlp/*", protect="none", field="mantissa"),),
            default=PolicyRule(deploy=False)))
    corrupt = training_fault_schedule(rel)
    params = _params()
    got = corrupt(params, jax.random.PRNGKey(1))
    # deploy=False leaves (embed/unembed/norm) are untouched; mlp leaves see
    # raw-BER faults
    assert np.array_equal(np.asarray(got["embed"]), np.asarray(params["embed"]))
    assert np.array_equal(np.asarray(got["norm"]), np.asarray(params["norm"]))
    assert not np.array_equal(np.asarray(got["mlp"]["w1"]),
                              np.asarray(params["mlp"]["w1"]))
    # the rule's field restriction holds: mantissa-only faults never touch
    # sign/exponent bits (matching CIMDeployment.inject on the same policy)
    from repro.core import bitops
    for leaf in ("w1", "w2"):
        s0, e0, _ = bitops.split_fields(params["mlp"][leaf])
        s1, e1, _ = bitops.split_fields(got["mlp"][leaf])
        assert np.array_equal(np.asarray(s0), np.asarray(s1))
        assert np.array_equal(np.asarray(e0), np.asarray(e1))


# ------------------------------------------------- forced-8-device identity

def _run(tmp_path, name, script):
    path = tmp_path / name
    path.write_text(script)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(path)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_MESH_IDENTITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    import numpy as np
    from repro import CIMDeployment, PolicyRule, ReliabilityPolicy
    from repro.core import cim

    def rw(key, k, j):
        w = jax.random.normal(key, (k, j)) * 0.1
        return jnp.asarray(jnp.asarray(w, jnp.float16), jnp.float32)

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"embed": rw(ks[0], 128, 64), "unembed": rw(ks[1], 64, 128),
              "mlp": {"w1": rw(ks[2], 64, 128)}, "norm": jnp.ones((64,))}
    policy = ReliabilityPolicy(
        rules=(PolicyRule("unembed", protect="one4n"),
               PolicyRule("embed", protect="none"),
               PolicyRule("mlp/*", protect="none", field="mantissa")),
        default=PolicyRule(deploy=False))

    key = jax.random.PRNGKey(5)
    ref = CIMDeployment.deploy(params, policy)
    ref_faulty = ref.inject(key, 2e-3)
    ref_params, ref_stats = ref_faulty.read()

    mesh = auto_mesh((8,), ("model",))
    dep = CIMDeployment.deploy(params, policy).shard(mesh)
    inject = jax.jit(lambda d, k: d.inject(k, 2e-3))
    faulty = inject(dep, key)

    same_planes = True
    for (pa, ra, sa), (pb, rb, sb) in zip(ref_faulty.store_leaves(),
                                          faulty.store_leaves()):
        assert pa == pb and ra == rb
        for name, plane in cim._plane_dict(sa).items():
            q = cim._plane_dict(sb)[name]
            same_planes &= bool(np.array_equal(np.asarray(plane),
                                               np.asarray(q)))
    got_params, got_stats = faulty.read()
    same_reads = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                                     jax.tree_util.tree_leaves(got_params)))
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 64))
    ref_out = ref_faulty.linear(x, "unembed")
    out, info = faulty.linear(x, "unembed", with_info=True)
    print(json.dumps({
        "same_planes": bool(same_planes),
        "same_reads": bool(same_reads),
        "stats_equal": int(ref_stats["corrected"]) == int(got_stats["corrected"])
            and int(ref_stats["uncorrectable"]) == int(got_stats["uncorrectable"]),
        "sharded_linear": bool(info["sharded"]) and bool(info["used_kernel"]),
        "linear_close": bool(np.allclose(np.asarray(out), np.asarray(ref_out),
                                         rtol=1e-5, atol=1e-5)),
    }))
""")


def test_mixed_policy_bit_identical_on_8_device_mesh(tmp_path):
    """The 3-rule policy deployment sharded over a forced-8-device "model"
    mesh draws the same inject streams, decodes the same weights, reports
    the same ECC stats, and serves the same logits as the single-device
    deployment (per-shard counter-PRNG offsets at global store coords)."""
    res = _run(tmp_path, "mesh_identity.py", _MESH_IDENTITY_SCRIPT)
    assert res == {"same_planes": True, "same_reads": True,
                   "stats_equal": True, "sharded_linear": True,
                   "linear_close": True}
