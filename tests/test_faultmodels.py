"""Fault-model zoo (``repro.core.faultmodels``): process grammar, stream
identity, and cross-path/cross-device reproducibility.

Acceptance contracts:

* the default ``iid`` process is **bit-for-bit** the legacy counter-PRNG
  stream — static inject across all three protect modes, the dynamic
  per-read path, and the fused kernel scalars;
* every non-trivial process draws a flip set that is a **subset** of the
  iid flips at the same (key, BER) — model thresholds only ever scale down;
* drift is monotone in the tick (larger tick ⇒ superset flips) and
  ``tick=0`` is exactly iid;
* burst / drift masks are identical on 1 device vs a forced-8-device mesh,
  both shard layouts (subprocess; same pattern as test_sharded_store.py);
* the sweep engine's fault-model axis tags results and keeps the default
  ``("iid",)`` plan's streams unchanged.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import align, cim
from repro.core import faultmodels as fm
from repro.kernels.cim_read import ops as cr_ops
from repro.kernels.fault_inject.ops import ber_to_threshold


def _plane_equal(a, b):
    for name, p in cim._plane_dict(a).items():
        q = cim._plane_dict(b)[name]
        assert (np.asarray(p) == np.asarray(q)).all(), name


def _stores(w_shape=(64, 64), seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), w_shape) * 0.1
    w_al, _ = align.align_matrix(w, align.AlignmentConfig(8, 2))
    w16 = jnp.asarray(jnp.asarray(w, jnp.float16), jnp.float32)
    out = {}
    for protect in ("one4n", "none", "per_weight"):
        src = w16 if protect == "per_weight" else w_al
        out[protect] = cim.pack(src, cim.CIMConfig(protect=protect))
    return out


def _flip_words(clean, faulty):
    """Total differing words across planes (the incident flip mass)."""
    n = 0
    for name, p in cim._plane_dict(clean).items():
        q = cim._plane_dict(faulty)[name]
        n += int((np.asarray(p) != np.asarray(q)).sum())
    return n


def _flip_subset(clean, a, b):
    """Every bit flipped in ``a`` is also flipped in ``b`` (vs clean)."""
    for name, p in cim._plane_dict(clean).items():
        base = np.asarray(p)
        fa = base ^ np.asarray(cim._plane_dict(a)[name])
        fb = base ^ np.asarray(cim._plane_dict(b)[name])
        assert (fa & ~fb).sum() == 0, name


# ---------------------------------------------------------------- grammar


def test_grammar_parses_and_validates():
    p = fm.parse_fault_model("burst:rate=0.3,length=8,axis=col")
    assert (p.kind, p.rate, p.length, p.axis) == ("burst", 0.3, 8, "col")
    assert fm.parse_fault_model("") is None
    assert fm.parse_fault_model(None) is None
    assert fm.parse_fault_model(p) is p
    assert fm.parse_fault_model("drift").kind == "drift"
    assert fm.parse_fault_model("correlated:strength=0.9").strength == 0.9
    with pytest.raises(ValueError):
        fm.parse_fault_model("gamma:rate=0.1")
    with pytest.raises(ValueError):
        fm.parse_fault_model("burst:bogus=1")
    with pytest.raises(ValueError):
        fm.FaultProcess(kind="burst", axis="diag")


def test_process_is_static_pytree():
    p = fm.FaultProcess.burst(rate=0.5, length=4)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    assert leaves == []          # leafless: rides through jit as structure
    assert jax.tree_util.tree_unflatten(treedef, leaves) == p
    hash(p)                      # usable as a static_argnames value


# ---------------------------------------------------- iid stream identity


def test_iid_bitwise_equals_legacy_static_inject():
    key = jax.random.PRNGKey(11)
    for protect, store in _stores().items():
        legacy = cim.inject(key, store, 0.01, "full")
        for model in (None, fm.FaultProcess.iid(),
                      fm.parse_fault_model("iid")):
            _plane_equal(legacy, cim.inject(key, store, 0.01, "full",
                                            model=model))
        # a drift process at tick=0 is exactly the base BER
        _plane_equal(legacy, cim.inject(key, store, 0.01, "full",
                                        model=fm.FaultProcess.drift()))


def test_iid_bitwise_equals_legacy_dynamic_and_kernel():
    key = jax.random.PRNGKey(12)
    store = _stores()["one4n"]
    seeds = cim.plane_seeds(key)
    thr = ber_to_threshold(0.005)
    legacy = cim.inject_with_seeds(store, seeds, thr, thr)
    _plane_equal(legacy, cim.inject_with_seeds(store, seeds, thr, thr,
                                               model=fm.FaultProcess.iid()))
    # fused kernel: iid scalars produce bit-identical outputs to legacy
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
    sc0 = cr_ops.make_scalars(seeds, thr, thr)
    sc1 = cr_ops.make_scalars(seeds, thr, thr, model=fm.FaultProcess.iid())
    y0 = np.asarray(cr_ops.cim_linear_store(x, store, scalars=sc0))
    y1 = np.asarray(cr_ops.cim_linear_store(x, store, scalars=sc1,
                                            model=fm.FaultProcess.iid()))
    assert (y0 == y1).all()


# ------------------------------------------------------- model semantics


@pytest.mark.parametrize("spec", [
    "burst:rate=0.5,length=4,axis=row",
    "burst:rate=0.5,length=4,axis=col",
    "burst:rate=0.5,length=8,axis=bank",
    "correlated:strength=0.8,period=4",
])
def test_model_flips_subset_of_iid(spec):
    key = jax.random.PRNGKey(21)
    model = fm.parse_fault_model(spec)
    for protect, store in _stores().items():
        iid = cim.inject(key, store, 0.02, "full")
        got = cim.inject(key, store, 0.02, "full", model=model)
        _flip_subset(store, got, iid)
        assert _flip_words(store, got) < _flip_words(store, iid), \
            (protect, spec)   # the process actually thins the stream


def test_burst_concentrates_flips():
    # burst flips cluster into hit units: fewer distinct mantissa rows carry
    # flips than under iid at matched incident rate
    key = jax.random.PRNGKey(22)
    store = _stores((128, 64))["one4n"]
    iid = cim.inject(key, store, 0.02, "full")
    got = cim.inject(key, store, 0.02, "full",
                     model=fm.FaultProcess.burst(rate=0.3, length=4))
    def rows_hit(faulty):
        d = np.asarray(store.man) != np.asarray(faulty.man)
        return int(d.any(1).sum())
    assert 0 < rows_hit(got) < rows_hit(iid)


def test_drift_monotone_and_tick0_identity():
    key = jax.random.PRNGKey(23)
    store = _stores()["one4n"]
    model = fm.FaultProcess.drift(drift_rate=0.5)
    iid = cim.inject(key, store, 0.005, "full")
    t0 = cim.inject(key, store, 0.005, "full", model=model)
    _plane_equal(iid, t0)        # tick=0: no elapsed time, exactly iid
    prev, prev_n = store, 0
    import dataclasses
    for tick in (1, 4, 16):
        cur = cim.inject(key, store, 0.005, "full",
                         model=dataclasses.replace(model, tick=tick))
        _flip_subset(store, prev, cur)       # superset as time advances
        n = _flip_words(store, cur)
        assert n >= prev_n
        prev, prev_n = cur, n
    assert prev_n > _flip_words(store, iid)  # drift actually grew the BER
    # threshold curve saturates instead of wrapping
    thr = np.uint32(fm.drift_threshold(ber_to_threshold(0.005), 0.5, 1000))
    assert thr == np.uint32(0xFFFFFFFF)


def test_deployment_rule_fault_model():
    from repro.core import deployment as dep_lib
    with pytest.raises(ValueError):
        dep_lib.PolicyRule(fault_model="nope:x=1")
    rule = dep_lib.PolicyRule(fault_model="burst:rate=0.4,length=4")
    assert rule.fault_process.kind == "burst"
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.1}
    pol = dep_lib.ReliabilityPolicy(rules=(), default=rule)
    dep = dep_lib.CIMDeployment.deploy(params, pol)
    store = dep.store_leaves()[0][2]
    key = jax.random.PRNGKey(5)
    # rule-level process drives inject; an explicit model= overrides it
    via_rule = dep.inject(key, 0.02)
    k0 = jax.random.split(key, 1)[0]
    ref = cim.inject(k0, store, 0.02, "full", model=rule.fault_process)
    _plane_equal(ref, via_rule.store_leaves()[0][2])
    via_override = dep.inject(key, 0.02, model="iid")
    _plane_equal(cim.inject(k0, store, 0.02, "full"),
                 via_override.store_leaves()[0][2])


def test_sweep_fault_model_axis():
    from repro.core import sweep as sweep_lib
    from repro.core.resilience import characterize_protection
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 32)) * 0.1}

    def eval_fn(p):
        return -jnp.mean(jnp.abs(p["w"]))

    key = jax.random.PRNGKey(9)
    base = characterize_protection(key, params, eval_fn, bers=[1e-3],
                                   n_trials=2, protects=("one4n",))
    multi = characterize_protection(
        key, params, eval_fn, bers=[1e-3], n_trials=2, protects=("one4n",),
        fault_models=("iid", "burst:rate=0.5,length=4"))
    assert [r.fault_model for r in base] == ["iid"]
    assert sorted({r.fault_model for r in multi}) == \
        ["burst:rate=0.5,length=4", "iid"]
    # the iid arm of the widened plan draws the same streams as the default
    iid_arm = [r for r in multi if r.fault_model == "iid"]
    assert [r.accuracies for r in iid_arm] == [r.accuracies for r in base]
    with pytest.raises(ValueError):
        sweep_lib.SweepPlan(bers=(1e-3,), fault_models=("bogus:x=1",))


# ----------------------------------------- sharded mask identity (slow)


def _run(tmp_path, name, script):
    path = tmp_path / name
    path.write_text(script)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(path)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_SHARDED_MODEL_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    import numpy as np
    from repro.core import align, cim
    from repro.core import faultmodels as fm

    key = jax.random.PRNGKey(31)
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 0.1
    w_al, _ = align.align_matrix(w, align.AlignmentConfig(8, 2))
    store = cim.pack(w_al, cim.CIMConfig(protect="one4n"))
    meshes = [auto_mesh((2,), ("model",)),
              auto_mesh((8,), ("model",)),
              auto_mesh((2, 4), ("data", "model"))]
    models = [fm.FaultProcess.burst(rate=0.4, length=4, axis="row"),
              fm.FaultProcess.burst(rate=0.4, length=8, axis="col"),
              dataclasses.replace(fm.FaultProcess.drift(drift_rate=0.3),
                                  tick=5),
              fm.FaultProcess.correlated(strength=0.7, period=4)]

    def plane_equal(a, b):
        for name, p in cim._plane_dict(a).items():
            q = cim._plane_dict(b)[name]
            assert (np.asarray(p) == np.asarray(q)).all(), name

    checked = 0
    for model in models:
        ref = cim.inject(key, store, 0.01, "full", model=model)
        assert any((np.asarray(p) != np.asarray(q)).any()
                   for p, q in zip(cim._plane_dict(store).values(),
                                   cim._plane_dict(ref).values()))
        for mesh in meshes:
            for dim in ("j", "k"):
                st = cim.shard_store(store, mesh, dim=dim)
                got = jax.jit(lambda k, s, m=mesh, d=dim, mo=model:
                              cim.inject_sharded(k, s, 0.01, "full",
                                                 mesh=m, dim=d, model=mo)
                              )(key, st)
                plane_equal(ref, got)
                checked += 1
    print(json.dumps({"checked": checked}))
""")


@pytest.mark.slow
def test_model_masks_identical_across_mesh_shapes(tmp_path):
    result = _run(tmp_path, "sharded_models.py", _SHARDED_MODEL_SCRIPT)
    assert result["checked"] == 4 * 3 * 2   # models x meshes x shard dims
