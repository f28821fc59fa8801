"""Mesh-sharded CIM store: real multi-device equivalence (subprocess with 8
forced host devices, same pattern as ``tests/test_distributed.py``).

Acceptance contracts of the mesh-native deployment:

* ``shard_store`` + ``inject_sharded`` is **bit-identical** to the
  single-device packed image for the same key, across >=2 mesh shapes and
  both shard layouts (per-shard counter-PRNG offsets put every local block's
  flip stream at its global store coordinates);
* the ``shard_map``'d fused decode+matmul (static and per-read dynamic)
  matches the single-device kernel, including the 'k' layout's psum over the
  contracted axis;
* end-to-end: the sharded fused serve path matches ``hbm`` logits within
  fp16 tolerance on a (2 data, 4 model) mesh;
* a Fig. 6 protection arm on a 2-D ("trial", "model") sweep mesh returns
  exactly the single-device engine's accuracies and ECC stats.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest


def _run(tmp_path, name, script):
    path = tmp_path / name
    path.write_text(script)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(path)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_INJECT_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    import numpy as np
    from repro.core import align, cim
    from repro.kernels.cim_read import ops as cr_ops
    from repro.kernels.fault_inject.ops import ber_to_threshold

    key = jax.random.PRNGKey(3)
    thr = ber_to_threshold(0.005)
    seeds = cim.plane_seeds(key)
    sc = cr_ops.make_scalars(seeds, thr, thr)
    checked = []
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 0.1
    w_al, _ = align.align_matrix(w, align.AlignmentConfig(8, 2))
    w16 = jnp.asarray(jnp.asarray(w, jnp.float16), jnp.float32)
    meshes = [auto_mesh((2,), ("model",)),
              auto_mesh((2, 4), ("data", "model"))]

    def plane_equal(a, b):
        for name, p in cim._plane_dict(a).items():
            q = cim._plane_dict(b)[name]
            assert (np.asarray(p) == np.asarray(q)).all(), name

    # (1) bit-identical sharded inject for every protect mode, 2 mesh shapes
    for protect in ("one4n", "none", "per_weight"):
        store = cim.pack(w16 if protect == "per_weight" else w_al,
                         cim.CIMConfig(protect=protect))
        ref = cim.inject(key, store, 0.005, "full")
        rr, sr = cim.read_reference(ref)
        for mesh in meshes:
            for dim in ("j", "k"):
                st = cim.shard_store(store, mesh, dim=dim)
                inj = jax.jit(lambda k, s, m=mesh, d=dim:
                              cim.inject_sharded(k, s, 0.005, "full",
                                                 mesh=m, dim=d))
                got = inj(key, st)
                plane_equal(ref, got)
                checked.append([protect, mesh.shape["model"], dim, "inject"])
        # planes are bit-equal on every mesh/dim, so one per-bit oracle
        # decode of a sharded image suffices per protect mode
        rg, sg = cim.read_reference(got)
        a, b = np.asarray(rr), np.asarray(rg)
        assert ((a == b) | (np.isnan(a) & np.isnan(b))).all()
        assert int(sr["uncorrectable"]) == int(sg["uncorrectable"])

    # (2) shard_map'd fused kernel: static + dynamic vs single device,
    #     'j' (column groups) and 'k' (psum over the contraction)
    store = cim.pack(w_al, cim.CIMConfig(protect="one4n"))
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 128))
    ref_s = np.asarray(cr_ops.cim_linear_store(x, store))
    ref_d = np.asarray(cr_ops.cim_linear_store(x, store, scalars=sc))
    for mesh in meshes:
        for dim in ("j", "k"):
            st = cim.shard_store(store, mesh, dim=dim)
            out, info = cr_ops.cim_linear_store_sharded(
                x, st, mesh=mesh, dim=dim, with_info=True)
            assert info["sharded"], (mesh.shape, dim)
            np.testing.assert_allclose(np.asarray(out), ref_s,
                                       rtol=1e-5, atol=1e-5)
            out_d = cr_ops.cim_linear_store_sharded(x, st, scalars=sc,
                                                    mesh=mesh, dim=dim)
            np.testing.assert_allclose(np.asarray(out_d), ref_d,
                                       rtol=1e-4, atol=1e-4)
            checked.append(["one4n", mesh.shape["model"], dim, "linear"])
    print(json.dumps({"checked": len(checked)}))
""")


@pytest.mark.slow
def test_sharded_inject_and_linear_bit_identical(tmp_path):
    result = _run(tmp_path, "sharded_equiv.py", _INJECT_EQUIV_SCRIPT)
    assert result["checked"] >= 14   # 3 protects x 2 meshes x 2 dims + linear


_TILE_STREAM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    import numpy as np
    from repro.core import align, cim
    from repro.kernels.cim_read import ops as cr_ops
    from repro.kernels.fault_inject.ops import ber_to_threshold

    def bits(a):
        return np.asarray(jax.lax.bitcast_convert_type(
            jnp.asarray(a, jnp.float32), jnp.uint32))

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256)) * 0.1
    w_al, _ = align.align_matrix(w, align.AlignmentConfig(8, 2))
    store = cim.pack(w_al, cim.CIMConfig(protect="one4n"))
    key = jax.random.PRNGKey(11)
    seeds = cim.plane_seeds(key)
    thr = ber_to_threshold(0.003)
    sc = cr_ops.make_scalars(seeds, thr, thr)
    host = cim.inject_with_seeds(store, seeds, thr, thr)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 256))
    mesh = auto_mesh((8,), ("model",))
    checked = []
    # every autotuned tile combo, both shard layouts: the per-shard kernels
    # must draw flip streams at GLOBAL store coordinates (SCALAR_OFF_K/J
    # offsets), so the sharded dynamic read equals the sharded static read
    # of the host-injected image for the same key — bitwise
    for bm, bn, bk, hoist in cr_ops.autotuned_tile_shapes(store):
        for dim in ("j", "k"):
            st = cim.shard_store(store, mesh, dim=dim)
            st_host = cim.shard_store(host, mesh, dim=dim)
            dyn, info = cr_ops.cim_linear_store_sharded(
                x, st, scalars=sc, mesh=mesh, dim=dim, block_m=bm,
                block_n=bn, block_k=bk, hoist=hoist, with_info=True)
            assert info["sharded"], (dim, bm, bn, bk)
            static = cr_ops.cim_linear_store_sharded(
                x, st_host, mesh=mesh, dim=dim, block_m=bm, block_n=bn,
                block_k=bk, hoist=hoist)
            assert (bits(dyn) == bits(static)).all(), (dim, bm, bn, bk)
            checked.append([dim, bm, bn, bk, hoist])
    # cross-check against the single-device dynamic kernel (same key): the
    # 'j' layout splits pure column groups, so it stays bitwise; 'k' psums
    # partial products and is checked to fp32 tolerance
    ref_d = np.asarray(cr_ops.cim_linear_store(x, store, scalars=sc))
    for dim in ("j", "k"):
        st = cim.shard_store(store, mesh, dim=dim)
        out = np.asarray(cr_ops.cim_linear_store_sharded(
            x, st, scalars=sc, mesh=mesh, dim=dim))
        if dim == "j":
            assert (bits(out) == bits(ref_d)).all()
        else:
            np.testing.assert_allclose(out, ref_d, rtol=1e-5, atol=1e-5)
        checked.append([dim, "vs_1dev"])
    print(json.dumps({"checked": len(checked),
                      "n_tiles": len(cr_ops.autotuned_tile_shapes(store))}))
""")


@pytest.mark.slow
def test_sharded_dynamic_stream_identity_every_tile(tmp_path):
    """Satellite contract: on a forced-8-device "model" mesh, the shard_map'd
    kernel's per-read dynamic flip streams equal ``cim.inject_with_seeds``
    (static == dynamic for the same key) for EVERY autotuned tile shape and
    both shard layouts."""
    result = _run(tmp_path, "tile_stream.py", _TILE_STREAM_SCRIPT)
    assert result["n_tiles"] >= 2, result
    assert result["checked"] >= 2 * result["n_tiles"] + 2, result


_SERVE_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.distributed import sharding as shlib
    from repro.launch import serve as serve_lib
    from repro.models import lm

    cfg = get_config("olmo-1b").reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    dkey = jax.random.fold_in(key, 1)
    stores = serve_lib.deploy_fused(params, ber=1e-3, protect="one4n",
                                    n_group=8, index=2, key=dkey,
                                    inject_mode="static", field="full")
    hbm, _ = serve_lib.deploy(params, ber=1e-3, protect="one4n", n_group=8,
                              index=2, key=dkey)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 8)))
    lb, cb = lm.prefill(hbm, cfg, {"tokens": tokens})

    mesh = serve_lib.make_serve_mesh("2x4")
    shlib.set_mesh(mesh)
    placed = serve_lib.place_on_mesh(stores, mesh)
    unembed_shards = len(placed["unembed"].man.sharding.device_set)
    lf, cf = lm.prefill(placed, cfg, {"tokens": tokens})
    diff = float(np.abs(np.asarray(lf) - np.asarray(lb)).max())
    toks = jnp.argmax(lb, -1)[:, None]
    def grow(a):
        if a.ndim >= 4 and a.shape[-3] == 8:
            pad = [(0, 0)] * a.ndim; pad[-3] = (0, 2)
            return jnp.pad(a, pad)
        return a
    cf = jax.tree_util.tree_map(grow, cf)
    cb = jax.tree_util.tree_map(grow, cb)
    lf2, _ = lm.decode(placed, cfg, cf, toks)
    lb2, _ = lm.decode(hbm, cfg, cb, toks)
    diff2 = float(np.abs(np.asarray(lf2) - np.asarray(lb2)).max())
    print(json.dumps({"prefill_diff": diff, "decode_diff": diff2,
                      "unembed_shards": unembed_shards}))
""")


@pytest.mark.slow
def test_sharded_fused_serve_matches_hbm_logits(tmp_path):
    """Acceptance: the fused sharded serve path matches hbm logits within
    fp16 tolerance on a (2 data, 4 model) mesh, and the unembed store's
    planes are really distributed across devices."""
    result = _run(tmp_path, "sharded_serve.py", _SERVE_EQUIV_SCRIPT)
    assert result["prefill_diff"] < 1e-3, result
    assert result["decode_diff"] < 1e-3, result
    assert result["unembed_shards"] == 8, result


_SWEEP_COMPOSE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import sweep as sweep_lib
    from repro.launch.mesh import make_sweep_mesh

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w1": jax.random.normal(k1, (16, 64)) * 0.3,
              "w2": jax.random.normal(k2, (64, 16)) * 0.3}
    xe = jax.random.normal(jax.random.PRNGKey(5), (256, 16))
    ye = jnp.argmax(xe @ jax.random.normal(jax.random.PRNGKey(6), (16, 16)), -1)

    def eval_fn(p):
        h = jax.nn.relu(xe @ p["w1"])
        return jnp.mean(jnp.argmax(h @ p["w2"], -1) == ye)

    plan = sweep_lib.SweepPlan(bers=(1e-3, 1e-2), n_trials=8,
                               protects=("none", "one4n"))
    ref = sweep_lib.SweepEngine(plan, mesh=None).run_protection(
        jax.random.PRNGKey(9), params, eval_fn)
    mesh = make_sweep_mesh(model_axis=2)          # (4 trial, 2 model)
    eng = sweep_lib.SweepEngine(plan, mesh=mesh)
    got = eng.run_protection(jax.random.PRNGKey(9), params, eval_fn)
    same = all(a.accuracies == b.accuracies
               and (a.corrected, a.uncorrectable)
               == (b.corrected, b.uncorrectable)
               for a, b in zip(ref, got))
    compiles = max(eng.compiles().values())
    print(json.dumps({"cells": len(got), "identical": same,
                      "trial": mesh.shape["trial"],
                      "model": mesh.shape["model"],
                      "compiles_per_arm": compiles}))
""")


@pytest.mark.slow
def test_sweep_composes_trial_and_model_sharding(tmp_path):
    """A Fig. 6 arm on a ("trial", "model") mesh spans the whole mesh and
    returns exactly the single-device engine's numbers, still compiling once
    per arm."""
    result = _run(tmp_path, "sweep_compose.py", _SWEEP_COMPOSE_SCRIPT)
    assert result["identical"], result
    assert result["cells"] == 4
    assert (result["trial"], result["model"]) == (4, 2)
    assert result["compiles_per_arm"] == 1
