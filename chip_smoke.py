#!/usr/bin/env python3
"""Smoke run of the CIM serving engine on a TPU, at the published olmo-1b
width (16 layers, d_model 2048, vocab 50304; random weights from a seed).

    python chip_smoke.py               # one chip: kernel, serving, reference
    python chip_smoke.py --four-chips  # four chips: the replica fleet only

Everything runs in this one process (a chip belongs to one process), with
the package imported from ``src/`` as ``python -m repro.launch.serve`` does.
Phases, each of which ends the script with a non-zero exit when it fails:

1. device     the first JAX device must be a TPU (no CPU continuation);
2. kernel     one fused SECDED-decode + matmul read at the unembed width
              (K=2048, J=50304) for decode (m=8) and prefill (m=128) rows,
              compiled by Mosaic; the decoded image must equal the fp16
              rounding of the aligned source bitwise, and the read must
              match ``x @ w_fp16`` at highest precision; the row-cache
              (static) route against the kernel;
3. serving    ``repro.launch.serve.main`` with ``--engine --cim --protect
              one4n``, dynamic then static injection at BER 1e-4;
4. reference  at BER 0, the engine's prefill logits for one prompt against
              ``lm.forward`` on the fp16-rounded aligned source weights
              (built without the decoder).

``--four-chips`` runs only the fleet: four one-chip replicas
(``--fleet 4 --mesh 1x1``) behind the router, each replica's image on its own
device, and the solo-replay probe as the comparison.

Earlier lines report phase wall times, compile time and peak device memory;
the last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
ARTIFACTS = os.path.join(ROOT, "artifacts")
SEED = 0


class PhaseError(AssertionError):
    """A smoke check that did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def phase_device():
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX sees {devs[0].platform} devices ({len(devs)})")
    log(f"device {devs[0].device_kind} x{len(devs)} "
        f"(jax {jax.__version__})")
    return devs


def _elementwise_bound(x, w, k: int):
    """Worst-case float32 error of any summation order of ``x @ w``:
    ``k * 2^-24 * (|x| @ |w|)`` (the classic dot-product bound). It also
    covers a multi-pass bf16 product, whose error per term is below
    ``2^-16 |x_i w_i|``."""
    import jax.numpy as jnp
    return k * 2.0 ** -24 * jnp.matmul(jnp.abs(x), jnp.abs(w),
                                       precision="highest")


def phase_kernel():
    """One fused read at the unembed width through the serving entry point
    (``cim_linear_store(..., with_info=True)``): zero-threshold dynamic
    scalars select the in-kernel injection route, which then draws no
    flips. At BER 0 the decoded image must be the fp16 rounding of the
    aligned source, bit for bit: the checks below compare against that
    rounding, so a decode fault shared by ``cim.read`` and the kernel
    cannot cancel out."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import align, cim
    from repro.core import deployment as dep_lib
    from repro.kernels.cim_read import ops as cr_ops

    cfg = get_config("olmo-1b")
    k, j = cfg.d_model, cfg.vocab_size
    ccfg = cim.CIMConfig(protect="one4n")
    kw, kx, ks = jax.random.split(jax.random.PRNGKey(SEED), 3)

    @jax.jit
    def build(key):
        w = jax.random.normal(key, (k, j)) * 0.02
        w_al, _ = align.align_matrix(
            w, align.AlignmentConfig(n_group=ccfg.n_group, index=ccfg.index))
        w16 = w_al.astype(jnp.float16).astype(jnp.float32)
        return w16, cim.pack(w_al, ccfg)

    w16, store = build(kw)
    decoded = jax.jit(lambda s: cim.read(s)[0])(store)
    bits = jax.jit(lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32))
    n_diff = int(jnp.sum(bits(decoded) != bits(w16)))
    check(n_diff == 0, f"decoded image differs from the fp16 source in "
          f"{n_diff} of {k * j} weights")
    scalars = cr_ops.make_scalars(cim.plane_seeds(ks), 0, 0)
    cached = cim.build_row_cache(store)
    for m in (8, 128):
        x = jax.random.normal(jax.random.fold_in(kx, m), (m, k))
        out, info = cr_ops.cim_linear_store(x, store, scalars=scalars,
                                            with_info=True)
        check(info["used_kernel"], f"m={m}: reference fallback taken")
        check(not info["interpret"], f"m={m}: kernel ran in interpret mode")
        hlo = jax.jit(lambda x_, s_, sc_: cr_ops.cim_linear_store(
            x_, s_, scalars=sc_)).lower(x, store, scalars).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"m={m}: no tpu_custom_call in the compiled read")
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda x_, w_: x_ @ w_)(x, w16)
        bound = _elementwise_bound(x, w16, k)
        err = jnp.abs(out - ref)
        check(bool(jnp.all(jnp.isfinite(out))), f"m={m}: non-finite output")
        check(bool(jnp.all(err <= bound)),
              f"m={m}: kernel vs x @ w_fp16 off by {float(jnp.max(err))} "
              f"(bound {float(jnp.min(bound))}..)")
        static, sinfo = dep_lib.dispatch_linear(x, cached, with_info=True)
        check(sinfo.get("route") == "cached", f"m={m}: row cache not used")
        serr = jnp.abs(static - out)
        check(bool(jnp.all(serr <= bound)),
              f"m={m}: static (row cache) vs dynamic kernel read off by "
              f"{float(jnp.max(serr))}")
        log(f"kernel m={m}: tiles {info['tiles']} hoist {info['hoist']}; "
            f"max |kernel - ref| {float(jnp.max(err)):.3e}, "
            f"max |static - kernel| {float(jnp.max(serr)):.3e}, "
            f"max |out| {float(jnp.max(jnp.abs(ref))):.3e}, "
            f"bound >= {float(jnp.min(bound)):.3e}")


SERVE_ARGS = ["--arch", "olmo-1b", "--engine", "--cim", "--protect", "one4n",
              "--ber", "1e-4", "--slots", "4", "--chunk", "16",
              "--requests", "6", "--prompt-range", "8,32",
              "--gen-range", "4,8", "--seed", str(SEED)]


def _check_engine_json(path: str, n_requests: int, dynamic: bool) -> dict:
    with open(path) as f:
        d = json.load(f)
    reqs = d["requests"]
    check(len(reqs) == n_requests,
          f"{path}: {len(reqs)} of {n_requests} requests finished")
    check(all(r["finite"] for r in reqs), f"{path}: non-finite logits")
    check(all(r["n_tokens"] >= 1 for r in reqs), f"{path}: empty request")
    if dynamic:
        check(all(r["ecc"]["reads"] >= 1 for r in reqs),
              f"{path}: a request charged no ECC read")
    return d["aggregate"]


def phase_serving():
    from repro.launch import serve
    n = int(SERVE_ARGS[SERVE_ARGS.index("--requests") + 1])
    for inject in ("dynamic", "static"):
        path = os.path.join(ARTIFACTS, f"chip_smoke_{inject}.json")
        t0 = time.perf_counter()
        serve.main(SERVE_ARGS + ["--inject", inject, "--engine-json", path])
        gc.collect()
        agg = _check_engine_json(path, n, dynamic=inject == "dynamic")
        log(f"serving {inject}: {agg['n_requests']} requests, "
            f"{agg['total_tokens']} tokens, ECC {agg['ecc']}, "
            f"{time.perf_counter() - t0:.1f} s wall")


def phase_reference():
    """Engine prefill logits vs ``lm.forward`` on the source weights.

    The reference weights are the source parameters with every deployed
    matrix aligned and rounded to fp16, the image the store must hold at
    BER 0; they never pass through the decoder.

    Both run under ``jax.default_matmul_precision("highest")``: the model's
    block matmuls otherwise take one bf16 pass on the TPU, which rounds
    every operand to 8 significant bits and can swap a near-tied greedy
    token of random weights — a precision setting, not an engine fault.
    Tolerance: ``2e-4 * max|ref|``. The two sides differ only by float32
    reassociation (chunked prefill over the KV cache vs one full forward,
    the row-cache unembed vs ``x @ w``), about 1e-6 relative (a v5e
    measured 4.1e-6 of max |logit| 6.1); a wrong exponent or sign from the
    decode moves a weight by 2x or more, and a bf16 pass rounds each
    product by up to ~4e-3 relative.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import align
    from repro.launch import engine as engine_lib
    from repro.launch import serve
    from repro.models import lm

    cfg = get_config("olmo-1b")
    key = jax.random.PRNGKey(SEED)
    params = lm.init_lm(key, cfg)
    dkey = jax.random.fold_in(key, 1)
    dep = serve.make_deployment(params, ber=0.0, protect="one4n", n_group=8,
                                index=2, key=dkey, inject_mode="static",
                                field="full")
    sparams = dep.serving_params(**serve.serving_kw(
        ber=0.0, key=dkey, inject_mode="static", field="full"))
    def source(w, acfg):
        # op by op, as CIMDeployment.deploy aligns: under one jit the TPU
        # fuses the rescale differently and rounds some weights to the
        # neighbouring fp16 value
        return align.align_matrix(w, acfg)[0].astype(jnp.float16).astype(
            w.dtype)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    check(len(leaves) == len(dep.rules), "deployment rules do not line up "
          "with the parameter leaves")
    ref_params = jax.tree_util.tree_unflatten(treedef, [
        leaf if rule is None else source(leaf, rule.align_cfg)
        for leaf, rule in zip(leaves, dep.rules)])
    check(sum(rule is not None for rule in dep.rules) == 2,
          "expected the embed and unembed matrices deployed")
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng = engine_lib.Engine(cfg, sparams, n_slots=4, max_len=32,
                                chunk=16, collect_logits=True)
        res, _ = eng.run([engine_lib.Request(rid=0, tokens=prompt,
                                             max_new=1)])
        ref = jax.jit(lambda p, t: lm.forward(p, cfg, {"tokens": t},
                                              remat=False)[0])(
            ref_params, prompt[None])
    got = np.asarray(res[0].logits[0], np.float32)
    want = np.asarray(ref[0, -1], np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    check(np.isfinite(got).all(), "engine prefill logits not finite")
    check(err <= 2e-4 * scale,
          f"engine vs reference logits off by {err:.3e} (max |ref| "
          f"{scale:.3e})")
    check(int(np.argmax(got)) == int(np.argmax(want)),
          f"greedy first token {int(np.argmax(got))} != reference "
          f"{int(np.argmax(want))}")
    log(f"reference: max |engine - forward| {err:.3e} of max |logit| "
        f"{scale:.3e}; greedy token {int(np.argmax(got))} on both")


def phase_fleet(devs):
    """Four one-chip replicas behind the router; the probe re-serves one
    request solo off the same spool and must match tokens and ECC counts."""
    from repro.launch import serve
    check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    path = os.path.join(ARTIFACTS, "chip_smoke_fleet.json")
    n = 8
    serve.main(["--arch", "olmo-1b", "--cim", "--protect", "one4n",
                "--inject", "dynamic", "--ber", "1e-4", "--fleet", "4",
                "--mesh", "1x1", "--slots", "2", "--chunk", "16",
                "--requests", str(n), "--prompt-range", "8,24",
                "--gen-range", "3,6", "--probe", "3", "--seed", str(SEED),
                "--engine-json", path])
    agg = _check_engine_json(path, n, dynamic=True)
    with open(path) as f:
        d = json.load(f)
    placed = d["replica_devices"]
    check(len(placed) == 4 and all(len(v) == 1 for v in placed.values())
          and len({v[0] for v in placed.values()}) == 4,
          f"replicas do not sit one per device: {placed}")
    check(d["probe"]["ok"], f"solo-replay probe diverged: {d['probe']}")
    log(f"fleet: {agg['n_requests']} requests, routed "
        f"{agg['requests_by_replica']}, replica devices {placed}, probe "
        f"rid={d['probe']['rid']} tokens {d['probe']['tokens_equal']} "
        f"ecc {d['probe']['ecc_equal']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}: run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch import compile_cache
    cache_dir = compile_cache.configure()
    import jax

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    walls = {}
    try:
        t0 = time.perf_counter()
        devs = phase_device()
        walls["device"] = time.perf_counter() - t0
        os.makedirs(ARTIFACTS, exist_ok=True)
        if args.four_chips:
            phases = [("fleet", lambda: phase_fleet(devs))]
        else:
            phases = [("kernel", phase_kernel), ("serving", phase_serving),
                      ("reference", phase_reference)]
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            gc.collect()
            walls[name] = time.perf_counter() - t0
            mem = devs[0].memory_stats() or {}
            log(f"phase {name} ok in {walls[name]:.1f} s; device 0 holds "
                f"{mem.get('bytes_in_use')} bytes, peak so far "
                f"{mem.get('peak_bytes_in_use')}")
    except Exception as e:                       # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devs[:4 if args.four_chips else 1]]
    log("phase wall s "
        + json.dumps({k: round(v, 3) for k, v in walls.items()}))
    log(f"compile s {sum(compile_s):.1f} over {len(compile_s)} compiles; "
        f"cache {cache_dir}")
    log(f"peak HBM bytes per device {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
