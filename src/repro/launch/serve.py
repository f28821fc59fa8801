"""Serving launcher: batched prefill + decode with CIM-deployed weights.

The weight path mirrors deployment on a Unicorn-CIM macro: weights are
exponent-aligned and packed into the word-packed SRAM image (mantissa plane +
SECDED codeword words, or raw exponent rows + packed sign words).

Two serve paths (``--serve-path``):

* ``fused`` (default) — the model's CIM-deployed matrices stay **packed** for
  the whole run: the unembed projection runs through the fused decode-on-read
  Pallas kernel (``kernels/cim_read``: SECDED decode + FP16 reconstruction +
  matmul in VMEM) and the embedding table is decoded row-by-row at gather
  time. Decoded fp16 weight matrices never materialize in HBM. Supports
  static injection (``--inject static``: flip the image once, serve many) and
  per-read dynamic injection (``--inject dynamic``: every prefill/decode step
  draws fresh counter-PRNG faults in-kernel, keyed by the decode position).
* ``hbm`` — the legacy path: inject + ECC-decode once, rematerialize fp16
  weights, serve those (the baseline ``benchmarks/cim_store_bench.py``
  compares against).

Multi-device serving (``--mesh DATAxMODEL``, e.g. ``--mesh 2x4``): requests
are data-parallel (each "data" row of the mesh serves its own batch shard)
while every CIM store's packed planes are column-sharded over "model" — one
shard ≈ one macro column group, served through the ``shard_map``'d fused
kernel (``kernels/cim_read.ops.cim_linear_store_sharded``). tok/s is
reported per device and aggregate. ``--rounds`` turns the single batch into
a serving loop over successive request batches.

  python -m repro.launch.serve --arch olmo-1b --reduced --batch 4 \\
      --prompt-len 64 --gen 32 --cim --ber 1e-4 --protect one4n \\
      --serve-path fused --inject dynamic --mesh 2x4 --rounds 2

``--engine`` swaps the lock-step batch loop for the continuous-batching
engine (``repro.launch.engine``): a synthetic open-loop Poisson load of
``--requests`` requests at ``--rate`` req/s with ragged prompt/generation
lengths is scheduled through ``--slots`` decode slots (chunked prefill,
per-request counter-PRNG fault streams, per-request ECC + TTFT accounting;
``--engine-json`` writes the per-request artifact). This file stays a thin
frontend — the scheduler lives in ``repro.launch.engine``.

  python -m repro.launch.serve --arch olmo-1b --reduced --engine \\
      --cim --ber 1e-3 --inject dynamic --slots 4 --chunk 8 \\
      --requests 32 --rate 64 --prompt-range 4,24 --gen-range 4,12 \\
      --engine-json artifacts/engine.json
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import cim as cim_lib
from repro.core import deployment as dep_lib
from repro.core.api import ReliabilityConfig
from repro.data.synthetic import MarkovLM
from repro.distributed import sharding as shlib
from repro.launch import compile_cache
from repro.models import lm
from repro.training import steps as steps_lib


def serving_policy(*, protect: str, n_group: int, index: int,
                   field: str = "full", serve_path: str = "fused"
                   ) -> dep_lib.ReliabilityPolicy:
    """The serving launcher's deployment policy.

    ``fused``: only the big embedding/unembedding matrices deploy (block
    weights are scan-stacked >2-D and were never deployable) and stay
    packed. ``hbm``: every 2-D float matrix deploys, to be decoded once.

    Row-cache economics: the **unembed** projection needs the full decoded
    matrix on every decode step, so static serving warms its decoded-row
    cache once per fault image (a fault refresh only re-decodes this one
    leaf). The **embed** table opts out — each step gathers a handful of
    rows, decoded on read straight off the packed image, so a full decode
    (the thing the HBM path pays on every refresh) never happens for it.
    """
    rule = dep_lib.PolicyRule(pattern="*", protect=protect, n_group=n_group,
                              index=index, field=field, serve_path=serve_path)
    if serve_path == "hbm":
        return dep_lib.ReliabilityPolicy(rules=(), default=rule)
    return dep_lib.ReliabilityPolicy(
        rules=(dataclasses.replace(rule, pattern="embed", row_cache=False),
               dataclasses.replace(rule, pattern="unembed", row_cache=True)),
        default=dep_lib.PolicyRule(deploy=False))


def expert_serving_policy(*, protect: str, n_group: int, index: int,
                          field: str = "full", ber_scales: dict = None
                          ) -> dep_lib.ReliabilityPolicy:
    """Per-expert MoE deployment policy (``--expert-cim``).

    Every expert store (paths like ``groups/blk0/moe_win/g0/expert3``) gets
    the launcher's protection settings; ``ber_scales`` maps expert index ->
    BER scale for experts on weaker macros (``{3: 4.0}`` ages expert 3 of
    every MoE matrix 4x harder).
    """
    base = dep_lib.PolicyRule(pattern="*", protect=protect, n_group=n_group,
                              index=index, field=field, serve_path="hbm")
    rules = tuple(
        dataclasses.replace(base, pattern=f"*/expert{e}", ber_scale=s)
        for e, s in sorted((ber_scales or {}).items()))
    return dep_lib.ReliabilityPolicy(rules=rules, default=base)


def deploy(params, *, ber: float, protect: str, n_group: int, index: int,
           key, fault_model: str = ""):
    """HBM path through :class:`CIMDeployment`: align -> pack -> (inject) ->
    read. Returns the decoded fp16 weights the macro would serve, plus ECC
    statistics."""
    policy = serving_policy(protect=protect, n_group=n_group, index=index,
                            serve_path="hbm")
    dep = dep_lib.CIMDeployment.deploy(params, policy)
    if ber > 0:
        dep = dep.inject(key, ber, field="full", model=fault_model or None)
    return dep.read()


def deploy_fused(params, *, ber: float, protect: str, n_group: int,
                 index: int, key, inject_mode: str, field: str,
                 fault_model: str = ""):
    """Fused path through :class:`CIMDeployment`: align -> pack; weights STAY
    packed. Static faults are injected into the image; dynamic faults ride in
    via the ``_cim`` runtime (per-read seeds + thresholds consumed by the
    model's read hooks). Returns the serving params pytree; the deployment
    object itself comes from :func:`make_deployment`."""
    dep = make_deployment(params, ber=ber, protect=protect, n_group=n_group,
                          index=index, key=key, inject_mode=inject_mode,
                          field=field, fault_model=fault_model)
    return dep.serving_params(**serving_kw(
        ber=ber, key=key, inject_mode=inject_mode, field=field,
        fault_model=fault_model))


def make_deployment(params, *, ber: float, protect: str, n_group: int,
                    index: int, key, inject_mode: str, field: str,
                    fault_model: str = "") -> dep_lib.CIMDeployment:
    policy = serving_policy(protect=protect, n_group=n_group, index=index,
                            field=field, serve_path="fused")
    dep = dep_lib.CIMDeployment.deploy(params, policy)
    if ber > 0 and inject_mode == "static":
        dep = dep.inject(key, ber, field=field, model=fault_model or None)
    return dep


def serving_kw(*, ber, key, inject_mode, field, fault_model: str = ""):
    """The ``serving_params`` kwargs for this launch — shared with the scrub
    controller so a post-scrub params rebuild serves identically."""
    dynamic = ber > 0 and inject_mode == "dynamic"
    return dict(
        dynamic_key=jax.random.fold_in(key, 99) if dynamic else None,
        ber=ber if dynamic else 0.0, field=field,
        model=(fault_model or None) if dynamic else None)


def _serving_params(dep, *, ber, key, inject_mode, field, fault_model=""):
    return dep.serving_params(**serving_kw(
        ber=ber, key=key, inject_mode=inject_mode, field=field,
        fault_model=fault_model))


def make_serve_mesh(spec: str) -> Mesh:
    """``"DxM"`` -> a ``("data", "model")`` mesh over the first D*M devices."""
    d_ax, m_ax = (int(v) for v in spec.lower().split("x"))
    devs = jax.devices()
    assert d_ax * m_ax <= len(devs), \
        f"mesh {spec} needs {d_ax * m_ax} devices, have {len(devs)}"
    return Mesh(np.asarray(devs[:d_ax * m_ax]).reshape(d_ax, m_ax),
                ("data", "model"))


def place_on_mesh(params, mesh: Mesh):
    """Serving placement: CIM stores column-sharded over "model" (one shard
    per macro column group); every other leaf — block weights, norms, the
    ``_cim`` dynamic runtime — replicated. One rule, shared with
    ``CIMDeployment.shard`` (:func:`repro.core.deployment.place_stores`)."""
    return dep_lib.place_stores(params, mesh, axis="model", dim="j")


def _fused_report(stores):
    n_stores, n_cached, packed_bytes, fp16_bytes, cache_bytes = 0, 0, 0, 0, 0
    corrected = uncorrectable = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            stores, is_leaf=cim_lib._is_store)[0]:
        if cim_lib._is_store(leaf):
            n_stores += 1
            packed_bytes += leaf.stored_bytes
            fp16_bytes += 2 * leaf.shape[0] * leaf.shape[1]
            if leaf.cache is not None:
                n_cached += 1
                cache_bytes += int(leaf.cache.size) * leaf.cache.dtype.itemsize
            st = cim_lib.store_stats(leaf)
            corrected += int(st["corrected"])
            uncorrectable += int(st["uncorrectable"])
    print(f"CIM fused serve: {n_stores} weight matrices stay packed "
          f"({packed_bytes / 1e6:.2f} MB image vs {fp16_bytes / 1e6:.2f} MB "
          f"decoded fp16); {n_cached} hot leaves carry a decoded-row cache "
          f"({cache_bytes / 1e6:.2f} MB, rebuilt per fault image), the rest "
          f"decode on read; corrected={corrected} "
          f"uncorrectable={uncorrectable}")


def _parse_range(spec: str) -> tuple:
    lo, hi = (int(v) for v in spec.split(","))
    assert 1 <= lo <= hi, f"bad length range {spec!r}"
    return lo, hi


def _serve_engine(args, cfg, params, mesh, dep=None, scrub_kw=None,
                  expert_dep=None):
    """Thin frontend onto :class:`repro.launch.engine.Engine`: synthetic
    Poisson load -> scheduler -> per-request ECC/latency artifact.

    ``--scrub`` attaches a :class:`repro.launch.scrub.ScrubController` as the
    engine's step hook (``dep`` + ``scrub_kw`` come from the fused deploy);
    ``--age-ber`` adds a drift-aging wear process under it. ``--probe RID``
    re-serves one request through a fresh solo engine and asserts its tokens
    and ECC stream match the co-batched run bitwise (skipped-with-a-note
    when MoE capacity coupling voids the guarantee at these shapes)."""
    from repro.launch import engine as engine_lib

    load = engine_lib.LoadGen(
        n_requests=args.requests,
        rate=args.rate if args.rate > 0 else float("inf"),
        prompt_lens=_parse_range(args.prompt_range),
        gen_lens=_parse_range(args.gen_range),
        vocab_size=cfg.vocab_size, seed=args.seed)
    max_len = args.max_len or load.max_len()
    eng = engine_lib.Engine(cfg, params, n_slots=args.slots,
                            max_len=max_len, chunk=args.chunk,
                            ecc_accounting=not args.no_ecc_accounting)
    scrubber = None
    if args.scrub:
        from repro.launch import scrub as scrub_lib
        assert dep is not None, \
            "--scrub needs the fused CIM serve path (--cim --serve-path fused)"
        assert not args.no_ecc_accounting, \
            "--scrub thresholds on per-store ECC accounting"
        aging = None
        if args.age_ber > 0:
            aging = scrub_lib.DriftAging(
                key=jax.random.fold_in(jax.random.PRNGKey(args.seed), 7),
                ber=args.age_ber, model=args.fault_model or "drift",
                every=args.age_every)
        scrubber = scrub_lib.ScrubController(
            dep, scrub_lib.ScrubPolicy(threshold=args.scrub_threshold,
                                       interval=args.scrub_interval),
            aging=aging, serving_kw=scrub_kw or {})
    requests = load.requests()
    results, agg = eng.run(requests, open_loop=args.rate > 0,
                           on_step=scrubber)

    incomplete = [r.rid for r in requests if r.rid not in results]
    assert not incomplete, f"engine dropped requests: {incomplete}"
    print(f"engine: {agg['n_requests']} requests over {args.slots} slots "
          f"(chunk {args.chunk}, max_len {max_len}); "
          f"{agg['total_tokens']} tokens in {agg['decode_steps']} decode "
          f"steps, occupancy {agg['slot_occupancy']:.2f}")
    msg = (f"decode: {agg['decode_tok_s']:.1f} tok/s aggregate; "
           f"TTFT mean {agg['ttft_s_mean']*1e3:.0f} ms "
           f"p95 {agg['ttft_s_p95']*1e3:.0f} ms; "
           f"ECC reads={agg['ecc']['reads']} "
           f"corrected={agg['ecc']['corrected']} "
           f"uncorrectable={agg['ecc']['uncorrectable']}")
    if mesh is not None:
        msg += (f" (mesh {mesh.shape['data']}x{mesh.shape['model']} "
                f"data x model, {mesh.size} devices)")
    print(msg)
    if scrubber is not None:
        sc = agg["scrub"]
        print(f"scrub: {sc['events']} events, {sc['rows_reencoded']} rows "
              f"re-encoded, corrected cleared {sc['corrected_cleared']}, "
              f"uncorrectable cleared {sc['uncorrectable_cleared']} "
              f"({sc['wall_s']*1e3:.0f} ms scrub wall)")
    if expert_dep is not None:
        est = expert_dep.stats_by_expert()
        print(f"expert CIM: {len(est)} expert stores, "
              f"corrected={sum(v['corrected'] for v in est.values())} "
              f"uncorrectable="
              f"{sum(v['uncorrectable'] for v in est.values())}")

    probe = None
    if args.probe >= 0:
        assert not args.scrub, \
            "--probe replays against the launch image; --scrub mutates it"
        preq = [r for r in requests if r.rid == args.probe]
        assert preq, f"--probe {args.probe}: no such rid in the load"
        solo_eng = engine_lib.Engine(
            cfg, params, n_slots=args.slots, max_len=max_len,
            chunk=args.chunk, ecc_accounting=not args.no_ecc_accounting)
        pres, _ = solo_eng.run(preq)
        routed, solo = results[args.probe], pres[args.probe]
        ok = (routed.tokens == solo.tokens and routed.ecc == solo.ecc)
        probe = {"rid": args.probe,
                 "tokens_equal": routed.tokens == solo.tokens,
                 "ecc_equal": routed.ecc == solo.ecc, "ok": ok,
                 "capacity_coupled": eng.capacity_coupled}
        print(f"probe rid={args.probe}: solo replay "
              f"{'MATCHES' if ok else 'DIVERGES'} "
              f"(tokens {probe['tokens_equal']}, ecc {probe['ecc_equal']})")
        if eng.capacity_coupled:
            print("probe: MoE capacity coupling active at these shapes — "
                  "bitwise match not guaranteed (moe.drop_free)")
        else:
            assert ok, f"solo-vs-cobatched probe failed: {probe}"

    if args.engine_json:
        import json
        import os
        os.makedirs(os.path.dirname(args.engine_json) or ".", exist_ok=True)
        payload = {
            "config": {"arch": args.arch, "reduced": args.reduced,
                       "slots": args.slots, "chunk": args.chunk,
                       "max_len": max_len, "requests": args.requests,
                       "rate": args.rate, "ber": args.ber,
                       "protect": args.protect, "inject": args.inject,
                       "serve_path": args.serve_path or "fused",
                       "mesh": args.mesh, "seed": args.seed,
                       "fault_model": args.fault_model,
                       "scrub": bool(args.scrub),
                       "age_ber": args.age_ber,
                       "expert_cim": bool(args.expert_cim)},
            "aggregate": agg,
            "probe": probe,
            "expert_ecc": (expert_dep.stats_by_expert()
                           if expert_dep is not None else None),
            "requests": [results[r.rid].to_json() for r in requests],
        }
        with open(args.engine_json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.engine_json}")
    return results, agg


def _serve_fleet(args, cfg, params):
    """Frontend onto :class:`repro.launch.fleet.Fleet`: one deployed image,
    ``--fleet N`` data-parallel engine replicas behind the SLO router.

    ``params`` must be UNSHARDED — the fleet spools it once and places it on
    each replica's own mesh (``--mesh DxM`` is the per-replica shape over
    disjoint device blocks). ``--probe RID`` re-serves one request through a
    fresh single-replica fleet off the same spool and asserts its tokens and
    ECC stream match the routed run bitwise (the live replica-invariance
    probe).

    ``params`` arrive on the host (see :func:`_serve`), and the routed fleet
    is released before the probe builds its own: at published widths one
    copy of a model fills a third of a chip, so the launch copy, replica 0
    and the probe replica cannot all sit on device 0.
    """
    import gc

    from repro.launch import engine as engine_lib
    from repro.launch import fleet as fleet_lib

    load = engine_lib.LoadGen(
        n_requests=args.requests,
        rate=args.rate if args.rate > 0 else float("inf"),
        prompt_lens=_parse_range(args.prompt_range),
        gen_lens=_parse_range(args.gen_range),
        vocab_size=cfg.vocab_size, seed=args.seed,
        prefix_len=args.shared_prefix)
    max_len = args.max_len or load.max_len()
    meshes = fleet_lib.make_fleet_meshes(args.mesh, args.fleet) \
        if args.mesh else None
    fl = fleet_lib.Fleet.from_serving_params(
        cfg, params, n_replicas=args.fleet, meshes=meshes,
        prefix_cache=not args.no_prefix_cache, n_slots=args.slots,
        max_len=max_len, chunk=args.chunk,
        ecc_accounting=not args.no_ecc_accounting)
    requests = load.requests()
    results, agg = fl.run(requests, open_loop=args.rate > 0)

    incomplete = [r.rid for r in requests if r.rid not in results]
    assert not incomplete, f"fleet dropped requests: {incomplete}"
    by_rep = " ".join(f"{k}={v}" for k, v in
                      sorted(agg["requests_by_replica"].items()))
    # the device ids each replica's serving image occupies
    replica_devices = {
        name: sorted({d.id for leaf in jax.tree_util.tree_leaves(
            rep.engine.params) for d in leaf.devices()})
        for name, rep in fl.replicas.items()}
    print(f"fleet: {agg['n_requests']} requests over "
          f"{agg['n_replicas']} replicas x {args.slots} slots "
          f"(chunk {args.chunk}, max_len {max_len}); routed {by_rep}")
    print(f"fleet: replica devices {replica_devices}")
    spool_dir = fl.spool_dir
    del fl
    gc.collect()
    print(f"fleet: {agg['tok_s']:.1f} tok/s wall, "
          f"{agg['tok_s_virtual']:.1f} tok/s virtual "
          f"(busy wall {agg['busy_wall_s']:.2f}s of {agg['wall_s']:.2f}s); "
          f"TTFT mean {agg['ttft_s_mean']*1e3:.0f} ms "
          f"p95 {agg['ttft_s_p95']*1e3:.0f} ms; "
          f"prefix hits {agg['prefix_hits']} "
          f"({agg['prefix_tokens']} tokens reused)")

    probe = None
    if args.probe >= 0:
        preq = [r for r in requests if r.rid == args.probe]
        assert preq, f"--probe {args.probe}: no such rid in the load"
        pf = fleet_lib.Fleet.from_serving_params(
            cfg, params, n_replicas=1,
            meshes=meshes[:1] if meshes else None,
            spool_dir=spool_dir, prefix_cache=not args.no_prefix_cache,
            n_slots=args.slots, max_len=max_len, chunk=args.chunk,
            ecc_accounting=not args.no_ecc_accounting)
        pres, _ = pf.run(preq)
        routed, solo = results[args.probe], pres[args.probe]
        ok = (routed.tokens == solo.tokens and routed.ecc == solo.ecc)
        probe = {"rid": args.probe, "replica_routed": routed.replica,
                 "tokens_equal": routed.tokens == solo.tokens,
                 "ecc_equal": routed.ecc == solo.ecc, "ok": ok}
        print(f"probe rid={args.probe}: routed via {routed.replica!r}, "
              f"solo replay {'MATCHES' if ok else 'DIVERGES'} "
              f"(tokens {probe['tokens_equal']}, ecc {probe['ecc_equal']})")
        assert ok, f"replica-invariance probe failed: {probe}"

    if args.engine_json:
        import json
        import os
        os.makedirs(os.path.dirname(args.engine_json) or ".", exist_ok=True)
        payload = {
            "config": {"arch": args.arch, "reduced": args.reduced,
                       "fleet": args.fleet, "slots": args.slots,
                       "chunk": args.chunk, "max_len": max_len,
                       "requests": args.requests, "rate": args.rate,
                       "ber": args.ber, "protect": args.protect,
                       "inject": args.inject,
                       "serve_path": args.serve_path or "fused",
                       "mesh": args.mesh, "seed": args.seed,
                       "shared_prefix": args.shared_prefix,
                       "prefix_cache": not args.no_prefix_cache},
            "aggregate": agg,
            "probe": probe,
            "replica_devices": replica_devices,
            "requests": [results[r.rid].to_json() for r in requests],
        }
        with open(args.engine_json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.engine_json}")
    return results, agg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cim", action="store_true", help="serve via CIM image")
    ap.add_argument("--ber", type=float, default=0.0)
    ap.add_argument("--protect", default="one4n",
                    choices=["one4n", "per_weight", "none"])
    ap.add_argument("--n-group", type=int, default=8)
    ap.add_argument("--index", type=int, default=2)
    ap.add_argument("--serve-path", default=None, choices=["fused", "hbm"],
                    help="fused: decode-on-read kernels off the packed image; "
                         "hbm: decode once to fp16 copies "
                         "(default: ReliabilityConfig.serve_path)")
    ap.add_argument("--inject", default="static",
                    choices=["static", "dynamic"],
                    help="static: flip the image once; dynamic: fresh "
                         "in-kernel faults on every weight read (fused only)")
    ap.add_argument("--field", default="full",
                    choices=["full", "mantissa", "exponent_sign"])
    ap.add_argument("--expert-cim", action="store_true",
                    help="MoE archs: deploy every expert's matrices as its "
                         "own per-expert CIM store (static faults, decode-"
                         "once restack; per-expert ECC in the artifact)")
    ap.add_argument("--fault-model", default="", metavar="SPEC",
                    help="error process for injection "
                         "(repro.core.faultmodels grammar, e.g. "
                         "'burst:rate=0.3,length=8,axis=col' or "
                         "'drift:drift_rate=0.05'; default: i.i.d.)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data, model) device mesh, e.g. 2x4: "
                         "request batches shard over 'data', CIM stores "
                         "column-shard over 'model'")
    ap.add_argument("--rounds", type=int, default=1,
                    help="number of successive request batches to serve")
    # continuous-batching engine mode (repro.launch.engine)
    ap.add_argument("--engine", action="store_true",
                    help="serve a synthetic open-loop request stream through "
                         "the continuous-batching engine instead of one "
                         "lock-step batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine decode slots (the fixed co-batch width)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="engine prefill chunk length (ragged prompts)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine per-slot KV ceiling (0: fit the load)")
    ap.add_argument("--requests", type=int, default=16,
                    help="engine load: number of requests")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="engine load: Poisson arrival rate in req/s "
                         "(0: closed burst, all arrive at t=0)")
    ap.add_argument("--prompt-range", default="8,32", metavar="LO,HI",
                    help="engine load: uniform prompt-length range")
    ap.add_argument("--gen-range", default="4,16", metavar="LO,HI",
                    help="engine load: uniform generation-length range")
    ap.add_argument("--engine-json", default=None, metavar="PATH",
                    help="write the engine's per-request ECC/latency JSON")
    ap.add_argument("--no-ecc-accounting", action="store_true",
                    help="skip per-read ECC accounting (dynamic accounting "
                         "re-decodes the codeword planes per read — "
                         "disable when measuring throughput)")
    # online ECC scrubbing (repro.launch.scrub, engine mode only)
    ap.add_argument("--scrub", action="store_true",
                    help="engine: background ECC scrubbing — when a store's "
                         "cumulative ECC events cross --scrub-threshold, "
                         "re-encode its image and hot-swap the params "
                         "(fused CIM path only)")
    ap.add_argument("--scrub-threshold", type=int, default=16,
                    help="scrub: per-store cumulative ECC events before a "
                         "re-encode fires")
    ap.add_argument("--scrub-interval", type=int, default=1,
                    help="scrub: check cadence in engine steps")
    ap.add_argument("--age-ber", type=float, default=0.0,
                    help="scrub soak: per-step static wear injection at this "
                         "BER under --fault-model (default drift), keyed per "
                         "engine step — damage accumulates until scrubbed")
    ap.add_argument("--age-every", type=int, default=1,
                    help="scrub soak: apply wear every N engine steps")
    # fleet mode (repro.launch.fleet)
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve the engine load through N data-parallel "
                         "replicas behind the SLO router (one deployed "
                         "image, spooled + restored per replica); with "
                         "--fleet, --mesh DxM is the PER-REPLICA mesh over "
                         "disjoint device blocks")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="fleet: disable per-replica prefix/KV-chunk reuse")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="L",
                    help="fleet load: prepend one shared L-token prefix to "
                         "every prompt (the system-prompt workload the "
                         "prefix cache accelerates)")
    ap.add_argument("--probe", type=int, default=-1, metavar="RID",
                    help="after the run, re-serve request RID solo (engine "
                         "mode: a fresh solo engine; fleet mode: a fresh "
                         "single-replica fleet off the same spool) and "
                         "assert tokens+ECC match the co-batched/routed run "
                         "bitwise")
    args = ap.parse_args(argv)
    assert args.rounds >= 1, "--rounds must be >= 1"
    compile_cache.configure()

    if args.fleet > 0:
        # per-replica meshes are built (and entered) inside the fleet; the
        # image must deploy unsharded so every replica places its own copy
        return _serve(args, None)
    mesh = make_serve_mesh(args.mesh) if args.mesh else None
    if mesh is None:
        return _serve(args, None)
    with shlib.use_mesh(mesh):   # restores the global mesh on any exit
        return _serve(args, mesh)


def _serve(args, mesh):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    assert cfg.modality == "text", "serving demo uses text archs"
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_lm(key, cfg)

    edep = None
    if args.expert_cim:
        # expert-parallel MoE deployment: per-expert stores, static faults,
        # decode-once restack — runs BEFORE the embed/unembed deploy so the
        # fused/hbm paths see the expert weights the macros would serve
        epolicy = expert_serving_policy(
            protect=args.protect, n_group=args.n_group, index=args.index,
            field=args.field)
        edep = dep_lib.ExpertDeployment.deploy(params, epolicy)
        if args.ber > 0:
            edep = edep.inject(jax.random.fold_in(key, 2), args.ber,
                               model=args.fault_model or None)
        params = edep.serving_params(params)
        est = edep.stats_by_expert()
        print(f"expert CIM deploy: {len(est)} per-expert stores "
              f"(protect={args.protect} ber={args.ber:.1e}), "
              f"corrected={sum(v['corrected'] for v in est.values())} "
              f"uncorrectable="
              f"{sum(v['uncorrectable'] for v in est.values())}")

    serve_path = args.serve_path or ReliabilityConfig().serve_path
    stats = None
    dep = scrub_kw = None
    if args.cim or args.ber > 0:
        dkey = jax.random.fold_in(key, 1)
        if serve_path == "fused":
            dep = make_deployment(
                params, ber=args.ber, protect=args.protect,
                n_group=args.n_group, index=args.index, key=dkey,
                inject_mode=args.inject, field=args.field,
                fault_model=args.fault_model)
            if mesh is not None:
                dep = dep.shard(mesh, axis="model", dim="j")
            scrub_kw = serving_kw(ber=args.ber, key=dkey,
                                  inject_mode=args.inject, field=args.field,
                                  fault_model=args.fault_model)
            params = dep.serving_params(**scrub_kw)
            _fused_report(params)
        else:
            params, stats = deploy(params, ber=args.ber, protect=args.protect,
                                   n_group=args.n_group, index=args.index,
                                   key=dkey, fault_model=args.fault_model)
            print(f"CIM deploy (hbm): protect={args.protect} "
                  f"ber={args.ber:.1e} corrected={int(stats['corrected'])} "
                  f"uncorrectable={int(stats['uncorrectable'])}")
            if mesh is not None:
                params = place_on_mesh(params, mesh)
    elif mesh is not None:
        params = place_on_mesh(params, mesh)

    if args.fleet > 0:
        # the replicas hold the device copies; the launch copy waits on host
        params = jax.device_get(params)
        return _serve_fleet(args, cfg, params)

    if args.engine:
        return _serve_engine(args, cfg, params, mesh, dep=dep,
                             scrub_kw=scrub_kw, expert_dep=edep)

    data = MarkovLM(cfg.vocab_size, args.prompt_len, args.batch, seed=args.seed)

    def place_batch(tokens):
        if mesh is None:
            return tokens
        # per-device request shards: each "data" row serves its own slice
        spec = P("data", None) if args.batch % mesh.shape["data"] == 0 else P()
        return jax.device_put(tokens, NamedSharding(mesh, spec))

    prefill = jax.jit(steps_lib.make_prefill_step(cfg))
    serve = jax.jit(steps_lib.make_serve_step(cfg))

    def grow(a):
        # grow attention caches to hold the generated tokens
        if a.ndim >= 4 and a.shape[-3] == args.prompt_len:
            pad = [(0, 0)] * a.ndim
            pad[-3] = (0, args.gen)
            return jnp.pad(a, pad)
        return a

    gen = None
    prefill_s = decode_s = 0.0
    for r in range(args.rounds):
        prompts = place_batch(data.batch(r)["tokens"])
        t0 = time.time()
        logits, caches = prefill(params, {"tokens": prompts})
        caches = jax.tree_util.tree_map(grow, caches)
        jax.block_until_ready(logits)
        prefill_s += time.time() - t0

        toks = jnp.argmax(logits, -1)[:, None]
        out = [toks]
        t1 = time.time()
        for _ in range(args.gen - 1):
            logits, caches = serve(params, caches, toks)
            toks = jnp.argmax(logits, -1)[:, None]
            out.append(toks)
        jax.block_until_ready(toks)
        decode_s += time.time() - t1
        gen = jnp.concatenate(out, axis=1)

    n_tok = args.rounds * args.batch * (args.gen - 1)
    tok_per_s = n_tok / max(decode_s, 1e-9)
    msg = (f"prefill: {args.rounds}x{args.batch}x{args.prompt_len} in "
           f"{prefill_s*1e3:.0f} ms; decode: {tok_per_s:.1f} tok/s")
    if mesh is not None:
        msg += (f" aggregate / {tok_per_s / mesh.size:.1f} tok/s/device "
                f"(mesh {mesh.shape['data']}x{mesh.shape['model']} "
                f"data x model, {mesh.size} devices)")
    print(msg + f"; sample: {gen[0, :16].tolist()}")
    return gen, stats


if __name__ == "__main__":
    main()
