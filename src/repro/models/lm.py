"""Unified decoder LM covering all ten assigned architectures.

A model is a cycle of block kinds (``cfg.block_pattern``) over ``n_layers``:

  * ``attn``  — GQA attention + dense MLP          (dense family, VLM, audio)
  * ``local`` — windowed attention + dense MLP      (recurrentgemma 1/3 layers)
  * ``moe``   — GQA attention + MoE FFN             (qwen3-moe, dbrx)
  * ``rwkv``  — RWKV6 time-mix + channel-mix        (attention-free)
  * ``rec``   — RG-LRU recurrent block + dense MLP  (recurrentgemma 2/3 layers)

Layers are stacked into pattern *groups* and iterated with ``lax.scan``
(+ optional ``jax.checkpoint``), which keeps HLO size and compile time bounded
at 80–94 layers and makes the saved residual stream a single ``[G, B, S, D]``
tensor that the sharding rules distribute over both mesh axes.

Three entry points per model: ``forward`` (training), ``prefill`` (returns
last-token logits + caches) and ``decode`` (one token against caches).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cim as cim_lib
from repro.distributed import sharding as shlib
from repro.distributed.sharding import shard
from repro.models import attention as attn_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.common import apply_norm, embed_init, init_norm


# ---------------------------------------------------------------- init

def init_block(key, cfg: ModelConfig, kind: str):
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.pdtype()
    p = {"norm1": init_norm(k3, cfg.norm_type, cfg.d_model, dt),
         "norm2": init_norm(k3, cfg.norm_type, cfg.d_model, dt)}
    if kind in ("attn", "local"):
        p["attn"] = attn_lib.init_attention(k1, cfg)
        p["mlp"] = mlp_lib.init_mlp(k2, cfg)
    elif kind == "moe":
        p["attn"] = attn_lib.init_attention(k1, cfg)
        p["moe"] = moe_lib.init_moe(k2, cfg)
    elif kind == "rwkv":
        p["tmix"] = rwkv_lib.init_rwkv_tmix(k1, cfg)
        p["cmix"] = mlp_lib.init_mlp(k2, cfg)
    elif kind == "rec":
        p["rec"] = rglru_lib.init_rglru_block(k1, cfg)
        p["mlp"] = mlp_lib.init_mlp(k2, cfg)
    else:
        raise ValueError(kind)
    return p


def _group_kinds(cfg: ModelConfig):
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    tail = tuple(pat[i] for i in range(cfg.n_layers % len(pat)))
    return pat, n_groups, tail


def init_lm(key, cfg: ModelConfig):
    pat, n_groups, tail = _group_kinds(cfg)
    k_embed, k_unembed, k_layers, k_tail, k_norm = jax.random.split(key, 5)
    dt = cfg.pdtype()

    def init_group(k):
        ks = jax.random.split(k, len(pat))
        return {f"blk{i}": init_block(ks[i], cfg, kind)
                for i, kind in enumerate(pat)}

    group_keys = jax.random.split(k_layers, max(n_groups, 1))
    groups = jax.vmap(init_group)(group_keys) if n_groups else None
    tail_keys = jax.random.split(k_tail, max(len(tail), 1))
    tail_params = tuple(init_block(tail_keys[i], cfg, kind)
                        for i, kind in enumerate(tail))

    params = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
        "unembed": embed_init(k_unembed, (cfg.d_model, cfg.vocab_size), dt),
        "final_norm": init_norm(k_norm, cfg.norm_type, cfg.d_model, dt),
        "groups": groups,
        "tail": tail_params,
    }
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------- sequence

def apply_block_seq(p, cfg: ModelConfig, kind: str, x, positions,
                    want_cache: bool = False):
    """-> (x, aux_loss, cache_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if kind == "local" else 0
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        x = x + attn_lib.full_attention(p["attn"], cfg, h, positions, window)
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        if kind == "moe":
            out, aux = moe_lib.apply_moe(p["moe"], cfg, h2)
        else:
            out = mlp_lib.apply_mlp(p["mlp"], cfg, h2)
        x = x + out
        # (attn-kind caches are built by the caller via _prefill_block_cache)
    elif kind == "rwkv":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, state = rwkv_lib.apply_rwkv_tmix(p["tmix"], cfg, h)
        x = x + o
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        h2s = jnp.concatenate([jnp.zeros_like(h2[:, :1]), h2[:, :-1]], axis=1)
        x = x + mlp_lib.apply_mlp(p["cmix"], cfg, h2, h2s)
        if want_cache:
            state["x_cmix"] = h2[:, -1].astype(jnp.float32)
            cache = state
    elif kind == "rec":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, state = rglru_lib.apply_rglru_block(p["rec"], cfg, h)
        x = x + o
        x = x + mlp_lib.apply_mlp(p["mlp"], cfg, apply_norm(cfg.norm_type, p["norm2"], x))
        if want_cache:
            cache = state
    else:
        raise ValueError(kind)
    return x, aux, cache


def _prefill_block_cache(p, cfg: ModelConfig, kind: str, h, positions):
    """Recompute k/v of the (normed) layer input to build the decode cache."""
    b, s, _ = h.shape
    _, k, v = attn_lib._project_qkv(p["attn"], cfg, h, positions)
    if kind == "local":
        w = min(cfg.local_window, s)
        kw, vw = k[:, -w:], v[:, -w:]
        pw = positions[:, -w:]
        cache = attn_lib.init_local_cache(cfg, b, cfg.local_window, k.dtype)
        slots = jnp.mod(pw[0], cfg.local_window)
        cache["k"] = cache["k"].at[:, slots].set(kw)
        cache["v"] = cache["v"].at[:, slots].set(vw)
        cache["pos"] = cache["pos"].at[:, slots].set(pw)
        return cache
    return {"k": k, "v": v}


def _cim_read_state(params, pos, leaf, req_salt=None):
    """(per-plane seeds, thr_man, thr_meta, model) for CIM decode-on-read
    leaves.

    ``params['_cim']`` (optional, serving only) carries the dynamic-injection
    runtime: base counter-PRNG plane seeds plus per-field Bernoulli
    thresholds. Seeds are folded per the deployment key-derivation chain
    (:func:`repro.core.deployment.request_read_seeds`): a per-``leaf`` salt
    (so embed/unembed faults are uncorrelated), an optional per-request salt
    (the serving engine's batch-invariance contract), and the read index
    ``pos`` (so every prefill/decode step draws fresh soft errors) — per-read
    dynamic injection straight off the packed SRAM image. Absent, reads are
    static (the image serves whatever faults `cim.inject` left in it).

    An optional fault ``model`` in the runtime shapes the streams into a
    structured error process: a drift schedule keys its tick on the
    request-local ``pos`` — the thresholds returned here absorb that time
    scaling, so the model handed downstream always carries tick=0."""
    rt = params.get("_cim") if isinstance(params, dict) else None
    if rt is None:
        return None, 0, 0, None
    from repro.core import deployment as dep_lib
    from repro.core import faultmodels as fm_lib
    seeds = dep_lib.request_read_seeds(rt["seeds"], dep_lib.leaf_salt(leaf),
                                       req_salt, pos)
    model = rt.get("model")
    tm = fm_lib.compiled_threshold(model, rt["thr_man"], tick=pos)
    tt = fm_lib.compiled_threshold(model, rt["thr_meta"], tick=pos)
    if model is not None and model.kind == "drift":
        import dataclasses as _dc
        model = _dc.replace(model, tick=0)
    return seeds, tm, tt, model


def _embed_lookup(params, cfg: ModelConfig, tokens, pos=0, req_salt=None):
    """Token embedding gather; a CIMStore leaf is decoded row-by-row on read
    (only the gathered rows' codewords — no materialized fp16 table). The
    route lives in :func:`repro.core.deployment.dispatch_read_rows`."""
    dt = cfg.cdtype()
    emb = params["embed"]
    if isinstance(emb, cim_lib.CIMStore):
        from repro.core import deployment as dep_lib
        seeds, tm, tt, model = _cim_read_state(params, pos, "embed", req_salt)
        rows = dep_lib.dispatch_read_rows(emb, tokens, seeds=seeds,
                                          thr_man=tm, thr_meta=tt,
                                          model=model)
        return rows.astype(dt)
    return shard(emb.astype(dt), "vocab", None)[tokens]


def _unembed_logits(params, x, pos=0, req_salt=None):
    """Final projection; a CIMStore leaf routes through
    :func:`repro.core.deployment.dispatch_linear` — the single dispatch
    point that picks the fused decode-on-read Pallas kernel, its
    shard_map'd mesh twin (one program per macro column group, logits back
    vocab-sharded) or the GSPMD reference from the store's placement and
    dtype. No decoded weight matrix in HBM on any route."""
    w_un = params["unembed"]
    if isinstance(w_un, cim_lib.CIMStore):
        from repro.core import deployment as dep_lib
        from repro.kernels.cim_read import ops as cr_ops
        seeds, tm, tt, model = _cim_read_state(params, pos, "unembed",
                                               req_salt)
        scalars = cr_ops.make_scalars(seeds, tm, tt, model=model) \
            if seeds is not None else None
        return dep_lib.dispatch_linear(x, w_un, scalars=scalars, model=model)
    # FSDP: gather the (small, bf16) weight rather than partial-summing the
    # contraction over its "data"-sharded D axis — the latter all-reduces the
    # full fp32 logits (13 GB/step/device measured; the gather is 0.2 GB).
    w = shard(w_un.astype(x.dtype), None, "vocab")
    return x @ w


def _embed_inputs(params, cfg: ModelConfig, batch: Dict, pos=0):
    dt = cfg.cdtype()
    if cfg.modality == "vision_stub" and "vision_embeds" in batch:
        tok = _embed_lookup(params, cfg, batch["tokens"], pos)
        vis = batch["vision_embeds"].astype(dt)
        x = jnp.concatenate([vis, tok], axis=1)
    elif cfg.modality == "audio_stub" and "embeds" in batch:
        x = batch["embeds"].astype(dt)
    else:
        x = _embed_lookup(params, cfg, batch["tokens"], pos)
    return shard(x, "batch", "seq", None)


def forward(params, cfg: ModelConfig, batch: Dict, remat: bool = True,
            unroll: bool = False):
    """-> (logits [B,S,V], aux_loss, caches_or_None)."""
    pat, n_groups, tail = _group_kinds(cfg)
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def apply_group(gp, x, aux):
        for i, kind in enumerate(pat):
            x, a, _ = apply_block_seq(gp[f"blk{i}"], cfg, kind, x, positions)
            aux = aux + a
            x = shard(x, "batch", "seq", None)
        return x, aux

    group_fn = apply_group
    if remat:
        group_fn = jax.checkpoint(apply_group)

    aux = jnp.zeros((), jnp.float32)
    if n_groups:
        if unroll:
            # Python-loop over groups: every layer's ops/collectives appear
            # explicitly in the HLO (scan bodies are counted once by XLA cost
            # analysis — the dry-run extrapolates exact roofline terms from
            # 1-group and 2-group unrolled lowerings; DESIGN.md §6).
            for gi in range(n_groups):
                gp = jax.tree_util.tree_map(lambda a: a[gi], params["groups"])
                x, aux = group_fn(gp, x, aux)
        else:
            def body(carry, gp):
                x, aux = carry
                x, aux = group_fn(gp, x, aux)
                return (x, aux), None
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["groups"])
    for i, kind in enumerate(tail):
        x, a, _ = apply_block_seq(params["tail"][i], cfg, kind, x, positions)
        aux = aux + a

    # Leave SP before the unembed: tokens unsharded on "model" so dlogits and
    # the hidden agree on the contraction layout — otherwise GSPMD computes
    # the unembed grad by all-gathering full-vocab fp32 dlogits (13 GB/step
    # per device measured at olmo-1b train_4k vs a 0.27 GB bf16 gather here).
    x = shard(x, "batch", None, None)
    x = apply_norm(cfg.norm_type, params["final_norm"], x)
    logits = _unembed_logits(params, x)
    return shard(logits, "batch", None, "vocab"), aux, None


def prefill(params, cfg: ModelConfig, batch: Dict, unroll: bool = False):
    """Inference prefill: runs the sequence, returns last-token logits and the
    decode caches for every layer (scan-stacked for groups)."""
    pat, n_groups, tail = _group_kinds(cfg)
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def apply_group_cached(gp, x):
        caches = {}
        for i, kind in enumerate(pat):
            h_in = apply_norm(cfg.norm_type, gp[f"blk{i}"]["norm1"], x)
            x, _, c = apply_block_seq(gp[f"blk{i}"], cfg, kind, x, positions,
                                      want_cache=(kind in ("rwkv", "rec")))
            if kind in ("attn", "local", "moe"):
                c = _prefill_block_cache(gp[f"blk{i}"], cfg, kind, h_in, positions)
            caches[f"blk{i}"] = c
            x = shard(x, "batch", "seq", None)
        return x, caches

    group_caches = None
    if n_groups:
        if unroll:
            percall = []
            for gi in range(n_groups):
                gp = jax.tree_util.tree_map(lambda a: a[gi], params["groups"])
                x, caches = apply_group_cached(gp, x)
                percall.append(caches)
            group_caches = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *percall)
        else:
            def body(x, gp):
                x, caches = apply_group_cached(gp, x)
                return x, caches
            x, group_caches = jax.lax.scan(body, x, params["groups"])

    tail_caches = []
    for i, kind in enumerate(tail):
        h_in = apply_norm(cfg.norm_type, params["tail"][i]["norm1"], x)
        x, _, c = apply_block_seq(params["tail"][i], cfg, kind, x, positions,
                                  want_cache=(kind in ("rwkv", "rec")))
        if kind in ("attn", "local", "moe"):
            c = _prefill_block_cache(params["tail"][i], cfg, kind, h_in, positions)
        tail_caches.append(c)

    x = apply_norm(cfg.norm_type, params["final_norm"], x[:, -1:])
    logits = _unembed_logits(params, x)[:, 0]
    return logits, {"groups": group_caches, "tail": tuple(tail_caches),
                    "pos": jnp.asarray(s, jnp.int32)}


# ---------------------------------------------------------------- decode

def init_slot_state(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    """Protocol op 1: one block's zero slot state — KV rows for attn/moe, a
    rolling-window ring for local, the recurrent ``(wkv, x_shift)`` /
    rg-lru hidden state for rwkv/rec. Unknown kinds fail with the
    allowed-vocabulary error at :func:`slot_state_spec`."""
    slot_state_spec(kind)
    if kind in ("attn", "moe"):
        return attn_lib.init_kv_cache(cfg, batch, max_len)
    if kind == "local":
        return attn_lib.init_local_cache(cfg, batch,
                                         min(cfg.local_window, max_len))
    if kind == "rwkv":
        return rwkv_lib.init_rwkv_state(cfg, batch)
    return rglru_lib.init_rglru_state(cfg, batch)


def init_slot_states(cfg: ModelConfig, batch: int, max_len: int,
                     prefilled: int = 0):
    """Zero slot states for every layer, sized for ``max_len`` (dry-run
    serve_step input spec; the engine's decode batch)."""
    pat, n_groups, tail = _group_kinds(cfg)

    def stack(kind):
        c = init_slot_state(cfg, kind, batch, max_len)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), c)

    groups = {f"blk{i}": stack(kind) for i, kind in enumerate(pat)} \
        if n_groups else None
    return {"groups": groups,
            "tail": tuple(init_slot_state(cfg, kind, batch, max_len)
                          for kind in tail),
            "pos": jnp.asarray(prefilled, jnp.int32)}


def init_caches(cfg: ModelConfig, batch: int, max_len: int, prefilled: int = 0):
    """Deprecated shim: use :func:`init_slot_states` (bit-identical)."""
    warnings.warn("lm.init_caches is deprecated; use lm.init_slot_states",
                  DeprecationWarning, stacklevel=2)
    return init_slot_states(cfg, batch, max_len, prefilled)


def apply_block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos):
    if kind in ("attn", "local", "moe"):
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        if kind == "local":
            o, cache = attn_lib.decode_local_attention(p["attn"], cfg, h, cache,
                                                       pos, cfg.local_window)
        else:
            o, cache = attn_lib.decode_attention(p["attn"], cfg, h, cache, pos)
        x = x + o
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        if kind == "moe":
            out, _ = moe_lib.apply_moe(p["moe"], cfg, h2)
        else:
            out = mlp_lib.apply_mlp(p["mlp"], cfg, h2)
        x = x + out
    elif kind == "rwkv":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, state = rwkv_lib.decode_rwkv_tmix(p["tmix"], cfg, h, cache)
        x = x + o
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        x = x + mlp_lib.apply_mlp(p["cmix"], cfg, h2,
                                  cache["x_cmix"].astype(h2.dtype)[:, None])
        state["x_cmix"] = h2[:, 0].astype(jnp.float32)
        cache = state
    elif kind == "rec":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, cache = rglru_lib.decode_rglru_block(p["rec"], cfg, h, cache)
        x = x + o
        x = x + mlp_lib.apply_mlp(p["mlp"], cfg, apply_norm(cfg.norm_type, p["norm2"], x))
    else:
        raise ValueError(kind)
    return x, cache


def apply_block_advance(p, cfg: ModelConfig, kind: str, x, cache, pos,
                        length):
    """Protocol op 2 (chunked prefill): advance one block's slot state by a
    prompt chunk x [B,C,D] at scalar offset ``pos``; the first ``length``
    tokens are valid, the ragged tail padding.

    ``'parallel'`` kinds are position-parallel: attn/moe pad rows land at
    positions the causal mask hides until overwritten (`decode_attention`
    handles S=C natively); local scatters valid rows into the ring and
    *drops* pad writes. ``'scan'`` kinds (rwkv/rec) run the sequence
    formulation with the carried state, identity-masking pads out of the
    left fold — compiled once per chunk shape, ``length`` traced. Output
    rows past ``length`` are garbage the caller must ignore.
    """
    if kind in ("attn", "moe"):
        return apply_block_decode(p, cfg, kind, x, cache, pos)
    if kind == "local":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, cache = attn_lib.advance_local_attention(p["attn"], cfg, h, cache,
                                                    pos, cfg.local_window,
                                                    length)
        x = x + o
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        x = x + mlp_lib.apply_mlp(p["mlp"], cfg, h2)
    elif kind == "rwkv":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, state = rwkv_lib.advance_rwkv_tmix(p["tmix"], cfg, h, cache,
                                              length)
        x = x + o
        h2 = apply_norm(cfg.norm_type, p["norm2"], x)
        h2s = jnp.concatenate(
            [cache["x_cmix"].astype(h2.dtype)[:, None], h2[:, :-1]], axis=1)
        x = x + mlp_lib.apply_mlp(p["cmix"], cfg, h2, h2s)
        state["x_cmix"] = jax.lax.dynamic_slice_in_dim(
            h2, length - 1, 1, axis=1)[:, 0].astype(jnp.float32)
        cache = state
    elif kind == "rec":
        h = apply_norm(cfg.norm_type, p["norm1"], x)
        o, cache = rglru_lib.advance_rglru_block(p["rec"], cfg, h, cache,
                                                 length)
        x = x + o
        x = x + mlp_lib.apply_mlp(p["mlp"], cfg,
                                  apply_norm(cfg.norm_type, p["norm2"], x))
    else:
        raise ValueError(kind)
    return x, cache


def _decode_stack(params, cfg: ModelConfig, caches, x, pos,
                  unroll: bool = False, length=None):
    """Shared decode-path block stack: x [B,S,D] appended to the caches at
    offset ``pos`` (scalar, or per-slot [B] vector) -> (final-normed hidden
    [B,S,D], new group caches, new tail caches). With ``length`` (chunked
    prefill) blocks advance via :func:`apply_block_advance` — ragged chunks
    mask their padded tail out of recurrent folds and ring writes."""
    pat, n_groups, tail = _group_kinds(cfg)

    def step(p, kind, x, c):
        if length is None:
            return apply_block_decode(p, cfg, kind, x, c, pos)
        return apply_block_advance(p, cfg, kind, x, c, pos, length)

    new_group_caches = None
    if n_groups:
        def body(x, xs):
            gp, gc = xs
            out_c = {}
            for i, kind in enumerate(pat):
                x, c = step(gp[f"blk{i}"], kind, x, gc[f"blk{i}"])
                out_c[f"blk{i}"] = c
            return x, out_c
        if unroll:
            # measurement mode: do NOT restack the per-group caches — a
            # jnp.stack of sharded cache slices adds reshard copies that the
            # real scan path never performs (it would inflate decode roofline
            # terms ~20x; see EXPERIMENTS.md §Roofline methodology).
            percall = []
            for gi in range(n_groups):
                sel = lambda a: a[gi]
                x, out_c = body(x, (jax.tree_util.tree_map(sel, params["groups"]),
                                    jax.tree_util.tree_map(sel, caches["groups"])))
                percall.append(out_c)
            new_group_caches = tuple(percall)
        else:
            x, new_group_caches = jax.lax.scan(body, x,
                                               (params["groups"], caches["groups"]))

    new_tail = []
    for i, kind in enumerate(tail):
        x, c = step(params["tail"][i], kind, x, caches["tail"][i])
        new_tail.append(c)

    x = apply_norm(cfg.norm_type, params["final_norm"], x)
    return x, new_group_caches, tuple(new_tail)


def decode(params, cfg: ModelConfig, caches, tokens, pos=None,
           unroll: bool = False):
    """One decode step. tokens [B,1] -> (logits [B,V], new caches)."""
    if pos is None:
        pos = caches["pos"]
    dt = cfg.cdtype()
    if isinstance(params["embed"], cim_lib.CIMStore):
        x = _embed_lookup(params, cfg, tokens, pos=pos)
    else:
        x = params["embed"].astype(dt)[tokens]
    x = shard(x, "batch", None, None)
    x, new_group_caches, new_tail = _decode_stack(params, cfg, caches, x, pos,
                                                  unroll=unroll)
    logits = _unembed_logits(params, x, pos=pos)[:, 0]
    return logits, {"groups": new_group_caches, "tail": new_tail,
                    "pos": pos + 1}


# ------------------------------------------------- continuous-batching engine
#
# Slot-state protocol: the engine/model boundary. Every block kind declares a
# SlotStateSpec, and the engine drives four kind-dispatched operations —
# init_slot_state / advance (prefill_chunk + decode_slots) /
# extract_state_chunk / inject_state_chunk — against it. The engine,
# PrefixCache and Fleet consume only this protocol; they never look inside a
# block's state pytree.

ENGINE_KINDS = ("attn", "local", "moe", "rwkv", "rec")

_SPEC_VOCAB = {"kind": ENGINE_KINDS,
               "advance": ("parallel", "scan"),
               "cache_unit": ("rows", "state")}


@dataclasses.dataclass(frozen=True)
class SlotStateSpec:
    """Per-block-kind contract of the serving engine's slot-state protocol.

    * ``advance`` — how a prompt chunk enters the state: ``'parallel'``
      (position-parallel attention over KV rows / ring slots) or ``'scan'``
      (strictly-recurrent left fold, compiled once per chunk shape).
    * ``cache_unit`` — the prefix cache's unit of reuse: ``'rows'`` states
      are position-addressable (a chunk extracts/injects the rows it wrote);
      ``'state'`` kinds cache the *final* state snapshot per trie node,
      which is exact because the state is a pure left fold over the salted
      prefix (see docs/architecture.md §8).
    * ``fold_state`` — the state is a destructive left fold with no position
      gating: the engine zeroes it on admission (``pos == 0``) and freezes
      it for inactive slots, where attention-style states instead rely on
      the causal mask to hide stale rows until overwritten.
    * ``window_bound`` — the state is a rolling window: the engine clamps
      its prefill chunk to the window so valid writes never collide.
    * ``capacity_coupled`` — co-batched tokens *may* couple through
      capacity-based dispatch; :func:`repro.models.moe.drop_free` decides
      whether a given engine shape actually voids the bitwise guarantee.

    Unknown vocabulary fails here, at construction — not deep inside
    ``advance``.
    """
    kind: str
    advance: str = "parallel"
    cache_unit: str = "rows"
    fold_state: bool = False
    window_bound: bool = False
    capacity_coupled: bool = False

    def __post_init__(self):
        for field, allowed in _SPEC_VOCAB.items():
            got = getattr(self, field)
            if got not in allowed:
                raise ValueError(
                    f"SlotStateSpec.{field}: unknown value {got!r}; allowed: "
                    f"{', '.join(repr(a) for a in allowed)}")


SLOT_STATE_SPECS = {
    "attn": SlotStateSpec("attn"),
    "moe": SlotStateSpec("moe", capacity_coupled=True),
    "local": SlotStateSpec("local", cache_unit="state", window_bound=True),
    "rwkv": SlotStateSpec("rwkv", advance="scan", cache_unit="state",
                          fold_state=True),
    "rec": SlotStateSpec("rec", advance="scan", cache_unit="state",
                         fold_state=True),
}


def slot_state_spec(kind: str) -> SlotStateSpec:
    """The :class:`SlotStateSpec` for one block kind (allowed-vocabulary
    error for unknown kinds)."""
    if kind not in SLOT_STATE_SPECS:
        raise ValueError(
            f"slot_state_spec: unknown block kind {kind!r}; allowed: "
            f"{', '.join(repr(k) for k in ENGINE_KINDS)}")
    return SLOT_STATE_SPECS[kind]


def slot_state_specs(cfg: ModelConfig) -> Tuple[SlotStateSpec, ...]:
    """The distinct specs an arch's block pattern uses (validates every
    kind up front — the engine calls this once at construction)."""
    pat, _, tail = _group_kinds(cfg)
    seen, out = set(), []
    for kind in tuple(pat) + tuple(tail):
        if kind not in seen:
            seen.add(kind)
            out.append(slot_state_spec(kind))
    return tuple(out)


def check_engine_kinds(cfg: ModelConfig) -> Tuple[SlotStateSpec, ...]:
    """Validate every block kind of ``cfg`` against the slot-state protocol
    (allowed-vocabulary error on unknown kinds) and return the specs.

    Since the protocol redesign every shipped kind is servable; MoE's
    capacity coupling is no longer a blanket warning here but a tested
    contract boundary the engine checks per shape
    (:func:`engine_capacity_coupled`)."""
    return slot_state_specs(cfg)


def engine_capacity_coupled(cfg: ModelConfig, tokens: int) -> bool:
    """True when serving ``cfg`` at batches up to ``tokens`` tokens can
    couple co-batched requests through capacity-based MoE dispatch — i.e.
    some spec is ``capacity_coupled`` AND the shape is not provably
    drop-free. Drop-free configs keep the bitwise solo-vs-cobatched
    guarantee (see :func:`repro.models.moe.drop_free`)."""
    if not any(s.capacity_coupled for s in slot_state_specs(cfg)):
        return False
    return not moe_lib.drop_free(cfg, tokens)


def _map_block_states(cfg: ModelConfig, sub, fn):
    """Apply ``fn(kind, *block_states)`` to every block of one or more
    structurally-aligned slot-cache views (the protocol's kind-dispatch
    walk)."""
    pat, n_groups, tail = _group_kinds(cfg)
    subs = sub if isinstance(sub, tuple) else (sub,)
    g = None
    if subs[0]["groups"] is not None:
        g = {f"blk{i}": fn(kind, *(s["groups"][f"blk{i}"] for s in subs))
             for i, kind in enumerate(pat)}
    t = tuple(fn(kind, *(s["tail"][i] for s in subs))
              for i, kind in enumerate(tail))
    return {"groups": g, "tail": t}


def slot_caches(caches, slot):
    """One slot's decode caches as a batch-1 view (the batch axis sits at
    axis 1 under the scan-stacked groups, axis 0 in the tail)."""
    g = caches["groups"]
    if g is not None:
        g = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), g)
    t = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
        caches["tail"])
    return {"groups": g, "tail": t}


def merge_slot_caches(caches, slot, sub):
    """Write a batch-1 slot cache view back into the batched caches."""
    g = caches["groups"]
    if g is not None:
        g = jax.tree_util.tree_map(
            lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                a, b.astype(a.dtype), slot, axis=1), g, sub["groups"])
    t = jax.tree_util.tree_map(
        lambda a, b: jax.lax.dynamic_update_slice_in_dim(
            a, b.astype(a.dtype), slot, axis=0), caches["tail"], sub["tail"])
    return {"groups": g, "tail": t, "pos": caches["pos"]}


def extract_state_chunk(cfg: ModelConfig, caches, slot, pos, length: int):
    """Protocol op 3: one slot's per-block state contribution of the chunk
    that just prefilled positions ``[pos, pos + length)``.

    Kind-dispatched on ``SlotStateSpec.cache_unit``: ``'rows'`` blocks
    (attn/moe — KV leaves and their int8 scales carry the position axis at
    ``-3``) return exactly the rows the chunk wrote; ``'state'`` blocks
    (local/rwkv/rec) return the full post-chunk state snapshot — exact as a
    prefix-cache unit because their state at a chunk boundary is a pure
    left fold of the salted prefix (ring writes are position-gated, the
    recurrences fold left-to-right). The returned pytree is what
    :func:`inject_state_chunk` consumes. ``length`` is static (one trace
    per chunk shape); ``slot``/``pos`` are traced.
    """
    check_engine_kinds(cfg)
    sub = slot_caches(caches, slot)

    def ex(kind, c):
        if slot_state_spec(kind).cache_unit == "rows":
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, pos, length,
                                                       axis=a.ndim - 3), c)
        return c
    return _map_block_states(cfg, sub, ex)


def inject_state_chunk(cfg: ModelConfig, caches, slot, pos, chunk):
    """Protocol op 4: prefill-from-cache entry — write a previously
    extracted state chunk into ``slot`` at positions
    ``[pos, pos + chunk_len)`` and return the updated caches.

    ``'rows'`` blocks write the rows back in place; ``'state'`` blocks
    overwrite the whole snapshot (injecting a trie path's chunks in order
    leaves the last — deepest — snapshot standing, which IS the state after
    that prefix). Injecting what another request prefilled for the same
    token prefix (same content-salted fault streams, same image) leaves the
    caches bitwise identical to having run :func:`prefill_chunk` on the
    chunk — the prefix cache skips the compute, not the contract. The
    caller still owns ``caches['pos']``.
    """
    check_engine_kinds(cfg)
    sub = slot_caches(caches, slot)

    def inj(kind, c, ch):
        if slot_state_spec(kind).cache_unit == "rows":
            return jax.tree_util.tree_map(
                lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                    a, b.astype(a.dtype), pos, axis=a.ndim - 3), c, ch)
        return jax.tree_util.tree_map(lambda a, b: b.astype(a.dtype), c, ch)
    upd = _map_block_states(cfg, (sub, chunk), inj)
    return merge_slot_caches(caches, slot, upd)


def extract_kv_chunk(cfg: ModelConfig, caches, slot, pos, length: int):
    """Deprecated shim: use :func:`extract_state_chunk` (bit-identical)."""
    warnings.warn(
        "lm.extract_kv_chunk is deprecated; use lm.extract_state_chunk",
        DeprecationWarning, stacklevel=2)
    return extract_state_chunk(cfg, caches, slot, pos, length)


def inject_kv_chunk(cfg: ModelConfig, caches, slot, pos, chunk):
    """Deprecated shim: use :func:`inject_state_chunk` (bit-identical)."""
    warnings.warn(
        "lm.inject_kv_chunk is deprecated; use lm.inject_state_chunk",
        DeprecationWarning, stacklevel=2)
    return inject_state_chunk(cfg, caches, slot, pos, chunk)


def prefill_chunk(params, cfg: ModelConfig, caches, tokens, slot, pos,
                  length=None, req_salt=None):
    """Chunked prefill of ONE slot into the batched decode caches.

    ``tokens`` [C] is one prompt chunk (the first ``length`` entries valid;
    the ragged tail is padding — attn/moe pad K/V land at positions the
    causal mask hides until a later write overwrites them, local drops pad
    ring writes, and the recurrent kinds identity-mask pads out of their
    left fold; see :func:`apply_block_advance`). ``slot`` indexes the batch
    row, ``pos`` is the slot's current token count, ``req_salt`` keys this
    request's dynamic-injection streams (the chunk reads the CIM image
    once, at read index ``pos``).

    A chunk at ``pos == 0`` starts a fresh request: ``fold_state`` blocks
    (rwkv/rec) have their slot state zeroed first — without position-gated
    writes, the previous occupant's fold would otherwise leak into the new
    request (attention-style states need no reset; stale rows stay masked
    until overwritten).

    Returns (last-valid-token logits [V], updated caches with
    ``caches['pos'][slot] = pos + length``). Both ``slot`` and ``pos`` are
    traced, so one jit covers every slot and offset per chunk shape.
    """
    check_engine_kinds(cfg)
    if length is None:
        length = tokens.shape[0]
    length = jnp.asarray(length, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    dt = cfg.cdtype()
    toks = tokens[None]                                       # [1, C]
    with jax.named_scope("embed"):
        if isinstance(params["embed"], cim_lib.CIMStore):
            x = _embed_lookup(params, cfg, toks, pos=pos, req_salt=req_salt)
        else:
            x = params["embed"].astype(dt)[toks]
    x = shard(x, "batch", None, None)
    sub = slot_caches(caches, slot)
    if any(s.fold_state for s in slot_state_specs(cfg)):
        fresh = pos == 0

        def reset(kind, c):
            if not slot_state_spec(kind).fold_state:
                return c
            return jax.tree_util.tree_map(
                lambda a: jnp.where(fresh, jnp.zeros_like(a), a), c)
        sub = _map_block_states(cfg, sub, reset)
    with jax.named_scope("blocks"):
        x, gc, tc = _decode_stack(params, cfg, sub, x, pos, length=length)
    h = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)  # [1,1,D]
    with jax.named_scope("unembed"):
        logits = _unembed_logits(params, h, pos=pos,
                                 req_salt=req_salt)[:, 0]
    out = merge_slot_caches(caches, slot, {"groups": gc, "tail": tc})
    out["pos"] = caches["pos"].at[slot].set(pos + length)
    return logits[0], out


def decode_slots(params, cfg: ModelConfig, caches, tokens, active,
                 req_salts=None):
    """One continuous-batching decode step across the slot batch.

    ``tokens`` [S,1] (each slot's last token; inactive slots' values are
    irrelevant), per-slot positions ride in ``caches['pos']`` [S], ``active``
    [S] bool. ``req_salts`` [S] uint32 (see
    :func:`repro.core.deployment.request_salt`) key each slot's
    dynamic-injection CIM reads by (request, position) — never by slot index
    or engine step — so a request's logits and fault streams are
    bit-identical served alone or continuously co-batched. Per-request reads
    run one slot at a time against the packed image (each slot IS a distinct
    macro read with its own counter-PRNG streams); static images read
    batched, which is invariant for free (no seeds in the chain).

    Inactive slots flow through the fixed-shape batch but their positions do
    not advance; their stale cache writes stay causally masked (see
    ``attention.decode_attention``), and ``fold_state`` blocks (rwkv/rec —
    no position gating) have their state frozen to the old value so an idle
    slot's garbage tokens never advance a fold.

    Returns (logits [S,V], new caches).
    """
    check_engine_kinds(cfg)
    pos = caches["pos"]                                       # [S]
    s = tokens.shape[0]
    dt = cfg.cdtype()
    dynamic = isinstance(params, dict) and params.get("_cim") is not None
    if dynamic and req_salts is None:
        raise ValueError(
            "decode_slots: params carry a dynamic-injection '_cim' runtime "
            "but no req_salts — per-read seeds would alias across requests; "
            "pass deployment.request_salt(rid) per slot")
    emb = params["embed"]
    with jax.named_scope("embed"):
        if isinstance(emb, cim_lib.CIMStore) and dynamic:
            x = jnp.concatenate(
                [_embed_lookup(params, cfg, tokens[i:i + 1], pos=pos[i],
                               req_salt=req_salts[i]) for i in range(s)],
                axis=0)
        elif isinstance(emb, cim_lib.CIMStore):
            x = _embed_lookup(params, cfg, tokens)
        else:
            x = emb.astype(dt)[tokens]
    x = shard(x, "batch", None, None)
    with jax.named_scope("blocks"):
        x, gc, tc = _decode_stack(params, cfg, caches, x, pos)
    if any(sp.fold_state for sp in slot_state_specs(cfg)):
        act = jnp.asarray(active, bool)

        def keep_active(axis):
            def f(n, o):
                shape = [1] * n.ndim
                shape[axis] = act.shape[0]
                return jnp.where(act.reshape(shape), n, o)
            return f

        pat, _, tail_kinds = _group_kinds(cfg)
        if gc is not None:
            gc = {f"blk{i}": jax.tree_util.tree_map(
                      keep_active(1), gc[f"blk{i}"],
                      caches["groups"][f"blk{i}"])
                  if slot_state_spec(kind).fold_state else gc[f"blk{i}"]
                  for i, kind in enumerate(pat)}
        tc = tuple(jax.tree_util.tree_map(keep_active(0), tc[i],
                                          caches["tail"][i])
                   if slot_state_spec(kind).fold_state else tc[i]
                   for i, kind in enumerate(tail_kinds))
    with jax.named_scope("unembed"):
        if isinstance(params["unembed"], cim_lib.CIMStore) and dynamic:
            logits = jnp.concatenate(
                [_unembed_logits(params, x[i:i + 1], pos=pos[i],
                                 req_salt=req_salts[i]) for i in range(s)],
                axis=0)[:, 0]
        else:
            logits = _unembed_logits(params, x)[:, 0]
    return logits, {"groups": gc, "tail": tc,
                    "pos": pos + active.astype(jnp.int32)}
