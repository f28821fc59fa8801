"""Expert-parallel MoE dispatch via shard_map + lax.all_to_all.

The GSPMD dense dispatch (``repro.models.moe``) leaves the compiler to infer
collectives for the token->expert scatter; §Perf 4.1 measured its residual
cost and refuted the pre-sharded-scatter fix. This module is the explicit
alternative: inside ``shard_map`` every device

  1. routes its LOCAL tokens (the residual stream is already sharded over
     batch x sequence = every mesh device holds a distinct token slice),
  2. packs them into per-(owner, local-expert) capacity slots,
  3. exchanges slots with ``lax.all_to_all`` over the "model" axis
     (= the expert-parallel axis),
  4. runs its local experts' FFN,
  5. all_to_all's results back and combines with the gates.

Collective cost per layer is exactly 2 all-to-alls of
``T_loc·k·cf·D`` bytes — no compiler guesswork. Enabled with
``cfg.moe_dispatch="a2a"`` (requires an active mesh with a "model" axis;
falls back to the dense dispatch on hosts without one, so CPU unit tests and
reduced configs run unchanged).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shlib


def _local_rank(flat_ids, n_buckets):
    """rank of each assignment within its bucket (sort-based, local)."""
    n = flat_ids.shape[0]
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_buckets, dtype=flat_ids.dtype))
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_ids]
    return jnp.zeros((n,), jnp.int32).at[order].set(ranks_sorted)


def _moe_a2a_local(router, w_gate, w_in, w_out, x_loc, cfg, ep: int,
                   mesh_axes=("data", "model")):
    """Body inside shard_map. x_loc [Tl, D]; expert weights are the LOCAL
    slice [E_loc, D, F]; returns (out [Tl, D], aux scalar)."""
    tl, d = x_loc.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // ep
    dt = x_loc.dtype

    logits = (x_loc @ router.astype(dt)).astype(jnp.float32)          # [Tl, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), 1), 0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)
    aux = jax.lax.pmean(aux, mesh_axes)

    # pack assignments into [ep owners, E_loc, C, D] send slots
    c = max(8, int(math.ceil(tl * k * cfg.capacity_factor / e)))
    flat_ids = ids.reshape(tl * k)                                    # global e
    rank = _local_rank(flat_ids, e)
    keep = rank < c
    # destination slot: owner = e // e_loc ; slot = (e % e_loc) * c + rank
    dest = jnp.where(keep, flat_ids * c + rank, e * c)
    src = jnp.repeat(x_loc, k, axis=0)
    send = jnp.zeros((e * c + 1, d), dt).at[dest].add(src)[:e * c]
    send = send.reshape(ep, e_loc * c, d)                             # by owner

    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=False)                            # [ep, elc, d]
    buf = recv.reshape(ep, e_loc, c, d).transpose(1, 0, 2, 3) \
        .reshape(e_loc, ep * c, d)                                    # senders merged

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_in.astype(dt))
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))         # [elc, ep*c, d]

    back = out_buf.reshape(e_loc, ep, c, d).transpose(1, 0, 2, 3) \
        .reshape(ep, e_loc * c, d)
    ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                             tiled=False).reshape(e * c, d)

    gathered = jnp.where(keep[:, None], ret[jnp.minimum(dest, e * c - 1)], 0)
    out = jnp.sum((gathered * gates.reshape(tl * k, 1).astype(dt))
                  .reshape(tl, k, d), axis=1)
    return out, aux


def apply_moe_a2a(params, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B,S,D] -> (out, aux). Requires an active mesh with "model"+"data"."""
    mesh = shlib.get_mesh()
    ep = mesh.shape["model"]
    b, s, d = x.shape

    def body(router, w_gate, w_in, w_out, x_blk):
        # blocks: router full; w_* are the LOCAL [E_loc, D, F] slices
        blk_shape = x_blk.shape
        out, aux = _moe_a2a_local(router, w_gate, w_in, w_out,
                                  x_blk.reshape(-1, d), cfg, ep,
                                  tuple(mesh.axis_names))
        return out.reshape(blk_shape), aux[None]

    batch_axes = shlib.batch_axes()
    x_spec = P(batch_axes, "model", None)         # tokens: batch x seq sharded
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None),                  # router replicated
                  P("model", None, None),         # experts on "model", D full
                  P("model", None, None),
                  P("model", None, None),
                  x_spec),
        out_specs=(x_spec, P("model")),
        check_vma=False,
    )(params["router"], params["moe_wgate"], params["moe_win"],
      params["moe_wout"], x)
    return out, jnp.mean(aux)
