"""jit'd public wrappers for the fused decode-on-read matmul.

``cim_linear_store`` is the serving-path integration point: it consumes a
packed :class:`repro.core.cim.CIMStore` directly (mantissa plane + packed
codeword / exponent / sign words), pads every operand to tile boundaries, and
launches the fused Pallas kernel — decoded fp16 weight matrices never
materialize in HBM. Inputs that the kernel cannot tile (``per_weight``
protection, non-fp16 formats) fall back to the reference path; callers can
assert the kernel route actually ran via ``with_info=True``.

``interpret`` (see :func:`repro.kernels.resolve_interpret`) runs the kernel
body on the CPU off-TPU; on a TPU the kernel always runs compiled by Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import cim as cim_lib
from repro.core import faultmodels as fm_lib
from repro.kernels import resolve_interpret
from repro.kernels.cim_read.kernel import (SCALAR_M_LEN, SCALAR_M_THR,
                                           SCALAR_THR_MAN, SCALAR_THR_META,
                                           cim_read_matmul_one4n,
                                           cim_read_matmul_raw,
                                           decode_rows_for)
from repro.kernels.cim_read.ref import cim_read_ref  # noqa: F401


def _round_up(x: int, m: int) -> int:
    return math.ceil(x / m) * m


def _pad2(a, r, c):
    return jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))


def make_scalars(seeds=None, thr_man=0, thr_meta=0, off_k=0, off_j=0,
                 model=None) -> jnp.ndarray:
    """SMEM scalar vector for the fused kernel (see kernel.SCALAR_*).

    ``seeds`` is a :func:`repro.core.cim.plane_seeds` dict; zero thresholds
    mean static serving (no in-kernel flips are drawn on that field).
    ``off_k``/``off_j`` place a mesh shard's plane block at its global store
    coordinates (:func:`cim_linear_store_sharded` sets them per shard); zero
    offsets are the single-device image. ``model`` (a
    :class:`~repro.core.faultmodels.FaultProcess`) fills the fault-model
    parameter slots — its static kind/axis travel separately (the ``model=``
    argument of the kernel wrappers), so sweeping a rate or run length never
    recompiles.
    """
    z = jnp.uint32(0)
    seeds = seeds or {}
    m_thr, m_len = fm_lib.model_scalars(model)
    return jnp.stack([
        jnp.asarray(thr_man, jnp.uint32),
        jnp.asarray(thr_meta, jnp.uint32),
        jnp.asarray(seeds.get("man", z), jnp.uint32),
        jnp.asarray(seeds.get("meta", z), jnp.uint32),
        jnp.asarray(seeds.get("cw", z), jnp.uint32),
        jnp.asarray(off_k, jnp.uint32),
        jnp.asarray(off_j, jnp.uint32),
        jnp.asarray(m_thr, jnp.uint32),
        jnp.asarray(m_len, jnp.uint32),
    ])


_STATIC = ("block_m", "block_n", "block_k", "dynamic", "hoist", "interpret",
           "model_kind", "model_axis")


@functools.partial(jax.jit, static_argnames=(
    "codec", "n_group", "man_bits", "exp_bits", "bias", "store_g",
    "store_j") + _STATIC)
def _one4n_call(x, man, cw, scalars, **kw):
    return cim_read_matmul_one4n(x, man, cw, scalars, **kw)


@functools.partial(jax.jit, static_argnames=(
    "n_group", "man_bits", "exp_bits", "bias", "store_k",
    "store_j") + _STATIC)
def _raw_call(x, man, exp, signw, scalars, **kw):
    return cim_read_matmul_raw(x, man, exp, signw, scalars, **kw)


# Tiles are chosen so the modelled footprint (`vmem_bytes`) stays under
# VMEM_BUDGET; every call may use up to kernel.VMEM_LIMIT (48 MiB), and the
# difference covers compiler-internal scratch the model does not itemize. At
# the olmo-1b unembed width (K=2048, bn=768) the model gives 25.7 MiB for
# decode (m=8) and 28.3 MiB for prefill (m=128) tiles, where Mosaic for v5e
# allocated 19-20 and 22-23.5 MiB (scoped-VMEM sizes it reports when the
# limit is lowered below them, in compiles for a described v5e as in
# tests/test_chip_compile.py; no chip needed).
VMEM_BUDGET = 32 * 2 ** 20


def vmem_bytes(cfg, bm: int, bn: int, bk: int, k_t: int, hoist: bool) -> int:
    """Modelled VMEM footprint of one fused-read call with these tiles.

    * pipelined windows, each double-buffered: activations ``[bm, bk]`` f32,
      mantissas ``[bk, bn]`` uint16, the exponent/sign planes (one4n: the
      lane-dense ``[bk/n, bn/rw*S*W]`` uint32 codeword window; raw: the
      ``[bk/n, bn]`` uint8 exponent rows padded to 32-row tiles plus the
      ``[bk/32, bn]`` sign words padded to 8-row tiles) and the ``[bm, bn]``
      f32 output block;
    * scratch: the decoded f32 strip (``[K, bn]`` hoisted, else
      ``[bk, bn]``) and the ``[bk/n, bn]`` int32 metadata;
    * working set: one decoded tile's worth for the dot's operand passes,
      plus eight int32 temporaries per ``decode_rows`` chunk.
    """
    n, rw = cfg.n_group, cfg.row_weights
    bkb = bk // n
    if cfg.protect == "one4n":
        codec = cfg.codec
        planes = bkb * (bn // rw) * codec.n_segments * codec.codeword_words * 4
    else:
        planes = _round_up(bkb, 32) * bn + _round_up(bk // 32, 8) * bn * 4
    windows = 2 * (bm * bk * 4 + bk * bn * 2 + planes + bm * bn * 4)
    scratch = (k_t if hoist else bk) * bn * 4 + bkb * bn * 4
    working = bk * bn * 4 + 8 * decode_rows_for(bk) * bn * 4
    return windows + scratch + working


def resolve_tiles(store, m: int, *, block_m=None, block_n=None, block_k=None,
                  hoist=None, vmem_budget: int = VMEM_BUDGET,
                  j_pad: int | None = None):
    """Grid selection for one store shape -> ``(bm, bn, bk, hoist)``.

    ``j_pad`` tiles J as for a store that wide: a shard of a column-sharded
    store passes the whole store's padded J, so its dots take the
    single-device tile shapes and its output columns the same bits.

    ``None`` block sizes are **autotuned** per store shape; explicit values
    reproduce the legacy fixed-tile behaviour (snapped to the layout quanta:
    ``bn`` covers whole row_weights groups and a lane-dense plane window,
    ``bk`` whole exponent blocks and sign words). The autotune policy:

    * ``bk`` prefers **full K** (one decode pass per plane tile — the
      K-revisit refold the decode hoist exists to kill simply never happens —
      and a single-K-step grid keeps the accumulation order of a plain
      ``x @ w`` matmul, which the bit-identity test matrix relies on);
    * ``bn`` covers the whole padded J when small, else near 1024 lanes;
    * ``bm`` covers M up to 128 rows;
    * while :func:`vmem_bytes` exceeds ``vmem_budget``, ``bn`` shrinks by
      lane quanta first and ``bk`` halves only once ``bn`` is at its quantum;
    * ``hoist`` turns on exactly when some output row-block revisits the
      decoded strip (more than one M block) and the hoisted strip fits.
    """
    cfg = store.cfg
    k_pad = store.man.shape[0]
    j_pad = j_pad or store.man.shape[1]
    n, rw = cfg.n_group, cfg.row_weights
    lcm_k = n if cfg.protect == "one4n" else (n * 32 // math.gcd(n, 32))
    bn_lane = math.lcm(rw, 128)
    # one block over all of J is a legal window for every plane (a window
    # dim may equal the array dim); split J blocks must also keep the one4n
    # codeword window [bk/n, bn/rw*S*W] in whole 128-lane rows
    bn_all = bn_lane * math.ceil(j_pad / bn_lane)
    bn0 = bn_lane
    if cfg.protect == "one4n":
        sw = cfg.codec.n_segments * cfg.codec.codeword_words
        bn0 = math.lcm(bn_lane, rw * 128 // math.gcd(sw, 128))
    want = 1024 if block_n is None else block_n
    bn = bn_all if want >= bn_all else bn0 * max(1, want // bn0)
    bm = min(_round_up(block_m if block_m is not None else 128, 8),
             _round_up(max(m, 1), 8))
    if block_k is None:
        bk = _round_up(k_pad, lcm_k)
    else:
        bk = max(lcm_k, (min(block_k, k_pad) // lcm_k) * lcm_k)

    def fits(bn_, bk_, hoist_):
        k_t = _round_up(k_pad, bk_)
        return vmem_bytes(cfg, bm, bn_, bk_, k_t, hoist_) <= vmem_budget

    if block_n is None:
        while bn > bn0 and not fits(bn, bk, False):
            bn = bn0 * ((bn - 1) // bn0)
    if block_k is None:
        while bk > lcm_k and not fits(bn, bk, False):
            bk = max(lcm_k, (bk // 2 // lcm_k) * lcm_k)
    if hoist is None:
        m_t = _round_up(max(m, 1), bm)
        hoist = (m_t // bm) > 1 and fits(bn, bk, True)
    return bm, bn, bk, bool(hoist)


def autotuned_tile_shapes(store, ms=(2, 8, 128, 512)):
    """The deduped ``(bm, bn, bk, hoist)`` combos :func:`resolve_tiles` picks
    for a store across representative batch sizes — the tile matrix the
    parity/stream-identity tests and the ``kernel_bench`` sweep cover."""
    seen, out = set(), []
    for m in ms:
        t = resolve_tiles(store, m)
        if t not in seen:
            seen.add(t)
            out.append(t)
    return out


def cim_linear_store(x, store, *, scalars=None, model=None,
                     block_m: int | None = None,
                     block_n: int | None = None, block_k: int | None = None,
                     hoist: bool | None = None,
                     interpret: bool | None = None,
                     with_info: bool = False, global_dims=None):
    """Fused linear layer on a packed CIM store: ``x [..., K] -> [..., J]``.

    Static serving: ``scalars=None`` (or zero thresholds). Per-read dynamic
    injection: pass ``make_scalars(cim.plane_seeds(key), thr, thr)`` — the
    kernel then draws the exact :func:`repro.core.cim.inject` flip streams
    in-VMEM before decoding, so every read sees fresh faults without a stored
    image update.

    Block sizes default to :func:`resolve_tiles` autotuning (full-K tiles,
    whole-J columns when they fit, decode hoist when M revisits the strip);
    pass explicit ``block_m``/``block_n``/``block_k`` to pin a grid.

    Operands are zero-padded to tile boundaries (padded activations are zero,
    so padding never changes the result); outputs are sliced back. Returns
    the output array, or ``(out, info)`` with ``info['used_kernel']`` (False
    only on the reference route of ``per_weight`` / non-fp16 stores) and
    ``info['interpret']`` when ``with_info=True``.

    ``global_dims=(k_pad_global, j_pad_global)`` tells the kernel the store
    is one shard of a larger image: dynamic elem indices are computed against
    the GLOBAL padded dims (offsets ride in via the scalars vector), so the
    per-shard flip streams equal the single-device image's.

    ``model`` selects the :class:`~repro.core.faultmodels.FaultProcess` of a
    dynamic read: its kind/axis pick the compiled threshold path (static, like
    ``dynamic``), its parameters overwrite the SCALAR_M_* slots (traced), and
    a static drift tick pre-scales the field thresholds — streams bit-
    identical to ``cim.inject(..., model=model)`` at the same seeds.
    """
    interpret = resolve_interpret(interpret)
    cfg = store.cfg
    k_log, j_log = store.shape
    b_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    assert x2.shape[-1] == k_log, (x2.shape, store.shape)
    dynamic = scalars is not None

    m_kind = model.kind if model is not None else "iid"
    m_axis = model.axis if model is not None else "row"
    if dynamic and model is not None:
        m_thr, m_len = fm_lib.model_scalars(model)
        scalars = scalars.at[SCALAR_M_THR].set(m_thr) \
                         .at[SCALAR_M_LEN].set(m_len)
        if m_kind == "drift":
            # element-independent: pre-scale the field thresholds once
            scalars = scalars.at[SCALAR_THR_MAN].set(
                fm_lib.compiled_threshold(model, scalars[SCALAR_THR_MAN]))
            scalars = scalars.at[SCALAR_THR_META].set(
                fm_lib.compiled_threshold(model, scalars[SCALAR_THR_META]))

    supported = cfg.protect in ("one4n", "none") \
        and cfg.fmt.name == "fp16"
    if not supported:
        assert global_dims is None, \
            "sharded (global_dims) calls require the kernel route"
        out = _fallback(x2, store, scalars, model)
        out = out.reshape(*b_shape, j_log)
        return (out, {"used_kernel": False, "route": "fallback"}) \
            if with_info else out

    n, rw = cfg.n_group, cfg.row_weights
    k_pad, j_pad = store.man.shape
    gk_pad, gj_pad = global_dims or (k_pad, j_pad)
    m = x2.shape[0]

    bm, bn, bk, hoist = resolve_tiles(store, m, block_m=block_m,
                                      block_n=block_n, block_k=block_k,
                                      hoist=hoist, j_pad=gj_pad)
    j_t = _round_up(j_pad, bn)
    k_t = _round_up(k_pad, bk)
    m_t = _round_up(m, bm)

    xp = jnp.pad(x2, ((0, m_t - m), (0, k_t - k_log)))
    man = _pad2(store.man, k_t, j_t)
    if scalars is None:
        scalars = make_scalars()
    common = dict(man_bits=cfg.fmt.man_bits, exp_bits=cfg.fmt.exp_bits,
                  bias=cfg.fmt.bias, block_m=bm, block_n=bn, block_k=bk,
                  dynamic=dynamic, hoist=hoist, interpret=interpret,
                  model_kind=m_kind, model_axis=m_axis)
    if cfg.protect == "one4n":
        cw = store.codewords
        b_t, g_t = k_t // n, j_t // rw
        cw = jnp.pad(cw, ((0, b_t - cw.shape[0]), (0, g_t - cw.shape[1]),
                          (0, 0), (0, 0)))
        out = _one4n_call(xp, man, cw, scalars, codec=cfg.codec, n_group=n,
                          store_g=gj_pad // rw, store_j=gj_pad, **common)
    else:
        b_t = k_t // n
        exp = _pad2(store.exp, b_t, j_t)
        sw_t = k_t // 32
        signw = _pad2(store.sign, sw_t, j_t)
        out = _raw_call(xp, man, exp, signw, scalars, n_group=n,
                        store_k=gk_pad, store_j=gj_pad, **common)
    out = out[:m, :j_log].reshape(*b_shape, j_log)
    if with_info:
        return out, {"used_kernel": True, "interpret": interpret,
                     "tiles": (bm, bn, bk), "hoist": hoist}
    return out


def cim_linear_store_sharded(x, store, *, scalars=None, model=None, mesh=None,
                             axis: str = "model", dim: str = "j",
                             block_m: int | None = None,
                             block_n: int | None = None,
                             block_k: int | None = None,
                             hoist: bool | None = None,
                             interpret: bool | None = None,
                             with_info: bool = False):
    """Mesh-sharded fused linear layer: each model-axis shard decodes and
    multiplies only ITS slab of the packed SRAM image (one shard ≈ one macro
    column group), under ``shard_map``.

    * ``dim='j'`` (default): planes column-sharded; every shard computes its
      ``[M, J/n]`` output slice — no collective on the contraction, the
      output stays J-sharded (``P(batch, axis)``).
    * ``dim='k'``: planes word-line-sharded; each shard contracts its K slab
      and the partial products are combined with a ``psum`` over ``axis``.

    Dynamic per-read injection stays bit-identical to the single-device
    image: each shard's kernel gets its global (row, col) offset via the
    SMEM scalars, so the counter-PRNG elem indices are global store
    coordinates. Falls back to the plain (GSPMD) :func:`cim_linear_store`
    when there is no mesh / no model axis, when the store does not split
    evenly, or for stores the kernel cannot tile (``per_weight``, non-fp16) —
    a 1-device mesh degrades to a single-shard ``shard_map``.
    """
    from jax.sharding import PartitionSpec as P
    from repro.kernels.cim_read.kernel import SCALAR_OFF_J, SCALAR_OFF_K

    if mesh is None:
        from repro.distributed import sharding as shlib
        mesh = shlib.get_mesh()
    cfg = store.cfg
    n_sh = int(mesh.shape[axis]) if mesh is not None \
        and axis in mesh.axis_names else 0
    k_log, j_log = store.shape
    k_pad, j_pad = store.man.shape
    supported = n_sh > 0 and cfg.protect in ("one4n", "none") \
        and cfg.fmt.name == "fp16" \
        and cim_lib.can_shard_store(store, n_sh, dim) \
        and (dim == "j" or k_log == k_pad)   # K shards must tile whole slabs
    if not supported:
        out = cim_linear_store(x, store, scalars=scalars, model=model,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, hoist=hoist,
                               interpret=interpret, with_info=with_info)
        if with_info:
            out, info = out
            return out, dict(info, sharded=False)
        return out

    dynamic = scalars is not None
    sc = scalars if dynamic else make_scalars()
    b_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    m = x2.shape[0]
    planes = cim_lib._plane_dict(store)
    pspecs = cim_lib.store_plane_specs(store, axis, dim)
    data_ax = "data" if "data" in mesh.axis_names \
        and m % int(mesh.shape["data"]) == 0 else None
    j_loc, k_loc = j_pad // n_sh, k_pad // n_sh

    def body(x_loc, planes_loc, sc_loc):
        i = jax.lax.axis_index(axis)
        if dim == "j":
            sc_i = sc_loc.at[SCALAR_OFF_J].set(jnp.uint32(i * j_loc))
            shape = (k_log, j_loc)
        else:
            sc_i = sc_loc.at[SCALAR_OFF_K].set(jnp.uint32(i * k_loc))
            shape = (k_loc, j_log)
        loc = cim_lib.CIMStore(
            man=planes_loc["man"], sign=planes_loc.get("sign"),
            exp=planes_loc.get("exp"), codewords=planes_loc.get("cw"),
            shape=shape, cfg=cfg)
        out = cim_linear_store(x_loc, loc, scalars=sc_i if dynamic else None,
                               model=model, block_m=block_m, block_n=block_n,
                               block_k=block_k, hoist=hoist,
                               interpret=interpret,
                               global_dims=(k_pad, j_pad))
        if dim == "k":
            out = jax.lax.psum(out, axis)
        return out

    x_spec = P(data_ax, None) if dim == "j" else P(data_ax, axis)
    out_spec = P(data_ax, axis) if dim == "j" else P(data_ax, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(x_spec, pspecs, P(None)),
                        out_specs=out_spec, check_vma=False)(x2, planes, sc)
    out = out[:, :j_log].reshape(*b_shape, j_log)
    if with_info:
        return out, {"used_kernel": True, "sharded": True}
    return out


def _fallback(x2, store, scalars, model=None):
    """Reference path: packed jnp decode fused by XLA into the matmul (still
    no persistent fp16 copy; used for per_weight / non-fp16 formats). Dynamic
    scalars draw the same flip streams as the fused kernel; the fault model's
    drift tick was already folded into the threshold slots by the caller, so
    it is zeroed here to avoid double time-scaling."""
    if scalars is not None:
        import dataclasses as _dc
        if model is not None and model.kind == "drift" and model.tick:
            model = _dc.replace(model, tick=0)
        seeds = {"man": scalars[2], "meta": scalars[3], "cw": scalars[4]}
        store = cim_lib.inject_with_seeds(store, seeds, scalars[0], scalars[1],
                                          model=model)
    w, _ = cim_lib.read(store)
    return x2 @ w
