"""Bit-accurate emulation of the Unicorn-CIM weight memory (paper Fig. 3/4).

A :class:`CIMStore` holds one weight matrix the way the macro's SRAM does —
as **word-packed bit planes**, not as one byte per stored bit:

* a mantissa plane (``man_bits`` per weight) in native ``uint16`` words — the
  Mantissa Multiplication Array;
* ONE shared exponent per ``N x row_weights`` block — the reduced Exponent
  Summation Array (8x fewer exponent bit cells for N=8, Table III);
* for ``protect='one4n'``: each block row's exponent + sign payload lives
  ONLY inside SECDED codewords (:class:`~repro.core.ecc.One4NRowCodec`),
  packed 32 bits per ``uint32`` word — check bits are SRAM cells next to the
  payload, exactly as in Fig. 4 ①;
* for ``protect='per_weight'``: one SECDED(6) codeword per weight, packed in
  a single ``uint16`` word (11 stored bits);
* for ``protect='none'``: a raw exponent plane plus a K-packed ``uint32``
  sign plane (bit ``k % 32`` of word ``k // 32``).

``inject`` flips stored bits (including check bits — they are SRAM cells too)
at a given BER. Flip decisions come from the same counter-based PRNG as the
:mod:`repro.kernels.fault_inject` Pallas kernel: bit ``p`` of the word at
C-order flat index ``e`` flips iff ``murmur3(e*32 + p ^ seed*0x9E3779B9) <
round(ber * 2^32)`` — one draw **per stored bit**, never one tensor op per
bit. ``read`` runs the packed ECC decode path (Fig. 4 ②③) and reconstructs
FP16 weights; :func:`read_reference` is the per-bit oracle the packed path is
equivalence-tested against. Static injection = inject once then read many;
dynamic injection = fresh inject before every read (the fused
``kernels/cim_read`` path draws the identical streams in-kernel).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import align as align_lib
from repro.core import bitops, bitpack
from repro.core import faultmodels as fm
from repro.core.bitops import FP16, FloatFormat
from repro.core.ecc import One4NRowCodec, SecdedCode


@dataclasses.dataclass(frozen=True)
class CIMConfig:
    n_group: int = 8            # N
    index: int = 2              # exponent rank used at alignment time
    protect: str = "one4n"      # 'one4n' | 'per_weight' | 'none'
                                # per_weight = Table III "traditional ECC for
                                # exponent & sign": SECDED(6) per weight,
                                # 5 redundant bits each (83.3% SRAM overhead)
    fmt: FloatFormat = FP16
    row_weights: int = 16       # weights per SRAM row (256-bit rows of FP16)

    @property
    def codec(self) -> One4NRowCodec:
        return One4NRowCodec(n_group=self.n_group, row_weights=self.row_weights,
                             exp_bits=self.fmt.exp_bits,
                             sign_bits_per_row=self.row_weights)

    @property
    def pw_code(self) -> SecdedCode:
        """The per-weight (Table III traditional) SECDED over sign+exponent."""
        return SecdedCode(self.fmt.exp_bits + 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CIMStore:
    """Word-packed SRAM image of one [K, J] weight matrix.

    Exactly one of {``codewords``, (``sign``, ``exp``)} is populated: when the
    exponent/sign payload is ECC-protected it lives *only* inside the
    codeword words (so the overhead accounting counts each sign bit once).
    """

    man: jnp.ndarray                      # uint16 [K_pad, J_pad], mantissas
    sign: Optional[jnp.ndarray]           # uint32 [ceil(K_pad/32), J_pad] or None
    exp: Optional[jnp.ndarray]            # uint8  [B, J_pad] or None
    codewords: Optional[jnp.ndarray]      # one4n: uint32 [B, G, n_seg, W];
                                          # per_weight: uint16 [K_pad, J_pad]
    shape: Tuple[int, int]                # logical (K, J)
    cfg: CIMConfig
    cache: Optional[jnp.ndarray] = None   # fp32 [K, J] decoded-row cache
                                          # (== read(store)[0]); serving-only
                                          # materialization, NOT part of the
                                          # SRAM image or its bit accounting.

    def tree_flatten(self):
        children = (self.man, self.sign, self.exp, self.codewords, self.cache)
        return children, (self.shape, self.cfg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        man, sign, exp, codewords, cache = children
        shape, cfg = aux
        return cls(man, sign, exp, codewords, shape, cfg, cache)

    @property
    def stored_bits(self) -> int:
        """Total SRAM bits of this image (for the overhead accounting).

        Counts *logical* stored cells, not container bytes: codeword planes
        count ``code.n`` bits per codeword, and — because protected images
        keep no separate sign/exponent planes — each sign bit is counted
        exactly once (inside its codeword).
        """
        n = int(self.man.size) * self.cfg.fmt.man_bits
        if self.codewords is not None:
            if self.cfg.protect == "per_weight":
                n += int(self.codewords.size) * self.cfg.pw_code.n
            else:
                n_cw = int(np.prod(self.codewords.shape[:-1]))
                n += n_cw * self.cfg.codec.code.n
        else:
            n += int(self.exp.size) * self.cfg.fmt.exp_bits
            n += int(self.man.size)                      # one sign bit/weight
        return n

    @property
    def stored_bytes(self) -> int:
        """Actual container bytes of every plane (what HBM/SRAM emulation
        holds) — the quantity the packed refactor shrinks."""
        planes = [self.man, self.sign, self.exp, self.codewords]
        return sum(int(p.size) * p.dtype.itemsize
                   for p in planes if p is not None)


def _pad_to(x: jnp.ndarray, k: int, j: int) -> jnp.ndarray:
    return jnp.pad(x, ((0, k - x.shape[0]), (0, j - x.shape[1])))


def pack_sign_plane(sign_bits: jnp.ndarray) -> jnp.ndarray:
    """Sign bit plane [K, J] {0,1} -> K-packed uint32 [ceil(K/32), J]."""
    k, j = sign_bits.shape
    sw = bitpack.n_words(k)
    padded = jnp.pad(sign_bits.astype(jnp.uint32), ((0, sw * 32 - k), (0, 0)))
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return jnp.sum(padded.reshape(sw, 32, j) << shifts, axis=1).astype(jnp.uint32)


def unpack_sign_plane(sign_words: jnp.ndarray, k: int) -> jnp.ndarray:
    """K-packed uint32 [SW, J] -> sign bit plane [k, J] uint8."""
    sw, j = sign_words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = ((sign_words[:, None, :] >> shifts) & 1).astype(jnp.uint8)
    return bits.reshape(sw * 32, j)[:k]


def pack(w: jnp.ndarray, cfg: CIMConfig) -> CIMStore:
    """Pack an exponent-aligned [K, J] weight matrix into its SRAM image.

    Weights must already be aligned (``align_matrix``): every N-block along K
    shares a biased exponent. The shared exponent is taken as the block max —
    exact for aligned input.
    """
    assert w.ndim == 2, "pack() operates on 2-D [in, out] matrices"
    k, j = w.shape
    n, rw = cfg.n_group, cfg.row_weights
    k_pad = math.ceil(k / n) * n
    j_pad = math.ceil(j / rw) * rw
    b = k_pad // n
    g = j_pad // rw

    s, e, m = bitops.split_fields(w.astype(jnp.float32), cfg.fmt)
    s = _pad_to(s.astype(jnp.uint8), k_pad, j_pad)
    e = _pad_to(e.astype(jnp.uint8), k_pad, j_pad)
    m = _pad_to(m.astype(jnp.uint16), k_pad, j_pad)

    e_block = jnp.max(e.reshape(b, n, j_pad), axis=1)          # [B, J_pad]
    sign = exp = codewords = None
    if cfg.protect == "one4n":
        codec = cfg.codec
        exp_rows = e_block.reshape(b, g, rw)                    # [B, G, rw]
        signs = s.reshape(b, n, g, rw).transpose(0, 2, 1, 3)    # [B, G, N, rw]
        codewords = codec.encode_packed(exp_rows, codec.pack_signs(signs))
    elif cfg.protect == "per_weight":
        # traditional scheme: one SECDED word per weight over its (exp, sign)
        # bits (per-weight exponents — no alignment assumed); the 11 stored
        # bits fit one uint16 word per weight.
        eb = cfg.fmt.exp_bits
        data = (e.astype(jnp.uint32) | (s.astype(jnp.uint32) << eb))[..., None]
        cw = cfg.pw_code.encode_packed(data)                    # [K, J, 1]
        assert cfg.pw_code.n <= 16
        codewords = cw[..., 0].astype(jnp.uint16)
    else:
        sign = pack_sign_plane(s)
        exp = e_block
    return CIMStore(man=m, sign=sign, exp=exp, codewords=codewords,
                    shape=(k, j), cfg=cfg)


# ---------------------------------------------------------------------------
# Counter-PRNG fault injection on packed words.
#
# The contract (shared with kernels/fault_inject and kernels/cim_read): a
# plane is a word array; bit p of the word at C-order flat index e flips iff
#     murmur3_fmix(e*32 + p  XOR  seed * 0x9E3779B9) < round(ber * 2^32),
# independently per (seed, e, p). Per-plane seeds derive from the caller's
# PRNG key via `plane_seeds`, so static injection (here) and per-read dynamic
# injection (in-kernel) draw bit-identical fault patterns from the same key.
# ---------------------------------------------------------------------------


def plane_seeds(key) -> dict:
    """Per-plane uint32 counter-PRNG seeds from one PRNG key.

    'man' seeds the mantissa plane; 'meta' the raw exponent plane; 'cw' the
    codeword plane (protected) or the raw sign plane (unprotected).
    """
    k_man, k_meta, k_cw = jax.random.split(key, 3)
    return {"man": jax.random.bits(k_man, (), jnp.uint32),
            "meta": jax.random.bits(k_meta, (), jnp.uint32),
            "cw": jax.random.bits(k_cw, (), jnp.uint32)}


def fold_seed(seed, i):
    """Decorrelate a plane seed per read index (dynamic injection streams)."""
    from repro.kernels.fault_inject.kernel import hash_u32
    salt = jnp.asarray(i, jnp.uint32) * jnp.uint32(0x85EBCA6B) \
        + jnp.uint32(0x9E3779B9)
    return hash_u32(jnp.asarray(seed, jnp.uint32) ^ salt)


def counter_flip_words(words: jnp.ndarray, seed, threshold, valid,
                       model=None) -> jnp.ndarray:
    """Flip bits of a packed word plane per the counter-PRNG contract.

    ``valid`` is a uint32 mask (scalar or array broadcastable to
    ``words.shape``) of the bit lanes that are real stored cells; only those
    see Bernoulli draws. ``model`` (a :class:`~repro.core.faultmodels
    .FaultProcess`) compiles to per-element thresholds before the draw;
    ``None``/``iid`` leave the threshold — and the streams — untouched. Pure
    jnp — usable under jit/vmap (the Pallas kernels implement the identical
    streams for the batched/fused paths).
    """
    elem = jnp.arange(words.size, dtype=jnp.uint32).reshape(words.shape)
    threshold = fm.plane_thresholds(model, threshold, elem, seed, words.shape)
    return _flip_gathered(words, elem, seed, threshold, valid)


def codeword_valid_masks(cfg: CIMConfig) -> np.ndarray:
    """Per-word stored-bit masks of the active codeword plane."""
    if cfg.protect == "per_weight":
        return np.asarray(bitpack.word_masks(cfg.pw_code.n)[0], np.uint32)
    return cfg.codec.code.code_word_masks


def inject_with_seeds(store: CIMStore, seeds: dict, thr_man, thr_meta,
                      model=None) -> CIMStore:
    """Flip stored bits from explicit per-plane seeds + field thresholds.

    ``thr_man`` gates the mantissa plane, ``thr_meta`` the exponent/sign
    cells (codeword words when protected — payload and check bits alike are
    SRAM cells). A zero threshold leaves that field untouched. ``model``
    compiles an error process (:mod:`repro.core.faultmodels`) into the
    per-element thresholds of every plane. This is the single source of
    truth for the flip streams: :func:`inject`, the sweep engine's kernel
    route and the fused ``cim_read`` kernel's in-VMEM dynamic injection all
    draw the same (seed, element, bit) decisions.
    """
    man, sign, exp, cw = store.man, store.sign, store.exp, store.codewords
    cfg = store.cfg
    mb = cfg.fmt.man_bits

    man = counter_flip_words(man, seeds["man"], thr_man, (1 << mb) - 1,
                             model=model)
    if cw is not None:
        cw = counter_flip_words(cw, seeds["cw"], thr_meta,
                                codeword_valid_masks(cfg), model=model)
    else:
        eb = cfg.fmt.exp_bits
        exp = counter_flip_words(exp, seeds["meta"], thr_meta, (1 << eb) - 1,
                                 model=model)
        k_pad = store.man.shape[0]
        sign = counter_flip_words(
            sign, seeds["cw"], thr_meta,
            bitpack.word_masks(k_pad, sign.shape[0])[:, None], model=model)
    return CIMStore(man=man, sign=sign, exp=exp, codewords=cw,
                    shape=store.shape, cfg=store.cfg)


def inject(key, store: CIMStore, ber, field: str = "full",
           model=None) -> CIMStore:
    """Flip stored bits at rate ``ber``; ``field`` restricts the target cells.

    field ∈ {'full', 'mantissa', 'exponent_sign'}: the macro stores mantissas,
    and (exponent+sign [+check]) rows — the paper's protected path. ``model``
    selects a :class:`~repro.core.faultmodels.FaultProcess` (default/``iid``
    is bit-for-bit the legacy stream).
    """
    if isinstance(ber, (int, float)) and ber <= 0.0:
        return store
    from repro.kernels.fault_inject.ops import ber_to_threshold
    thr = ber_to_threshold(ber)
    zero = jnp.uint32(0)
    return inject_with_seeds(
        store, plane_seeds(key),
        thr if field in ("full", "mantissa") else zero,
        thr if field in ("full", "exponent_sign") else zero, model=model)


# ---------------------------------------------------------------------------
# Read path: packed ECC decode + FP reconstruction.
# ---------------------------------------------------------------------------


def _decode_planes(store: CIMStore):
    """-> (e_block [B, J_pad], sign bit plane [K_pad, J_pad], status or None).

    For ``per_weight`` the exponent is per-weight; callers get
    ``e_block=None`` and a full ``e_full`` instead (second return slot)."""
    cfg = store.cfg
    n, rw = cfg.n_group, cfg.row_weights
    k_pad, j_pad = store.man.shape
    b, g = k_pad // n, j_pad // rw

    if store.codewords is not None and cfg.protect == "per_weight":
        cw32 = store.codewords.astype(jnp.uint32)[..., None]
        data, status = cfg.pw_code.decode_packed(cw32)
        data = data[..., 0]
        eb = cfg.fmt.exp_bits
        e_full = (data & ((1 << eb) - 1)).astype(jnp.uint8)
        sign = ((data >> eb) & 1).astype(jnp.uint8)
        return None, (e_full, sign), status
    if store.codewords is not None:
        codec = cfg.codec
        exp_rows, sign_words, status = codec.decode_packed(store.codewords)
        e_block = exp_rows.reshape(b, j_pad)
        # expand the packed sign words straight into [K_pad, J_pad] row order
        # (static window shifts; avoids a 4-D uint8 transpose on the hot path)
        sw_list = [sign_words[..., v] for v in range(sign_words.shape[-1])]
        shifts = jnp.arange(rw, dtype=jnp.uint32)
        rows = []
        for i_n in range(n):
            sv = bitpack.extract_window(sw_list, i_n * rw, rw)[0]   # [B, G]
            rows.append(((sv[..., None] >> shifts) & 1).reshape(b, j_pad))
        sign = jnp.stack(rows, axis=1).reshape(k_pad, j_pad).astype(jnp.uint8)
        return e_block, (None, sign), status
    sign = unpack_sign_plane(store.sign, k_pad)
    return store.exp, (None, sign), None


def read(store: CIMStore):
    """Packed ECC decode (if protected) + FP reconstruction.

    Returns (weights float32 [K, J], stats) with
    stats = {'corrected': #rows fixed, 'uncorrectable': #rows with >=2 errors}.
    """
    cfg = store.cfg
    n = cfg.n_group
    e_block, (e_full, sign), status = _decode_planes(store)
    if e_block is not None:
        e_full = jnp.repeat(e_block, n, axis=0)                 # [K_pad, J_pad]
    if status is None:
        stats = {"corrected": jnp.zeros((), jnp.int32),
                 "uncorrectable": jnp.zeros((), jnp.int32)}
    else:
        stats = {"corrected": jnp.sum(status == 1),
                 "uncorrectable": jnp.sum(status == 2)}
    w = bitops.combine_fields(sign.astype(jnp.uint32), e_full.astype(jnp.uint32),
                              store.man.astype(jnp.uint32), cfg.fmt)
    k, j = store.shape
    return jnp.asarray(w[:k, :j], jnp.float32), stats


def build_row_cache(store: CIMStore) -> CIMStore:
    """Attach the decoded-row cache: ``store.cache = read(store)[0]``.

    The cache is a serving-time materialization of the decoded fp32 matrix;
    the packed planes stay authoritative (``stored_bits``/``stored_bytes``,
    ECC stats and flip streams all keep reading the SRAM image). Every
    store-constructing function (:func:`pack`, :func:`inject_with_seeds`,
    :func:`inject_sharded`, sharding plumbing) builds stores *without* a
    cache, so any injection naturally invalidates it — a stale cache cannot
    survive a fault-image refresh.
    """
    return dataclasses.replace(store, cache=read(store)[0])


def drop_row_cache(store: CIMStore) -> CIMStore:
    """Return ``store`` without its decoded-row cache (no-op when absent)."""
    if store.cache is None:
        return store
    return dataclasses.replace(store, cache=None)


def read_reference(store: CIMStore):
    """Per-bit oracle for :func:`read`: unpack the packed planes to one-byte-
    per-bit arrays and decode with the per-bit SECDED codec.

    Kept as the equivalence baseline (tests) and the legacy-representation
    arm of ``benchmarks/cim_store_bench.py``; never used on the hot path.
    """
    cfg = store.cfg
    n, rw = cfg.n_group, cfg.row_weights
    k_pad, j_pad = store.man.shape
    b, g = k_pad // n, j_pad // rw

    if store.codewords is not None and cfg.protect == "per_weight":
        code = cfg.pw_code
        cw_bits = bitpack.unpack_words(
            store.codewords.astype(jnp.uint32)[..., None], code.n)
        data, status = code.decode(cw_bits)
        eb = cfg.fmt.exp_bits
        e_full = bitops.pack_bits(data[..., :eb], jnp.uint8)
        sign = data[..., eb]
        stats = {"corrected": jnp.sum(status == 1),
                 "uncorrectable": jnp.sum(status == 2)}
    elif store.codewords is not None:
        codec = cfg.codec
        cw_bits = bitpack.unpack_words(store.codewords, codec.code.n)
        exp_rows, signs, status = codec.decode(cw_bits)
        e_block = exp_rows.reshape(b, j_pad)
        sign = signs.transpose(0, 2, 1, 3).reshape(k_pad, j_pad)
        e_full = jnp.repeat(e_block, n, axis=0)
        stats = {"corrected": jnp.sum(status == 1),
                 "uncorrectable": jnp.sum(status == 2)}
    else:
        e_full = jnp.repeat(store.exp, n, axis=0)
        sign = unpack_sign_plane(store.sign, k_pad)
        stats = {"corrected": jnp.zeros((), jnp.int32),
                 "uncorrectable": jnp.zeros((), jnp.int32)}
    w = bitops.combine_fields(sign.astype(jnp.uint32), e_full.astype(jnp.uint32),
                              store.man.astype(jnp.uint32), cfg.fmt)
    k, j = store.shape
    return jnp.asarray(w[:k, :j], jnp.float32), stats


def store_stats(store: CIMStore):
    """ECC status counts without reconstructing weights (serve reporting)."""
    if store.codewords is None:
        z = jnp.zeros((), jnp.int32)
        return {"corrected": z, "uncorrectable": z}
    if store.cfg.protect == "per_weight":
        _, status = store.cfg.pw_code.decode_packed(
            store.codewords.astype(jnp.uint32)[..., None])
    else:
        _, _, status = store.cfg.codec.decode_packed(store.codewords)
    return {"corrected": jnp.sum(status == 1),
            "uncorrectable": jnp.sum(status == 2)}


def read_rows(store: CIMStore, idx: jnp.ndarray, seeds=None, thr_man=0,
              thr_meta=0, model=None):
    """Decode-on-read row gather: FP32 rows ``[*idx.shape, J]`` of the stored
    matrix, decoding ONLY the gathered rows' codewords (embedding-table serving
    path — the full weight matrix is never materialized).

    With ``seeds`` set (see :func:`plane_seeds`), fresh faults hit the
    gathered cells first — bit-identical to :func:`inject_with_seeds` on the
    whole store restricted to those cells (same counter-PRNG streams;
    ``thr_man`` gates mantissa cells, ``thr_meta`` exponent/sign cells, and
    ``model`` compiles a :class:`~repro.core.faultmodels.FaultProcess` into
    per-element thresholds at the gathered cells' GLOBAL indices).
    """
    cfg = store.cfg
    n, rw = cfg.n_group, cfg.row_weights
    k_pad, j_pad = store.man.shape
    g = j_pad // rw
    mb = cfg.fmt.man_bits
    dyn = seeds is not None

    def mthr(thr, elem_, seed_, shape_):
        return fm.plane_thresholds(model, thr, elem_, seed_, shape_)

    man = store.man[idx]                                   # [..., J_pad]
    if dyn:
        elem = (idx[..., None].astype(jnp.uint32) * jnp.uint32(j_pad)
                + jnp.arange(j_pad, dtype=jnp.uint32))
        man = _flip_gathered(man, elem, seeds["man"],
                             mthr(thr_man, elem, seeds["man"],
                                  store.man.shape), (1 << mb) - 1)

    if store.codewords is not None and cfg.protect == "per_weight":
        cw = store.codewords[idx]                          # [..., J_pad]
        if dyn:
            cw = _flip_gathered(cw, elem, seeds["cw"],
                                mthr(thr_meta, elem, seeds["cw"],
                                     store.codewords.shape),
                                int(codeword_valid_masks(cfg)))
        data, _ = cfg.pw_code.decode_packed(cw.astype(jnp.uint32)[..., None])
        data = data[..., 0]
        eb = cfg.fmt.exp_bits
        e_rows = (data & ((1 << eb) - 1)).astype(jnp.uint32)
        s_rows = ((data >> eb) & 1).astype(jnp.uint32)
    elif store.codewords is not None:
        codec = cfg.codec
        blk = (idx // n).astype(jnp.int32)
        i_n = (idx % n).astype(jnp.uint32)
        cw = store.codewords[blk]                          # [..., G, S, W]
        if dyn:
            s_, w_ = codec.n_segments, codec.codeword_words
            inner = jnp.arange(g * s_ * w_, dtype=jnp.uint32).reshape(g, s_, w_)
            celem = blk[..., None, None, None].astype(jnp.uint32) \
                * jnp.uint32(g * s_ * w_) + inner
            cw = _flip_gathered(cw, celem, seeds["cw"],
                                mthr(thr_meta, celem, seeds["cw"],
                                     store.codewords.shape),
                                codeword_valid_masks(cfg)[None, None, :])
        exp_rows, sign_words, _ = codec.decode_packed(cw)  # [..., G, rw], [..., G, Sw]
        e_rows = exp_rows.reshape(exp_rows.shape[:-2] + (j_pad,)).astype(jnp.uint32)
        signs = codec.unpack_signs(sign_words)             # [..., G, N, rw]
        s_sel = jnp.take_along_axis(
            signs, i_n[..., None, None, None].astype(jnp.int32), axis=-2)
        s_rows = s_sel[..., 0, :].reshape(s_sel.shape[:-3] + (j_pad,))
        s_rows = s_rows.astype(jnp.uint32)
    else:
        blk = (idx // n).astype(jnp.int32)
        e_rows = store.exp[blk].astype(jnp.uint32)
        sw = store.sign[(idx // 32).astype(jnp.int32)]     # [..., J_pad] words
        if dyn:
            eelem = (blk[..., None].astype(jnp.uint32) * jnp.uint32(j_pad)
                     + jnp.arange(j_pad, dtype=jnp.uint32))
            e_rows = _flip_gathered(e_rows, eelem, seeds["meta"],
                                    mthr(thr_meta, eelem, seeds["meta"],
                                         store.exp.shape),
                                    (1 << cfg.fmt.exp_bits) - 1)
            selem = ((idx // 32)[..., None].astype(jnp.uint32)
                     * jnp.uint32(j_pad) + jnp.arange(j_pad, dtype=jnp.uint32))
            svalid = np.uint32(0xFFFFFFFF) if k_pad % 32 == 0 \
                else np.uint32((1 << (k_pad % 32)) - 1)
            # rows in a full word see all 32 lanes; the last partial word only
            # its valid lanes (same masks as `inject`)
            full = (idx // 32 + 1) * 32 <= k_pad
            vmask = jnp.where(full[..., None], jnp.uint32(0xFFFFFFFF),
                              jnp.uint32(svalid))
            sw = _flip_gathered(sw, selem, seeds["cw"],
                                mthr(thr_meta, selem, seeds["cw"],
                                     store.sign.shape), vmask)
        s_rows = (sw >> (idx % 32)[..., None].astype(jnp.uint32)) & 1
    w = bitops.combine_fields(s_rows, e_rows, man.astype(jnp.uint32), cfg.fmt)
    return jnp.asarray(w[..., :store.shape[1]], jnp.float32)


def _plane_dict(store: CIMStore) -> dict:
    """The store's populated planes by name (sharding / shard_map plumbing)."""
    planes = {"man": store.man, "sign": store.sign, "exp": store.exp,
              "cw": store.codewords}
    return {k: v for k, v in planes.items() if v is not None}


def _restore_planes(store: CIMStore, planes: dict) -> CIMStore:
    return CIMStore(man=planes["man"], sign=planes.get("sign"),
                    exp=planes.get("exp"), codewords=planes.get("cw"),
                    shape=store.shape, cfg=store.cfg)


def can_shard_store(store: CIMStore, n_shards: int, dim: str = "j") -> bool:
    """Whether every plane splits evenly into ``n_shards`` along ``dim``.

    ``dim='j'`` splits output columns in whole ``row_weights`` groups (one
    shard ≈ one macro column group); ``dim='k'`` splits word lines in whole
    exponent blocks (and whole 32-row sign words for ``protect='none'``).
    """
    if n_shards == 1:
        return True
    k_pad, j_pad = store.man.shape
    cfg = store.cfg
    if dim == "j":
        return j_pad % (n_shards * cfg.row_weights) == 0
    if dim == "k":
        if k_pad % (n_shards * cfg.n_group) != 0:
            return False
        return store.sign is None or k_pad % (n_shards * 32) == 0
    raise ValueError(f"dim must be 'j' or 'k', got {dim!r}")


def store_plane_specs(store: CIMStore, axis: str = "model", dim: str = "j"):
    """Per-plane ``PartitionSpec``s of the packed SRAM image.

    Every plane carries its shard axis in the same position: dimension 1
    (columns / column groups) for ``dim='j'``, dimension 0 (K rows, exponent
    blocks, sign words) for ``dim='k'`` — C-order strides are unchanged, so
    the counter-PRNG flip contract keeps holding shard by shard.
    """
    from jax.sharding import PartitionSpec as P
    sdim = 0 if dim == "k" else 1
    return {name: P(*[axis if d == sdim else None for d in range(p.ndim)])
            for name, p in _plane_dict(store).items()}


def store_shardings(store: CIMStore, mesh, *, axis: str = "model",
                    dim: str = "j") -> CIMStore:
    """A CIMStore-shaped pytree of ``NamedSharding``s for the packed planes
    (jit ``in_shardings`` / ``device_put`` target). Planes that do not split
    evenly fall back to replication — callers degrade cleanly on any mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n_sh = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    if can_shard_store(store, n_sh, dim):
        specs = store_plane_specs(store, axis, dim)
    else:
        specs = {name: P() for name in _plane_dict(store)}
    named = {name: NamedSharding(mesh, spec) for name, spec in specs.items()}
    cache_sh = None
    if store.cache is not None:
        # The decoded cache is logical [K, J]; split it along the same dim as
        # the planes when it divides evenly, else replicate.
        sdim = 0 if dim == "k" else 1
        if (can_shard_store(store, n_sh, dim)
                and store.cache.shape[sdim] % n_sh == 0):
            spec = P(*[axis if d == sdim else None for d in range(2)])
        else:
            spec = P()
        cache_sh = NamedSharding(mesh, spec)
    return CIMStore(man=named["man"], sign=named.get("sign"),
                    exp=named.get("exp"), codewords=named.get("cw"),
                    shape=store.shape, cfg=store.cfg, cache=cache_sh)


def shard_store(store: CIMStore, mesh, *, axis: str = "model",
                dim: str = "j") -> CIMStore:
    """Place the packed planes on ``mesh`` with the model axis split along
    ``dim`` (one shard ≈ one macro column group). The arrays stay global-view
    jax arrays: ``stored_bits`` / ``stored_bytes`` / ``read_reference`` are
    unchanged, and GSPMD partitions the pure-jnp paths automatically."""
    return jax.device_put(store, store_shardings(store, mesh, axis=axis,
                                                 dim=dim))


def _global_elem(local_shape, global_shape, sdim: int, start) -> jnp.ndarray:
    """C-order flat indices into the GLOBAL plane for a local shard block
    whose ``sdim`` dimension starts at (traced) offset ``start``."""
    elem = jnp.zeros(local_shape, jnp.uint32)
    stride = 1
    for d in reversed(range(len(global_shape))):
        idx = jax.lax.broadcasted_iota(jnp.uint32, local_shape, d)
        if d == sdim:
            idx = idx + jnp.asarray(start, jnp.uint32)
        elem = elem + idx * jnp.uint32(stride)
        stride *= int(global_shape[d])
    return elem


def inject_sharded(key, store: CIMStore, ber, field: str = "full", *, mesh,
                   axis: str = "model", dim: str = "j",
                   model=None) -> CIMStore:
    """``shard_map`` twin of :func:`inject` for a mesh-sharded store.

    Each shard draws flips for its LOCAL plane block at the block's GLOBAL
    C-order element indices (``axis_index * local_extent`` offset along the
    shard dimension), so the flip streams are bit-identical to the
    single-device image for the same key — no resharding, no all-gather.
    ``model`` thresholds compile from the same global indices against the
    GLOBAL plane shapes, so burst/correlated/drift masks are likewise
    bit-identical shard by shard.

    Call under ``jit`` on hot paths: the per-bit-lane mask loop is ~100 tiny
    ops, and eager ``shard_map`` dispatch of those across many host devices
    is orders of magnitude slower than the compiled executable.
    """
    if isinstance(ber, (int, float)) and ber <= 0.0:
        return store
    from jax.sharding import PartitionSpec as P
    from repro.kernels.fault_inject.ops import ber_to_threshold

    cfg = store.cfg
    n_sh = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    assert can_shard_store(store, n_sh, dim), \
        f"store {store.man.shape} does not split {n_sh}-way along {dim!r}"
    thr = ber_to_threshold(ber)
    zero = jnp.uint32(0)
    rt = {"seeds": plane_seeds(key),
          "thr_man": thr if field in ("full", "mantissa") else zero,
          "thr_meta": thr if field in ("full", "exponent_sign") else zero}

    planes = _plane_dict(store)
    gshapes = {name: p.shape for name, p in planes.items()}
    sdim = 0 if dim == "k" else 1
    mb, eb = cfg.fmt.man_bits, cfg.fmt.exp_bits
    valids = {"man": (1 << mb) - 1}
    seed_of = {"man": "man", "cw": "cw", "exp": "meta", "sign": "cw"}
    if "cw" in planes:
        valids["cw"] = codeword_valid_masks(cfg)
    else:
        valids["exp"] = (1 << eb) - 1
        k_pad = store.man.shape[0]
        smasks = bitpack.word_masks(k_pad, store.sign.shape[0])
        # dim='k' splits the sign word rows; divisibility by 32*n_sh (checked
        # above) guarantees no ragged word, so the scalar mask is exact
        valids["sign"] = np.uint32(0xFFFFFFFF) if dim == "k" and n_sh > 1 \
            else smasks[:, None]

    def local(planes_loc, rt_loc):
        i = jax.lax.axis_index(axis)
        out = {}
        for name, words in planes_loc.items():
            t = rt_loc["thr_man"] if name == "man" else rt_loc["thr_meta"]
            elem = _global_elem(words.shape, gshapes[name], sdim,
                                i * words.shape[sdim])
            seed = rt_loc["seeds"][seed_of[name]]
            t = fm.plane_thresholds(model, t, elem, seed, gshapes[name])
            out[name] = _flip_gathered(words, elem, seed, t, valids[name])
        return out

    pspecs = store_plane_specs(store, axis, dim)
    rt_specs = jax.tree_util.tree_map(lambda _: P(), rt)
    flipped = jax.shard_map(local, mesh=mesh, in_specs=(pspecs, rt_specs),
                            out_specs=pspecs, check_vma=False)(planes, rt)
    return _restore_planes(store, flipped)


def _flip_gathered(words, elem, seed, threshold, valid):
    """Counter-PRNG flips on gathered cells, streams identical to
    :func:`counter_flip_words` at the same flat ``elem`` indices.

    ``valid`` may be a static mask (int / np array) — skipping dead bit
    lanes — or a traced jnp mask (all 32 lanes drawn, then masked)."""
    from repro.kernels.fault_inject.kernel import hash_u32
    if isinstance(valid, jnp.ndarray):
        union = 0xFFFFFFFF
    else:
        valid = np.asarray(valid, np.uint32)
        union = int(np.bitwise_or.reduce(valid.ravel())) if valid.ndim \
            else int(valid)
    seed = jnp.asarray(seed, jnp.uint32) * jnp.uint32(0x9E3779B9)
    threshold = jnp.asarray(threshold, jnp.uint32)
    mask = jnp.zeros(words.shape, jnp.uint32)
    for p in range(32):
        if not (union >> p) & 1:
            continue
        z = (elem * jnp.uint32(32) + jnp.uint32(p)) ^ seed
        flip = (hash_u32(z) < threshold).astype(jnp.uint32)
        mask = mask | (flip << p)
    mask = mask & jnp.asarray(valid, jnp.uint32)
    return (words.astype(jnp.uint32) ^ mask).astype(words.dtype)


# ---------------------------------------------------------------------------
# Pytree-level API: deploy a whole model onto emulated CIM macros.
#
# The public entry point is now :class:`repro.core.deployment.CIMDeployment`
# (per-layer reliability policies, placement, dispatch); the free functions
# below are kept as deprecation shims over the private ``*_impl`` twins,
# which internal callers (deployment, sweep engine, benches) use directly.
# ---------------------------------------------------------------------------

def _deprecated(old: str, new: str) -> None:
    import warnings
    warnings.warn(
        f"repro.core.cim.{old} is deprecated; use {new} "
        f"(repro.core.deployment) instead", DeprecationWarning, stacklevel=3)


def _deployable(path, leaf) -> bool:
    return hasattr(leaf, "ndim") and leaf.ndim == 2 and \
        jnp.issubdtype(leaf.dtype, jnp.floating)


def deploy_pytree(params, cfg: CIMConfig, align_cfg=None, predicate=_deployable):
    """Deprecated shim: use ``CIMDeployment.deploy`` with a policy."""
    _deprecated("deploy_pytree", "CIMDeployment.deploy")
    return deploy_pytree_impl(params, cfg, align_cfg, predicate)


def deploy_pytree_impl(params, cfg: CIMConfig, align_cfg=None,
                       predicate=_deployable):
    """Align (optionally) + pack every 2-D weight; other leaves pass through.

    Returns (stores_pytree, aligned_params). Leaves >2-D are reshaped to 2-D
    by callers (conv kernels etc.) before deployment.
    """
    if align_cfg is None:
        align_cfg = align_lib.AlignmentConfig(n_group=cfg.n_group, index=cfg.index,
                                              fmt=cfg.fmt)
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    flat, treedef = jax.tree_util.tree_flatten(params)
    stores, aligned = [], []
    for path, leaf in zip(paths, flat):
        if predicate(path, leaf):
            w_al, _ = align_lib.align_matrix(leaf, align_cfg)
            stores.append(pack(w_al, cfg))
            aligned.append(w_al)
        else:
            stores.append(leaf)
            aligned.append(leaf)
    return (jax.tree_util.tree_unflatten(treedef, stores),
            jax.tree_util.tree_unflatten(treedef, aligned))


def _is_store(x) -> bool:
    return isinstance(x, CIMStore)


def inject_pytree(key, stores, ber, field: str = "full"):
    """Deprecated shim: use ``CIMDeployment.inject``."""
    _deprecated("inject_pytree", "CIMDeployment.inject")
    return inject_pytree_impl(key, stores, ber, field)


def inject_pytree_impl(key, stores, ber, field: str = "full", model=None):
    """Fresh faults into every store of a deployed model."""
    flat, treedef = jax.tree_util.tree_flatten(stores, is_leaf=_is_store)
    keys = jax.random.split(key, len(flat))
    out = [inject(k, s, ber, field, model=model) if _is_store(s) else s
           for k, s in zip(keys, flat)]
    return jax.tree_util.tree_unflatten(treedef, out)


def read_pytree(stores):
    """Deprecated shim: use ``CIMDeployment.read``."""
    _deprecated("read_pytree", "CIMDeployment.read")
    return read_pytree_impl(stores)


def read_pytree_impl(stores):
    """Decode every store -> (params, aggregated stats)."""
    flat, treedef = jax.tree_util.tree_flatten(stores, is_leaf=_is_store)
    out, corrected, uncorrectable = [], 0, 0
    for s in flat:
        if _is_store(s):
            w, st = read(s)
            out.append(w)
            corrected = corrected + st["corrected"]
            uncorrectable = uncorrectable + st["uncorrectable"]
        else:
            out.append(s)
    params = jax.tree_util.tree_unflatten(treedef, out)
    return params, {"corrected": corrected, "uncorrectable": uncorrectable}
