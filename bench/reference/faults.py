"""Plain reference of the deployed CIM image: alignment, fp16 storage, soft
errors and SECDED decode, written from the published scheme and from the
documented fault-stream contract, with nothing imported from the program.

The image of one ``[K, J]`` matrix (paper Fig. 3/4, One4N with N=8):

* every block of ``N`` rows along K shares the ``index``-th largest fp16
  exponent of the block; each sign class is min-max rescaled into that
  exponent's range and rounded to fp16 (paper Eq. 4);
* mantissas are 10 stored bits per weight, unprotected;
* for each block and each group of 16 columns, the 16 shared exponents (5
  bits each, LSB first) and the 8x16 sign bits (bit ``n*16 + t``) form a
  208-bit payload, split into two 104-bit SECDED words (extended Hamming:
  positions 1..111, parity at the powers of two, an overall parity bit at
  index 111).

Soft errors follow the counter-PRNG contract of the stored image: stored
bit ``p`` of the word at C-order index ``e`` of a plane flips iff
``fmix32((e*32 + p) ^ (seed * 0x9E3779B9)) < round(ber * 2^32)``, uint32
arithmetic throughout. The mantissa plane is ``[K, J]`` words with 10 live
bits; the codeword plane is ``[K/8, J/16, 2, 4]`` words with 112 live bits
per codeword. Decoding needs only the error pattern of each codeword: the
syndrome of a received word is that of its errors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_GROUP = 8
INDEX = 2
ROW = 16                 # columns per payload row group
EXP_BITS = 5
MAN_BITS = 10
BIAS = 15
SEG_DATA = 104           # data bits per SECDED word
BODY = 111               # Hamming body bits (104 data + 7 parity)
CW_BITS = 112            # stored bits per codeword (body + overall parity)
CW_STRIDE = 128          # counter stride of one codeword (4 uint32 words)
N_SEG = 2

LEAF_SALT = {"embed": 0x1001, "unembed": 0x2002}
REQUEST_CONST = 0x7FEED5A1
PREFIX_CONST = 0x5EEDC0DE
GOLDEN = 0x9E3779B9


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def fmix32(z):
    """murmur3 32-bit finalizer."""
    z = _u32(z)
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> 13)
    z = z * jnp.uint32(0xC2B2AE35)
    return z ^ (z >> 16)


def fold(seed, i):
    """Derive a stream seed from ``seed`` and index ``i``."""
    salt = _u32(i) * jnp.uint32(0x85EBCA6B) + jnp.uint32(GOLDEN)
    return fmix32(_u32(seed) ^ salt)


def threshold(ber: float) -> int:
    return int(round(ber * 2.0 ** 32))


def plane_seeds(key) -> dict:
    """Mantissa and codeword plane seeds of one image key."""
    k_man, _, k_cw = jax.random.split(key, 3)
    return {"man": jax.random.bits(k_man, (), jnp.uint32),
            "cw": jax.random.bits(k_cw, (), jnp.uint32)}


def request_salt(rid: int):
    return fold(REQUEST_CONST, rid)


def prefix_salt(tokens) -> int:
    """FNV-1a over the little-endian uint32 bytes of a token prefix."""
    h = (0x811C9DC5 ^ PREFIX_CONST) & 0xFFFFFFFF
    for b in np.asarray(tokens, "<u4").tobytes():
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h


def read_seeds(seeds: dict, leaf: str, salt, pos) -> dict:
    """The seeds of one dynamic read: folded by matrix, salt and position."""
    return {k: fold(fold(fold(v, LEAF_SALT[leaf]), salt), pos)
            for k, v in seeds.items()}


# ------------------------------------------------------------ alignment


def fp16_fields(w):
    bits = jax.lax.bitcast_convert_type(w.astype(jnp.float16), jnp.uint16)
    bits = bits.astype(jnp.uint32)
    return bits >> 15, (bits >> 10) & 31, bits & 1023


def align(w):
    """Exponent-aligned fp16 fields of ``w`` [K, J], K a multiple of 8.

    Returns (sign [K, J], shared exponent [K/8, J], mantissa [K, J]), all
    uint32."""
    k, j = w.shape
    blk = w.astype(jnp.float32).reshape(k // N_GROUP, N_GROUP, j)
    _, e, _ = fp16_fields(blk)
    shared = jnp.sort(e, axis=1)[:, N_GROUP - INDEX]             # [B, J]
    ll = jnp.exp2(shared.astype(jnp.float32) - BIAS)[:, None]
    ul = ll * (2.0 - 2.0 ** -MAN_BITS)
    mag = jnp.abs(blk)
    pos = blk >= 0

    def rescale(mask):
        hi = jnp.max(jnp.where(mask, mag, -jnp.inf), axis=1, keepdims=True)
        lo = jnp.min(jnp.where(mask, mag, jnp.inf), axis=1, keepdims=True)
        span = hi - lo
        ok = jnp.isfinite(span) & (span > 0)
        t = jnp.where(ok, (mag - lo) / jnp.where(ok, span, 1.0), 0.5)
        return t * (ul - ll) + ll

    y = jnp.where(pos, rescale(pos), rescale(~pos))
    y16 = jnp.clip(y, ll, ul).astype(jnp.float16)
    _, _, man = fp16_fields(y16)
    sign = (~pos).astype(jnp.uint32)
    return (sign.reshape(k, j), shared, man.reshape(k, j))


def assemble(sign, exp_rows, man):
    """fp16 value of (sign, per-row exponent, mantissa) fields, as f32."""
    bits = (sign << 15) | (exp_rows << 10) | man
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16),
                                        jnp.float16).astype(jnp.float32)


def clean(fields):
    sign, shared, man = fields
    return assemble(sign, jnp.repeat(shared, N_GROUP, axis=0), man)


# ------------------------------------------------------------ soft errors


def _flip(counter, seed, thr):
    return fmix32(counter ^ (_u32(seed) * jnp.uint32(GOLDEN))) < _u32(thr)


def mantissa_errors(rows, j_total: int, seed, thr):
    """XOR masks of the 10 mantissa bits for the given rows: [len(rows), J].
    One lane-dense [rows, J] pass per bit."""
    col = jnp.arange(j_total, dtype=jnp.uint32)
    base = (_u32(rows)[:, None] * jnp.uint32(j_total) + col[None]) \
        * jnp.uint32(32)
    mask = jnp.zeros(base.shape, jnp.uint32)
    for p in range(MAN_BITS):
        flip = _flip(base + jnp.uint32(p), seed, thr)
        mask = mask | (flip.astype(jnp.uint32) << p)
    return mask


@functools.lru_cache(maxsize=None)
def _data_body() -> np.ndarray:
    """Hamming body index of each of the 104 data bits of a segment."""
    positions = np.arange(1, BODY + 1)
    return np.asarray([p - 1 for p in positions if p & (p - 1)], np.int32)


def _codewords(blocks, g_total: int, seed, thr):
    """Per codeword bit, segment, block and column group (bit-major, the
    column groups minor): (errors [112, S, nb, G], position, single,
    double)."""
    blocks = _u32(blocks)
    g = jnp.arange(g_total, dtype=jnp.uint32)
    cw_index = blocks[:, None] * jnp.uint32(g_total) + g[None]      # [nb, G]
    seg = jnp.arange(N_SEG, dtype=jnp.uint32)[:, None, None]
    base = (cw_index[None] * jnp.uint32(N_SEG) + seg) * jnp.uint32(CW_STRIDE)
    bit = jnp.arange(CW_BITS, dtype=jnp.uint32)[:, None, None, None]
    err = _flip(base[None] + bit, seed, thr)                    # [112,S,nb,G]
    body_pos = jnp.where(bit < BODY, bit + 1, 0).astype(jnp.uint32)
    pos = jax.lax.reduce(jnp.where(err, body_pos, jnp.uint32(0)),
                         jnp.uint32(0), jax.lax.bitwise_xor, (0,))
    single = (jnp.sum(err, axis=0, dtype=jnp.int32) & 1) == 1
    return err, pos, single, (~single) & (pos > 0)


def codeword_errors(blocks, g_total: int, seed, thr):
    """Decoded payload errors and ECC status of the codewords of the given
    row blocks, all ``g_total`` column groups.

    Returns (exponent XOR [len(blocks), G*16], sign XOR [len(blocks)*8,
    G*16], corrected, uncorrectable) with the counts as int32 scalars."""
    nb = _u32(blocks).shape[0]
    err, pos, single, double = _codewords(blocks, g_total, seed, thr)
    data_body = _data_body()
    fix = single[None] & (pos[None] == jnp.asarray(
        data_body + 1, jnp.uint32)[:, None, None, None])
    data = (err[data_body] ^ fix).astype(jnp.uint32)          # [104,S,nb,G]
    payload = data.transpose(1, 0, 2, 3).reshape(N_SEG * SEG_DATA, nb,
                                                 g_total)
    exp_part = payload[:ROW * EXP_BITS].reshape(ROW, EXP_BITS, nb, g_total)
    shifts = jnp.arange(EXP_BITS, dtype=jnp.uint32)[None, :, None, None]
    exp_err = jnp.sum(exp_part << shifts, axis=1, dtype=jnp.uint32)
    exp_err = exp_err.transpose(1, 2, 0)                       # [nb, G, 16]
    sign_err = payload[ROW * EXP_BITS:].reshape(N_GROUP, ROW, nb, g_total)
    sign_err = sign_err.transpose(2, 0, 3, 1)                  # [nb,8,G,16]
    return (exp_err.reshape(nb, g_total * ROW),
            sign_err.reshape(nb * N_GROUP, g_total * ROW),
            jnp.sum(single, dtype=jnp.int32),
            jnp.sum(double, dtype=jnp.int32))


def image_rows(fields, rows, seeds, thr):
    """Faulted decoded rows ``rows`` of the image (row gather: only the
    touched blocks' codewords are decoded)."""
    sign, shared, man = fields
    k, j = man.shape
    rows = jnp.asarray(rows, jnp.int32)
    blocks = rows // N_GROUP
    m_err = mantissa_errors(rows, j, seeds["man"], thr)
    e_err, s_err, _, _ = codeword_errors(blocks, j // ROW, seeds["cw"], thr)
    within = rows % N_GROUP
    s_err = s_err.reshape(rows.shape[0], N_GROUP, j)[
        jnp.arange(rows.shape[0]), within]
    return assemble(sign[rows] ^ s_err, shared[blocks] ^ e_err,
                    man[rows] ^ m_err)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def image(fields, seeds, thr, block_rows: int = 256):
    """The whole faulted decoded image [K, J] and its ECC counts, built
    ``block_rows`` rows at a time."""
    sign, shared, man = fields
    k, j = man.shape
    n = k // block_rows

    def one(i):
        rows = i * block_rows + jnp.arange(block_rows, dtype=jnp.int32)
        blocks = i * (block_rows // N_GROUP) + jnp.arange(
            block_rows // N_GROUP, dtype=jnp.int32)
        m_err = mantissa_errors(rows, j, seeds["man"], thr)
        e_err, s_err, c, u = codeword_errors(blocks, j // ROW, seeds["cw"],
                                             thr)
        w = assemble(sign[rows] ^ s_err,
                     jnp.repeat(shared[blocks] ^ e_err, N_GROUP, axis=0),
                     man[rows] ^ m_err)
        return w, c, u

    w, c, u = jax.lax.map(one, jnp.arange(n))
    return w.reshape(k, j), jnp.sum(c), jnp.sum(u)


@functools.partial(jax.jit, static_argnames=("k", "j", "block_rows"))
def ecc_counts(seed_cw, thr, *, k: int, j: int, block_rows: int = 256):
    """ECC (corrected, uncorrectable) codeword counts of a whole image read
    with codeword seed ``seed_cw``."""
    nb = block_rows // N_GROUP

    def one(i):
        blocks = i * nb + jnp.arange(nb, dtype=jnp.int32)
        _, _, single, double = _codewords(blocks, j // ROW, seed_cw, thr)
        return (jnp.sum(single, dtype=jnp.int32),
                jnp.sum(double, dtype=jnp.int32))

    c, u = jax.lax.map(one, jnp.arange(k // block_rows))
    return jnp.sum(c), jnp.sum(u)
