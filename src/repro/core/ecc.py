"""Error-correcting codes for the One4N scheme (paper §III-B, Fig. 4).

Two layers:

* :class:`SecdedCode` — a single-error-correct / double-error-detect extended
  Hamming code over ``d`` data bits, vectorized over leading axes.  The decode
  syndrome follows the paper's Fig. 4 ③ semantics exactly:

    - ``R == 0``                      → no error,
    - parity bit of R set (R[7])      → single-bit error at position R[6:0],
      corrected by flipping that bit,
    - R[7] == 0 but R[6:0] != 0       → ≥2-bit error, uncorrectable (detected).

* :class:`One4NRowCodec` — the paper's row-based payload layout: for each
  ``N×(16 weights)`` block, the protected payload is the shared-exponent row
  (16 × exp_bits) followed by the N×16 sign bits (Eq. 3:
  ``TB = exp_bits·16 + N·16``).  The payload is split into
  ``ceil(TB/104)`` rows ("divided into two rows for encoding" for N=8), each
  SECDED-encoded with an 8-bit redundancy (7 Hamming + 1 overall parity).

Everything is implemented as jit-able jnp bit arithmetic; generator/parity-check
structure is precomputed with numpy at trace time.

Both codecs expose **two equivalent APIs**:

* the original per-bit API (``encode`` / ``decode`` on ``uint8`` bit arrays) —
  kept as the readable oracle the packed path is tested against;
* a word-packed API (``encode_packed`` / ``decode_packed`` on ``uint32`` word
  arrays, bit ``i`` in word ``i//32`` lane ``i%32``) — syndrome/parity bits
  are computed with precomputed per-word column masks + XOR-parity folds
  (:mod:`repro.core.bitpack`), and parity-bit placement/removal uses static
  single-bit funnel shifts. No ``int32`` bit-matrix matmuls, no ``.at[].set``
  scatters — this is the representation the packed :class:`~repro.core.cim`
  store and the fused ``cim_read`` kernel operate on.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import bitpack

# Max data bits covered by one SECDED row with a 7-bit Hamming syndrome
# (2^7 = 128 >= 104 + 7 + 1). The paper's N=8 block (208 payload bits) splits
# into exactly two 104-bit rows with 8 redundant bits each.
MAX_SEGMENT_DATA_BITS = 104


def _hamming_r(d: int) -> int:
    r = 1
    while (1 << r) < d + r + 1:
        r += 1
    return r


@functools.lru_cache(maxsize=None)
def _secded_tables(d: int):
    """Precompute position layout + parity-check matrix for d data bits."""
    r = _hamming_r(d)
    n = d + r                      # codeword length before overall parity
    positions = np.arange(1, n + 1)
    is_parity = (positions & (positions - 1)) == 0  # powers of two
    data_pos = positions[~is_parity]                # length d
    parity_pos = positions[is_parity]               # length r
    # H[j, i] = bit j of position (i+1): syndrome bit j = XOR of bits whose
    # position has bit j set.
    H = ((positions[None, :] >> np.arange(r)[:, None]) & 1).astype(np.int32)
    # encode matrix: parity bit at position 2^j = XOR of *data* bits whose
    # position has bit j set (parity positions excluded from their own sum).
    enc = H[:, ~is_parity]                          # [r, d]
    # scatter indices: codeword[pos-1]
    return r, n, data_pos - 1, parity_pos - 1, H, enc


@functools.lru_cache(maxsize=None)
def _secded_packed_tables(d: int):
    """Per-word column masks for the packed encode/decode of ``d`` data bits.

    Packed codeword layout: body bit ``i`` (0-based, position ``i+1``) at word
    ``i//32`` lane ``i%32``; the overall parity bit at bit index ``n``.
    """
    r, n, data_idx, _, _, _ = _secded_tables(d)
    Wd = bitpack.n_words(d)
    Wc = bitpack.n_words(n + 1)
    # syndrome bit j = parity of body bits whose 1-based position has bit j set
    hmask = np.zeros((r, Wc), np.uint32)
    for i in range(n):
        pos = i + 1
        for j in range(r):
            if (pos >> j) & 1:
                hmask[j, i // 32] |= np.uint32(1 << (i % 32))
    # encode: parity bit j = parity of DATA bits whose (data) position has bit j
    encmask = np.zeros((r, Wd), np.uint32)
    for q, i in enumerate(data_idx):          # i = 0-based codeword body index
        pos = i + 1
        for j in range(r):
            if (pos >> j) & 1:
                encmask[j, q // 32] |= np.uint32(1 << (q % 32))
    body_mask = bitpack.word_masks(n, Wc)          # body bits only
    code_mask = bitpack.word_masks(n + 1, Wc)      # body + overall parity
    data_mask = bitpack.word_masks(d, Wd)
    parity_pos0 = tuple((1 << j) - 1 for j in range(r))   # 0-based body indices
    return r, n, Wd, Wc, hmask, encmask, body_mask, code_mask, data_mask, \
        parity_pos0


@dataclasses.dataclass(frozen=True)
class SecdedCode:
    """Extended Hamming SECDED over ``data_bits`` bits (vectorized)."""

    data_bits: int

    @property
    def r(self) -> int:
        return _secded_tables(self.data_bits)[0]

    @property
    def n(self) -> int:
        """Codeword length including the overall parity bit."""
        return _secded_tables(self.data_bits)[1] + 1

    @property
    def redundant_bits(self) -> int:
        return self.r + 1

    def encode(self, data: jnp.ndarray) -> jnp.ndarray:
        """data [..., d] bits in {0,1} -> codeword [..., n] (overall parity last)."""
        r, n, data_idx, parity_idx, _, enc = _secded_tables(self.data_bits)
        data = data.astype(jnp.uint8)
        parity = (data.astype(jnp.int32) @ jnp.asarray(enc.T)) & 1  # [..., r]
        code = jnp.zeros(data.shape[:-1] + (n,), jnp.uint8)
        code = code.at[..., jnp.asarray(data_idx)].set(data)
        code = code.at[..., jnp.asarray(parity_idx)].set(parity.astype(jnp.uint8))
        overall = jnp.sum(code, axis=-1, dtype=jnp.int32) & 1
        return jnp.concatenate([code, overall[..., None].astype(jnp.uint8)], axis=-1)

    def decode(self, code: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """codeword [..., n] -> (data [..., d], status [...]).

        status: 0 = clean, 1 = corrected single error, 2 = uncorrectable (>=2).
        """
        r, n, data_idx, _, H, _ = _secded_tables(self.data_bits)
        body = code[..., :n].astype(jnp.int32)
        overall_bit = code[..., n].astype(jnp.int32)
        syndrome_bits = (body @ jnp.asarray(H.T)) & 1            # [..., r]
        pos = jnp.sum(syndrome_bits << jnp.arange(r), axis=-1)   # R[6:0], 1-based
        parity = (jnp.sum(body, axis=-1) + overall_bit) & 1      # R[7]

        clean = (pos == 0) & (parity == 0)
        single = parity == 1          # odd number of flips -> assume 1, correctable
        double = (parity == 0) & (pos > 0)

        # Correct: flip bit at position ``pos`` (1-based). pos==0 with parity==1
        # means the overall parity bit itself flipped — body untouched.
        flip = (jnp.arange(1, n + 1) == pos[..., None]) & single[..., None]
        corrected = body ^ flip.astype(jnp.int32)
        data = corrected[..., jnp.asarray(data_idx)].astype(jnp.uint8)
        status = jnp.where(clean, 0, jnp.where(double, 2, 1)).astype(jnp.int32)
        return data, status

    # ------------------------------------------------------- packed (uint32)

    @property
    def data_words(self) -> int:
        return bitpack.n_words(self.data_bits)

    @property
    def code_words(self) -> int:
        return bitpack.n_words(self.n)

    @property
    def code_word_masks(self) -> np.ndarray:
        """uint32 [code_words] validity mask of stored codeword bits."""
        return _secded_packed_tables(self.data_bits)[7]

    def encode_packed(self, data_words: jnp.ndarray) -> jnp.ndarray:
        """Packed encode: data [..., data_words] uint32 -> [..., code_words].

        Parity bits come from XOR-parity folds against precomputed column
        masks; their placement at the power-of-two positions is a sequence of
        static single-bit funnel shifts (no scatters).
        """
        r, n, Wd, Wc, _, encmask, _, _, data_mask, parity_pos0 = \
            _secded_packed_tables(self.data_bits)
        dw = [data_words[..., w].astype(jnp.uint32) & jnp.uint32(data_mask[w])
              for w in range(Wd)]
        parity = [bitpack.masked_parity(dw, encmask[j]) for j in range(r)]
        body = dw + [jnp.zeros_like(dw[0]) for _ in range(Wc - Wd)]
        for pp in parity_pos0:                    # ascending 0, 1, 3, 7, ...
            body = bitpack.insert_zero_bit(body, pp)
        for j, pp in enumerate(parity_pos0):
            wl, sh = divmod(pp, 32)
            body[wl] = body[wl] | (parity[j] << sh)
        overall = bitpack.masked_parity(body, bitpack.word_masks(n, Wc))
        wl, sh = divmod(n, 32)
        body[wl] = body[wl] | (overall << sh)
        return bitpack.from_words(body)

    def syndrome_packed(self, code_words: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Syndrome half of :meth:`decode_packed` — the expensive part.

        All ``r + 1`` XOR-parity folds against the precomputed per-word
        column masks happen here (the "per-word column-mask folds" the fused
        kernel hoists: one syndrome per codeword tile, reused across output
        revisits). Returns ``(pos, parity, status)``: the 1-based error
        position ``R[6:0]``, the overall-parity bit ``R[7]``, and the
        0/1/2 clean/corrected/uncorrectable status.
        """
        return self.syndrome_words(bitpack.to_words(code_words))

    def syndrome_words(self, cw):
        """:meth:`syndrome_packed` on a word list (one array per code word
        — the form the fused kernel carries, with no trailing word axis)."""
        r, n, Wd, Wc, hmask, _, body_mask, _, _, _ = \
            _secded_packed_tables(self.data_bits)
        body = [cw[w] & jnp.uint32(body_mask[w]) for w in range(Wc)]
        synd = [bitpack.masked_parity(body, hmask[j]) for j in range(r)]
        pos = synd[0]
        for j in range(1, r):
            pos = pos | (synd[j] << j)                       # 1-based, R[6:0]
        owl, osh = divmod(n, 32)
        overall_bit = (cw[owl] >> osh) & jnp.uint32(1)
        parity = bitpack.masked_parity(body, bitpack.word_masks(n, Wc)) \
            ^ overall_bit                                    # R[7]
        clean = (pos == 0) & (parity == 0)
        double = (parity == 0) & (pos > 0)
        status = jnp.where(clean, 0, jnp.where(double, 2, 1)).astype(jnp.int32)
        return pos, parity, status

    def correct_extract_packed(self, code_words: jnp.ndarray, pos: jnp.ndarray,
                               parity: jnp.ndarray) -> jnp.ndarray:
        """Correction half of :meth:`decode_packed` — the cheap part.

        Flips the single errored bit located by ``(pos, parity)`` (from
        :meth:`syndrome_packed`) and removes the parity-bit positions with
        static funnel shifts. Returns the packed data words.
        """
        return bitpack.from_words(self.correct_extract_words(
            bitpack.to_words(code_words), pos, parity))

    def correct_extract_words(self, cw, pos, parity):
        """:meth:`correct_extract_packed` on a word list -> data word list."""
        r, n, Wd, Wc, _, _, body_mask, _, data_mask, parity_pos0 = \
            _secded_packed_tables(self.data_bits)
        body = [cw[w] & jnp.uint32(body_mask[w]) for w in range(Wc)]
        single = parity == 1
        do_flip = single & (pos > 0)
        pos0 = jnp.where(pos > 0, pos - 1, 0)
        flip_word = pos0 // 32
        flip_bit = jnp.left_shift(jnp.uint32(1), pos0 % 32)
        for w in range(Wc):
            flipw = jnp.where(do_flip & (flip_word == w), flip_bit,
                              jnp.uint32(0)) & jnp.uint32(body_mask[w])
            body[w] = body[w] ^ flipw
        for pp in reversed(parity_pos0):          # descending 63, 31, ..., 0
            body = bitpack.delete_bit(body, pp)
        return [body[w] & jnp.uint32(data_mask[w]) for w in range(Wd)]

    def decode_packed(self, code_words: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Packed decode: [..., code_words] uint32 -> (data words, status).

        Bit-exact with :meth:`decode` on the unpacked bits (same syndrome
        semantics, same status codes 0/1/2). Composition of
        :meth:`syndrome_packed` (column-mask folds) and
        :meth:`correct_extract_packed` (flip + funnel-shift extraction) —
        callers that reuse one syndrome across several passes over the same
        codeword tile call the halves directly.
        """
        pos, parity, status = self.syndrome_packed(code_words)
        return self.correct_extract_packed(code_words, pos, parity), status


@dataclasses.dataclass(frozen=True)
class One4NRowCodec:
    """Row-based One4N payload codec for an ``N x (row_weights)`` weight block.

    Payload per block & 16-weight row group (paper Eq. 3):
      ``[exp_0 .. exp_15] (exp_bits each)  ||  sign bits (N x row_weights)``.
    """

    n_group: int = 8          # N — weights sharing one exponent (input channel)
    row_weights: int = 16     # FP16 weights per 256-bit SRAM row
    exp_bits: int = 5
    sign_bits_per_row: int = 16

    @property
    def payload_bits(self) -> int:
        # TB = exp_bits * row_weights + N * row_weights (Eq. 3 with 16 weights/row)
        return self.exp_bits * self.row_weights + self.n_group * self.sign_bits_per_row

    @property
    def n_segments(self) -> int:
        return math.ceil(self.payload_bits / MAX_SEGMENT_DATA_BITS)

    @property
    def segment_bits(self) -> int:
        return math.ceil(self.payload_bits / self.n_segments)

    @property
    def code(self) -> SecdedCode:
        return SecdedCode(self.segment_bits)

    @property
    def redundant_bits_per_block(self) -> int:
        return self.n_segments * self.code.redundant_bits

    @property
    def padded_bits(self) -> int:
        return self.n_segments * self.segment_bits

    def build_payload(self, exp_row: jnp.ndarray, signs: jnp.ndarray) -> jnp.ndarray:
        """exp_row [..., 16] ints, signs [..., N, 16] bits -> payload bits."""
        from repro.core.bitops import unpack_bits
        exp_bits = unpack_bits(exp_row, self.exp_bits)                  # [...,16,5]
        exp_flat = exp_bits.reshape(exp_bits.shape[:-2] + (-1,))
        sign_flat = signs.astype(jnp.uint8).reshape(signs.shape[:-2] + (-1,))
        payload = jnp.concatenate([exp_flat, sign_flat], axis=-1)
        pad = self.padded_bits - self.payload_bits
        if pad:
            payload = jnp.concatenate(
                [payload, jnp.zeros(payload.shape[:-1] + (pad,), jnp.uint8)], axis=-1)
        return payload

    def split_payload(self, payload: jnp.ndarray):
        """Inverse of build_payload -> (exp_row [...,16], signs [..., N, 16])."""
        from repro.core.bitops import pack_bits
        eb = self.exp_bits * self.row_weights
        exp_flat = payload[..., :eb].reshape(payload.shape[:-1] + (self.row_weights, self.exp_bits))
        exp_row = pack_bits(exp_flat, jnp.uint8)
        sb = self.n_group * self.sign_bits_per_row
        signs = payload[..., eb:eb + sb].reshape(
            payload.shape[:-1] + (self.n_group, self.sign_bits_per_row)).astype(jnp.uint8)
        return exp_row, signs

    def encode(self, exp_row: jnp.ndarray, signs: jnp.ndarray) -> jnp.ndarray:
        """-> codewords [..., n_segments, code.n] bits."""
        payload = self.build_payload(exp_row, signs)
        segs = payload.reshape(payload.shape[:-1] + (self.n_segments, self.segment_bits))
        return self.code.encode(segs)

    def decode(self, codewords: jnp.ndarray):
        """-> (exp_row [...,16], signs [...,N,16], status [..., n_segments])."""
        data, status = self.code.decode(codewords)
        payload = data.reshape(data.shape[:-2] + (self.padded_bits,))
        payload = payload[..., :self.payload_bits] if self.padded_bits != self.payload_bits \
            else payload
        exp_row, signs = self.split_payload(payload)
        return exp_row, signs, status

    # ------------------------------------------------------- packed (uint32)

    @property
    def sign_bits(self) -> int:
        return self.n_group * self.sign_bits_per_row

    @property
    def sign_words(self) -> int:
        """uint32 words holding one block's sign bits (bit = i_n*row + t)."""
        return bitpack.n_words(self.sign_bits)

    @property
    def payload_words(self) -> int:
        return bitpack.n_words(self.padded_bits)

    @property
    def codeword_words(self) -> int:
        return self.code.code_words

    def pack_signs(self, signs: jnp.ndarray) -> jnp.ndarray:
        """signs [..., N, row_weights] bits -> packed [..., sign_words]."""
        flat = signs.reshape(signs.shape[:-2] + (self.sign_bits,))
        return bitpack.pack_bits_words(flat, self.sign_bits)

    def unpack_signs(self, sign_words: jnp.ndarray) -> jnp.ndarray:
        """Packed [..., sign_words] -> signs [..., N, row_weights] uint8 bits."""
        bits = bitpack.unpack_words(sign_words, self.sign_bits)
        return bits.reshape(bits.shape[:-1] +
                            (self.n_group, self.sign_bits_per_row))

    def build_payload_packed(self, exp_row: jnp.ndarray,
                             sign_words: jnp.ndarray):
        """exp_row [..., row_weights] ints + packed signs -> payload words list.

        Payload bit layout matches :meth:`build_payload`: ``row_weights``
        exponent fields of ``exp_bits`` each, then the ``N*row_weights`` sign
        bits, then zero padding up to ``padded_bits``.
        """
        eb, rw = self.exp_bits, self.row_weights
        pw = bitpack.zeros_like_words(exp_row[..., 0], self.payload_words)
        for t in range(rw):
            bitpack.or_window(pw, [exp_row[..., t].astype(jnp.uint32)],
                              t * eb, eb)
        off = rw * eb
        for v in range(self.sign_words):
            nb = min(32, self.sign_bits - 32 * v)
            bitpack.or_window(pw, [sign_words[..., v].astype(jnp.uint32)],
                              off + 32 * v, nb)
        return pw

    def split_payload_packed(self, pw):
        """Payload word list -> (exp_row [..., row_weights] uint8,
        sign_words [..., sign_words])."""
        eb, rw = self.exp_bits, self.row_weights
        exps = [bitpack.extract_window(pw, t * eb, eb)[0] for t in range(rw)]
        exp_row = jnp.stack(exps, axis=-1).astype(jnp.uint8)
        off = rw * eb
        svs = [bitpack.extract_window(pw, off + 32 * v,
                                      min(32, self.sign_bits - 32 * v))[0]
               for v in range(self.sign_words)]
        return exp_row, jnp.stack(svs, axis=-1)

    def encode_packed(self, exp_row: jnp.ndarray,
                      sign_words: jnp.ndarray) -> jnp.ndarray:
        """-> packed codewords [..., n_segments, codeword_words] uint32."""
        pw = self.build_payload_packed(exp_row, sign_words)
        segs = [bitpack.from_words(
            bitpack.extract_window(pw, s * self.segment_bits, self.segment_bits))
            for s in range(self.n_segments)]
        return self.code.encode_packed(jnp.stack(segs, axis=-2))

    def decode_packed(self, codewords: jnp.ndarray):
        """Packed codewords [..., n_segments, codeword_words] ->
        (exp_row [..., row_weights], sign_words [..., sign_words],
        status [..., n_segments])."""
        W = codewords.shape[-1]
        pw, status = self.decode_words(
            [[codewords[..., s, w].astype(jnp.uint32) for w in range(W)]
             for s in range(self.n_segments)])
        exp_row, sign_words = self.split_payload_packed(pw)
        return exp_row, sign_words, jnp.stack(status, axis=-1)

    def decode_words(self, segments):
        """SECDED-decode one block's segments, each a list of codeword words
        (one array per word), -> (payload word list, per-segment status
        list). The array-free form :meth:`decode_packed` and the fused
        kernel share."""
        code = self.code
        pw = bitpack.zeros_like_words(segments[0][0], self.payload_words)
        status = []
        for s, cw in enumerate(segments):
            pos, parity, st = code.syndrome_words(cw)
            data = code.correct_extract_words(cw, pos, parity)
            bitpack.or_window(pw, data, s * self.segment_bits,
                              self.segment_bits)
            status.append(st)
        return pw, status


def residual_ber_after_secded(ber: float, codeword_bits: Optional[int] = None,
                              codec: Optional[One4NRowCodec] = None) -> float:
    """Post-ECC residual error rate per protected bit.

    SECDED corrects one flip per codeword; a bit stays wrong only when its
    codeword took >=2 flips. With n-bit codewords and i.i.d. flips at ``ber``:
        P(>=2 flips) = 1 - (1-p)^n - n p (1-p)^(n-1)
    and conditional on that, ~2 of n bits are wrong. Used for closed-form
    injection at scales where bit-plane emulation is impractical (launcher
    dynamic mode); the bit-accurate path is ``repro.core.cim``.

    ``codeword_bits`` defaults to the stored codeword length of the active
    ``codec`` (or the paper's default :class:`One4NRowCodec`, 112 bits for
    N=8) so non-default ``n_group`` / ``row_weights`` configurations get a
    consistent closed form without callers hard-coding the length.
    """
    import math as _math
    if codeword_bits is None:
        codeword_bits = (codec or One4NRowCodec()).code.n
    n, p = codeword_bits, ber
    if p <= 0:
        return 0.0
    p_ge2 = 1.0 - (1.0 - p) ** n - n * p * (1.0 - p) ** (n - 1)
    return p_ge2 * 2.0 / n


def secded_redundant_bits(protected_bits: int) -> int:
    """SECDED redundancy (Hamming r + overall parity) for a payload.

    Matches every count in the paper: 6-bit sign+exponent -> 5 (§III-A2),
    10-bit mantissa -> 5, 96-bit unified row -> 8 (§III-B1), 104-bit One4N
    segment -> 8, 160-bit mantissa row -> 9 (Table III row-based full-num).
    """
    return _hamming_r(protected_bits) + 1
