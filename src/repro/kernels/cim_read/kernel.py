"""Pallas TPU kernel: fused SECDED decode + FP16 reconstruction + matmul.

The serving-path realization of the packed CIM store (DESIGN: decode-on-read).
Weights stream HBM->VMEM **in the macro's packed SRAM layout** — a uint16
mantissa plane plus either word-packed One4N codewords (``protect='one4n'``)
or a raw exponent plane + K-packed sign words (``protect='none'``). Each
weight tile is ECC-decoded and reconstructed to fp32 *in VMEM* and fed
straight to the MXU; decoded fp16 weight matrices never exist in HBM:

    SECDED syndrome/correction  -> XOR-parity folds on uint32 words
                                   (`ecc.syndrome_packed` +
                                   `ecc.correct_extract_packed`, shared code)
    exponent summation array    -> shared-exponent pow2 scale (exact)
    sign processing unit (XOR)  -> sign factor in the reconstruction
    mantissa multiplication     -> MXU dot on the reconstructed tile

The decode follows the hybrid-domain split of arXiv:2502.07212: the
exponent/SECDED path (``_meta_decode_*`` — all the per-word column-mask
parity folds, the correction and the sign/exponent expansion) is separated
from the cheap mantissa path (``_reconstruct_f32``), and both depend only on
the ``(j, kk)`` plane tile — never on the output-row index ``i``.

Optional **per-read dynamic injection**: with ``dynamic=True`` the kernel
draws counter-PRNG flip masks over the packed words before decoding —
bit-identical streams to :func:`repro.core.cim.inject` (same murmur3 hash,
same per-plane seeds, element index computed in *store* coordinates so
tile-level padding never shifts the streams). Thresholds and seeds are SMEM
scalars: sweeping BER or read index does not recompile. The flip masks are
functions of the ``(j, kk)`` tile coordinates only, so dynamic injection
hoists exactly like the clean decode.

Grid: (N/bn, M/bm, K/bk) — **j outermost, i middle, kk innermost** with
output revisiting; the [bm, bn] fp32 accumulator stays in VMEM across the K
loop, and plane tiles stream through ``pallas_call``'s pipelined
(double-buffered) BlockSpec windows across the K loop. Each plane tile is
decoded in two stages into a VMEM scratch strip: the per-block metadata
(exponents + signs, ``[bk/n, bn]`` int32) once per tile, then the weight
rows in ``decode_rows``-row chunks, so the decode's working set is bounded
by the chunk and not by the tile; one dot over the whole strip follows.
With ``hoist=True`` the strip holds the full decoded [K, bn] column: each
plane tile is decoded once at ``i == 0`` and the following M-row revisits
re-use it — the i dimension is marked "arbitrary" so the revisits stay
sequential on a core. ``bn`` must cover whole ``row_weights`` groups and
``bk`` whole exponent blocks (plus whole sign words for the raw path).

Every relayout in the decode is a transpose or a sublane split/merge, and
every unsigned-to-float conversion goes through int32: the forms Mosaic
compiles (``tests/test_chip_compile.py`` holds the kernel to that at the
published olmo-1b unembed width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitpack
from repro.core.ecc import One4NRowCodec
from repro.core.faultmodels import scale_elem_thresholds
from repro.kernels.fault_inject.kernel import hash_u32

# SMEM scalar layout (uint32[9]); thresholds of 0 mean "no flips".
SCALAR_THR_MAN = 0     # mantissa-field Bernoulli threshold
SCALAR_THR_META = 1    # exponent_sign-field Bernoulli threshold
SCALAR_SEED_MAN = 2    # mantissa-plane seed
SCALAR_SEED_META = 3   # raw-exponent-plane seed   (protect='none')
SCALAR_SEED_CW = 4     # codeword-plane seed (protected) / sign-plane seed
SCALAR_OFF_K = 5       # global K-row offset of this shard's plane block
SCALAR_OFF_J = 6       # global J-column offset of this shard's plane block
SCALAR_M_THR = 7       # fault-model parameter: burst hit threshold /
                       # correlated strength (Q16)
SCALAR_M_LEN = 8       # fault-model parameter: burst run length /
                       # correlated period
# The offsets put the dynamic flip streams in GLOBAL store coordinates when
# the planes are mesh-sharded (ops.cim_linear_store_sharded): each shard's
# kernel sees only its local block, but elem indices — and therefore the
# counter-PRNG draws — match the single-device image bit for bit. They are
# traced SMEM values, so every shard runs the same compiled program. The
# fault-model *parameters* are traced the same way (sweeping a rate or run
# length never recompiles), while the model's KIND/AXIS are static kernel
# arguments picking the threshold-compilation code path — exactly like
# `dynamic` itself. Per-element thresholds come from
# ``faultmodels.scale_elem_thresholds`` on the same GLOBAL element indices,
# so kernel streams stay bit-identical to the jnp inject paths per process.


def _flip_mask(elem: jnp.ndarray, seed, threshold, positions) -> jnp.ndarray:
    """Counter-PRNG flip mask over ``positions`` for word elements ``elem``
    (same streams as ``cim.counter_flip_words`` / the fault_inject kernel)."""
    seed = seed * jnp.uint32(0x9E3779B9)
    mask = jnp.zeros(elem.shape, jnp.uint32)
    for p in positions:
        z = (elem * jnp.uint32(32) + jnp.uint32(p)) ^ seed
        flip = (hash_u32(z) < threshold).astype(jnp.uint32)
        mask = mask | (flip << p)
    return mask


def _reconstruct_f32(sign_bit, e_full, man, *, man_bits: int, exp_bits: int,
                     bias: int) -> jnp.ndarray:
    """IEEE-faithful fp16-grid reconstruction (incl. subnormal/inf/nan, so a
    corrupted exponent behaves exactly like the bitcast `read` path). This is
    the cheap mantissa half of the hybrid-domain split — elementwise only, no
    parity folds. Integer operands are int32: Mosaic has no unsigned-to-float
    conversion, and every field here fits 16 bits."""
    man_f = (man.astype(jnp.int32) & ((1 << man_bits) - 1)).astype(jnp.float32)
    e = e_full.astype(jnp.int32)
    frac = man_f * (2.0 ** -man_bits)
    # 2^(e-bias) built by exponent-field bitcast: jnp.exp2 is a polynomial on
    # some backends and lands a few ulp off exact powers of two for large
    # (corrupted) exponents, which broke bit-identity with the bitcast `read`
    # path. e-bias+127 stays inside the normal f32 exponent range for every
    # 5-bit e, and (1+frac) * 2^s is exact, so normals match fp16 bit for bit.
    scale = jax.lax.bitcast_convert_type(
        jnp.left_shift(e - bias + 127, 23).astype(jnp.int32), jnp.float32)
    normal = (1.0 + frac) * scale
    sub = frac * (2.0 ** (1 - bias))
    emax = (1 << exp_bits) - 1
    special = jnp.where(man_f == 0.0, jnp.float32(jnp.inf), jnp.float32(jnp.nan))
    mag = jnp.where(e == 0, sub, jnp.where(e == emax, special, normal))
    sgn = jnp.where(sign_bit.astype(jnp.int32) & 1 == 1, -1.0, 1.0)
    return sgn.astype(jnp.float32) * mag


def _expand_rows(v, n_group: int):
    """[rows, bn] per-block rows -> [rows * n_group, bn] (each row repeated
    ``n_group`` times). Sublane-only broadcast: Mosaic-safe for 32-bit."""
    r, bn = v.shape
    return jnp.broadcast_to(v[:, None, :], (r, n_group, bn)).reshape(
        r * n_group, bn)


def _column_meta(pw, codec: One4NRowCodec):
    """Decoded payload words -> one packed int32 per weight column ``t`` of
    the row group: ``exp_t | sign_bits << exp_bits``, where sign bit ``i_n``
    (weight row ``i_n`` of the block) is payload bit ``rw*eb + i_n*rw + t``.
    Returns a list over ``t`` of arrays shaped like the payload words."""
    eb, rw, n = codec.exp_bits, codec.row_weights, codec.n_group
    off = rw * eb
    out = []
    for t in range(rw):
        v = bitpack.extract_window(pw, t * eb, eb)[0]
        for i_n in range(n):
            wl, sh = divmod(off + i_n * rw + t, bitpack.WORD)
            v = v | (((pw[wl] >> sh) & jnp.uint32(1)) << (eb + i_n))
        out.append(v.astype(jnp.int32))
    return out


def _codeword_lane_masks(codec: One4NRowCodec, shape):
    """Per-lane validity mask of the lane-dense codeword tile (lane ``l``
    holds word ``l % W`` of its codeword) and the union of valid bit
    positions."""
    masks = codec.code.code_word_masks
    w_of = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % len(masks)
    valid = jnp.zeros(shape, jnp.uint32)
    for w, m in enumerate(masks):
        valid = jnp.where(w_of == w, jnp.uint32(m), valid)
    positions = tuple(p for p in range(32)
                      if any((int(m) >> p) & 1 for m in masks))
    return valid, positions


class _Fault:
    """The SMEM scalars of one dynamic read, and the per-element flip masks
    they define in GLOBAL store coordinates."""

    def __init__(self, scalars_ref, *, model_kind: str, model_axis: str):
        self.thr_man = scalars_ref[SCALAR_THR_MAN]
        self.thr_meta = scalars_ref[SCALAR_THR_META]
        self.seed_man = scalars_ref[SCALAR_SEED_MAN]
        self.seed_meta = scalars_ref[SCALAR_SEED_META]
        self.seed_cw = scalars_ref[SCALAR_SEED_CW]
        self.off_k = scalars_ref[SCALAR_OFF_K]
        self.off_j = scalars_ref[SCALAR_OFF_J]
        self.m_thr = scalars_ref[SCALAR_M_THR]
        self.m_len = scalars_ref[SCALAR_M_LEN]
        self.kind, self.axis = model_kind, model_axis

    def mask(self, elem, thr, seed, positions, *, width: int,
             col_div: int = 1):
        t = scale_elem_thresholds(elem, thr, seed, kind=self.kind,
                                  axis=self.axis, m_thr=self.m_thr,
                                  m_len=self.m_len, width=width,
                                  col_div=col_div)
        return _flip_mask(elem, seed, t, positions)

    def plane_rows(self, shape, row0, *, row_div: int):
        """GLOBAL plane rows of a ``shape`` window starting at local row
        ``row0``; ``row_div`` maps the shard's K offset onto this plane."""
        return jax.lax.broadcasted_iota(jnp.uint32, shape, 0) \
            + jnp.uint32(row0) + self.off_k // jnp.uint32(row_div)

    def plane_elem(self, shape, row0, col0, *, row_div: int, width: int):
        """C-order element indices of a ``shape`` window whose first row and
        column sit at local plane coordinates ``(row0, col0)``."""
        cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1) \
            + jnp.uint32(col0) + self.off_j
        return self.plane_rows(shape, row0, row_div=row_div) \
            * jnp.uint32(width) + cols


def _mantissa_rows(fault, man, j, k0, *, man_bits: int, store_j: int,
                   block_n: int):
    """One chunk of mantissa rows (local K rows ``k0 ...``), faulted."""
    if fault is None:
        return man
    elem = fault.plane_elem(man.shape, k0, j * block_n, row_div=1,
                            width=store_j)
    return man ^ fault.mask(elem, fault.thr_man, fault.seed_man,
                            tuple(range(man_bits)),
                            width=store_j).astype(man.dtype)


def _meta_one4n(fault, cw, j, kk, *, codec: One4NRowCodec, n_group: int,
                store_g: int, block_n: int, block_k: int):
    """Exponent/SECDED stage of a one4n tile -> int32 [bk/n, bn] holding
    ``exp | sign_bits << exp_bits`` per (block, weight column).

    ``cw`` is the lane-dense codeword tile ``[bkb, bng * S * W]`` (lane
    ``g*S*W + s*W + w``). Dynamic flips are drawn in that layout; it is then
    transposed once so every codeword word is a ``[bng, bkb]`` array,
    SECDED-decoded (``ecc.One4NRowCodec.decode_words``: the per-word
    column-mask syndrome folds + correction), packed per weight column,
    interleaved along sublanes to ``[bn, bkb]`` (row ``g*rw + t``) and
    transposed back.
    """
    rw = codec.row_weights
    s_, w_ = codec.n_segments, codec.codeword_words
    bkb, bng = block_k // n_group, block_n // rw
    if fault is not None:
        # codeword word (b, g, s, w) sits at C-order element
        # (b * G + g) * S*W + s*W + w of the store's [B, G, S, W] plane
        c0 = jnp.uint32(j * bng) + fault.off_j // jnp.uint32(rw)
        rows = jax.lax.broadcasted_iota(jnp.uint32, cw.shape, 0) \
            + jnp.uint32(kk * bkb) + fault.off_k // jnp.uint32(n_group)
        cols = jax.lax.broadcasted_iota(jnp.uint32, cw.shape, 1) \
            + c0 * jnp.uint32(s_ * w_)
        celem = rows * jnp.uint32(store_g * s_ * w_) + cols
        valid, positions = _codeword_lane_masks(codec, cw.shape)
        cw = cw ^ (fault.mask(celem, fault.thr_meta, fault.seed_cw, positions,
                              width=store_g * s_ * w_, col_div=s_ * w_)
                   & valid)
    cw_t = cw.T.reshape(bng, s_ * w_, bkb)
    segments = [[cw_t[:, s * w_ + w, :] for w in range(w_)]
                for s in range(s_)]
    pw, _ = codec.decode_words(segments)
    meta = jnp.stack(_column_meta(pw, codec), axis=1)    # [bng, rw, bkb]
    return meta.reshape(block_n, bkb).T                  # [bkb, bn]


def _meta_raw(fault, exp, j, kk, *, n_group: int, exp_bits: int,
              store_j: int, block_n: int, block_k: int):
    """Exponent stage of an unprotected tile -> int32 [bk/n, bn]."""
    e = exp.astype(jnp.int32)
    if fault is not None:
        elem = fault.plane_elem(e.shape, kk * (block_k // n_group),
                                j * block_n, row_div=n_group, width=store_j)
        e = e ^ fault.mask(elem, fault.thr_meta, fault.seed_meta,
                           tuple(range(exp_bits)),
                           width=store_j).astype(jnp.int32)
    return e


def _sign_rows_raw(fault, signw, j, w0, *, store_k: int, store_j: int,
                   block_n: int):
    """K-packed sign words (local word rows ``w0 ...``) -> sign-bit rows."""
    if fault is not None:
        elem = fault.plane_elem(signw.shape, w0, j * block_n, row_div=32,
                                width=store_j)
        smask = fault.mask(elem, fault.thr_meta, fault.seed_cw,
                           tuple(range(32)), width=store_j)
        # lanes beyond the store's K rows are not cells: mask them off
        w_glob = fault.plane_rows(signw.shape, w0, row_div=32)
        n_valid = jnp.clip(jnp.int32(store_k) - w_glob.astype(jnp.int32) * 32,
                           0, 32)
        valid = jnp.where(n_valid >= 32, jnp.uint32(0xFFFFFFFF),
                          (jnp.uint32(1) << (n_valid & 31).astype(jnp.uint32))
                          - jnp.uint32(1))
        signw = signw ^ (smask & valid)
    bkw, bn = signw.shape
    lane = jax.lax.broadcasted_iota(jnp.uint32, (bkw, 32, bn), 1)
    return ((signw[:, None, :] >> lane) & 1).reshape(bkw * 32, bn)


def _matmul(x, w):
    """The MXU product at full f32 precision: decoded weights are fp16-exact
    and a single bf16 pass would round them."""
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _cim_read_kernel(scalars_ref, x_ref, man_ref, *refs, protect: str,
                     codec: One4NRowCodec, n_group: int, man_bits: int,
                     exp_bits: int, bias: int, store_k: int, store_g: int,
                     store_j: int, block_n: int, block_k: int,
                     decode_rows: int, dynamic: bool, hoist: bool,
                     model_kind: str, model_axis: str):
    """Shared body of both protection layouts: decode the (j, kk) plane tile
    into the ``w_ref`` strip (metadata stage, then ``decode_rows``-row
    chunks), then accumulate ``x @ strip`` into the output block. The decode
    depends only on the (j, kk) tile coordinates (plus SMEM scalars), never
    on the output-row index — the invariant the hoist relies on."""
    if protect == "one4n":
        cw_ref, o_ref, w_ref, meta_ref = refs
    else:
        exp_ref, signw_ref, o_ref, w_ref, meta_ref = refs
    j = pl.program_id(0)
    i = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def decode():
        fault = _Fault(scalars_ref, model_kind=model_kind,
                       model_axis=model_axis) if dynamic else None
        if protect == "one4n":
            meta_ref[...] = _meta_one4n(
                fault, cw_ref[...], j, kk, codec=codec, n_group=n_group,
                store_g=store_g, block_n=block_n, block_k=block_k)
        else:
            meta_ref[...] = _meta_raw(
                fault, exp_ref[...], j, kk, n_group=n_group,
                exp_bits=exp_bits, store_j=store_j, block_n=block_n,
                block_k=block_k)
        base = kk * block_k if hoist else 0
        eb = exp_bits

        def at(c, rows):
            return pl.ds(pl.multiple_of(c * rows, rows), rows)

        def chunk(c, carry):
            r0 = c * decode_rows
            v = _expand_rows(meta_ref[at(c, decode_rows // n_group), :],
                             n_group)
            if protect == "one4n":
                i_n = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) \
                    % n_group
                e_full, sign = v & ((1 << eb) - 1), (v >> (i_n + eb)) & 1
            else:
                e_full = v
                sign = _sign_rows_raw(
                    fault, signw_ref[at(c, decode_rows // 32), :], j,
                    kk * (block_k // 32) + c * (decode_rows // 32),
                    store_k=store_k, store_j=store_j, block_n=block_n)
            man = _mantissa_rows(
                fault, man_ref[at(c, decode_rows), :], j, kk * block_k + r0,
                man_bits=man_bits, store_j=store_j, block_n=block_n)
            w_ref[pl.ds(pl.multiple_of(base + r0, decode_rows), decode_rows),
                  :] = _reconstruct_f32(
                sign, e_full, man, man_bits=man_bits, exp_bits=exp_bits,
                bias=bias)
            return carry

        jax.lax.fori_loop(0, block_k // decode_rows, chunk, 0)

    if hoist:
        pl.when(i == 0)(decode)
        w_tile = w_ref[pl.ds(kk * block_k, block_k), :]
    else:
        decode()
        w_tile = w_ref[...]
    o_ref[...] += _matmul(x_ref[...], w_tile)


# Scoped VMEM a call may use, of the 128 MiB of a v5e core (Mosaic grants 16
# MiB unless told). `ops.resolve_tiles` keeps the modelled footprint under
# `ops.VMEM_BUDGET`; the rest is headroom for compiler-internal scratch.
VMEM_LIMIT = 48 * 2 ** 20


def decode_rows_for(block_k: int) -> int:
    """Rows per decode chunk: 256 (8 K-packed sign words, 32 one4n exponent
    blocks — whole (8, 128) tiles for every sliced window) when it divides
    the tile, else the whole tile."""
    return 256 if block_k % 256 == 0 else block_k


def _call(kernel_kw, planes, plane_specs, x, man, scalars, *, m, n, k,
          block_m, block_n, block_k, hoist, interpret):
    """(N/bn, M/bm, K/bk) grid — j outermost so each j-column's decoded strip
    is built once and revisited by every i — with the pipelined windows, the
    strip and metadata scratch, and the Mosaic compiler params."""
    n_group = kernel_kw["n_group"]
    decode_rows = decode_rows_for(block_k)
    kernel = functools.partial(_cim_read_kernel, block_n=block_n,
                               block_k=block_k, decode_rows=decode_rows,
                               hoist=hoist, **kernel_kw)
    scratch = [pltpu.VMEM((k if hoist else block_k, block_n), jnp.float32),
               pltpu.VMEM((block_k // n_group, block_n), jnp.int32)]
    # i ("arbitrary") keeps the M-revisits of one j-column sequential on a
    # core, so the strip decoded at i == 0 is still live for i > 0.
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n, m // block_m, k // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_k), lambda j, i, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda j, i, kk: (kk, j)),
            *plane_specs,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="cim_read",
    )(scalars, x, man, *planes)


def cim_read_matmul_one4n(x, man, cw, scalars, *, codec: One4NRowCodec,
                          n_group: int, man_bits: int, exp_bits: int,
                          bias: int, store_g: int, store_j: int,
                          block_m: int, block_n: int, block_k: int,
                          dynamic: bool, hoist: bool = False,
                          interpret: bool = True, model_kind: str = "iid",
                          model_axis: str = "row"):
    """x [M, K] float; man uint16 [K, N]; cw uint32 [K//n, N//rw, S, W];
    scalars uint32 [9] (see SCALAR_*) -> [M, N] f32, decode fused into the
    matmul. ``hoist=True`` decodes each (j, kk) plane tile once into VMEM
    scratch and reuses the strip across the M-row revisits. ``model_kind`` /
    ``model_axis`` statically select the fault-model threshold compilation
    (its traced parameters ride in SCALAR_M_THR/SCALAR_M_LEN).

    The codeword plane enters the kernel as its free C-order reshape
    ``[K//n, N//rw * S * W]``: a ``[bk/n, bn/rw * S * W]`` window is
    lane-dense, where the store's trailing ``(S, W)`` dims would each pad to
    a full (8, 128) VMEM tile."""
    m, k = x.shape
    k2, n = man.shape
    rw = codec.row_weights
    assert k == k2 and cw.shape[:2] == (k // n_group, n // rw)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % n_group == 0 and block_n % rw == 0
    sw = codec.n_segments * codec.codeword_words
    spec = pl.BlockSpec((block_k // n_group, block_n // rw * sw),
                        lambda j, i, kk: (kk, j))
    kw = dict(protect="one4n", codec=codec, n_group=n_group,
              man_bits=man_bits, exp_bits=exp_bits, bias=bias, store_k=0,
              store_g=store_g, store_j=store_j, dynamic=dynamic,
              model_kind=model_kind, model_axis=model_axis)
    return _call(kw, [cw.reshape(cw.shape[0], -1)], [spec], x, man, scalars,
                 m=m, n=n, k=k, block_m=block_m, block_n=block_n,
                 block_k=block_k, hoist=hoist, interpret=interpret)


def cim_read_matmul_raw(x, man, exp, signw, scalars, *, n_group: int,
                        man_bits: int, exp_bits: int, bias: int, store_k: int,
                        store_j: int, block_m: int, block_n: int,
                        block_k: int, dynamic: bool, hoist: bool = False,
                        interpret: bool = True, model_kind: str = "iid",
                        model_axis: str = "row"):
    """protect='none' variant: exp uint8 [K//n, N], signw uint32 [K//32, N];
    scalars uint32 [9] (see SCALAR_*)."""
    m, k = x.shape
    k2, n = man.shape
    assert k == k2 and exp.shape == (k // n_group, n)
    assert signw.shape == (k // 32, n)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % n_group == 0 and block_k % 32 == 0
    specs = [pl.BlockSpec((block_k // n_group, block_n),
                          lambda j, i, kk: (kk, j)),
             pl.BlockSpec((block_k // 32, block_n), lambda j, i, kk: (kk, j))]
    kw = dict(protect="none", codec=None, n_group=n_group, man_bits=man_bits,
              exp_bits=exp_bits, bias=bias, store_k=store_k, store_g=0,
              store_j=store_j, dynamic=dynamic, model_kind=model_kind,
              model_axis=model_axis)
    return _call(kw, [exp, signw], specs, x, man, scalars, m=m, n=n, k=k,
                 block_m=block_m, block_n=block_n, block_k=block_k,
                 hoist=hoist, interpret=interpret)
