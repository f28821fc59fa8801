"""Pallas TPU kernel: tiled bit-flip fault injection into a stored-bit plane.

Emulates soft errors in the CIM macro's SRAM cells (paper Fig. 1a) directly on
the packed uint16 weight representation. Randomness is a counter-based hash
PRNG (murmur3 finalizer) keyed by (seed, absolute element index, bit
position) — pure integer ops, so the kernel (a) lowers on TPU without the
Mosaic PRNG primitives, (b) runs bit-exactly in interpret mode on CPU, and
(c) produces tiling-independent faults (the same (seed, element, bit) always
flips the same way regardless of block shape). Counter streams are strided
by 32 bits per element so positions 0..31 are independent across elements
(covers every format up to fp32).

Per bit position p in the target field: flip iff hash(...) < ber * 2^32,
i.e. i.i.d. Bernoulli(ber) per stored bit, matching `repro.core.fault`.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def hash_u32(z: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (wrapping uint32 arithmetic)."""
    z = z.astype(jnp.uint32)
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> 13)
    z = z * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> 16)
    return z


def _fault_kernel(bits_ref, o_ref, *, seed: int, threshold: int,
                  positions: Tuple[int, ...], n_cols: int,
                  block_r: int, block_c: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 0) \
        + jnp.uint32(i * block_r)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 1) \
        + jnp.uint32(j * block_c)
    elem = rows * jnp.uint32(n_cols) + cols

    mask = jnp.zeros((block_r, block_c), jnp.uint32)
    for p in positions:
        # distinct stream per (seed, element, bit position)
        z = elem * jnp.uint32(32) + jnp.uint32(p)
        z = z ^ (jnp.uint32(seed) * jnp.uint32(0x9E3779B9))
        r = hash_u32(z)
        flip = (r < jnp.uint32(threshold)).astype(jnp.uint32)
        mask = mask | (flip << p)

    o_ref[...] = bits_ref[...] ^ mask.astype(bits_ref.dtype)


def _pick_block(dim: int, preferred: int, quantum: int) -> int:
    """Largest divisor of ``dim`` that is a multiple of ``quantum`` and not
    above ``preferred``; the whole ``dim`` when there is none (Mosaic takes a
    block dim that is either tile-aligned or the full array dim)."""
    for d in range(min(preferred, dim) // quantum * quantum, 0, -quantum):
        if dim % d == 0:
            return d
    return dim


def _pick_blocks(bits, block_r: int, block_c: int) -> Tuple[int, int]:
    """Row/column block sizes in whole VMEM tiles of the plane's dtype:
    128 lanes by 8 sublanes of 32-bit words (16 rows of 16-bit, 32 of
    8-bit words)."""
    rows = 8 * (4 // bits.dtype.itemsize)
    return (_pick_block(bits.shape[0], block_r, rows),
            _pick_block(bits.shape[1], block_c, 128))


# The counter is a uint32 striding 32 per element, so streams repeat after
# 2^27 elements; beyond that, element pairs 2^27 apart would receive
# identical (correlated) faults. Refuse instead of silently biasing stats.
MAX_COUNTER_ELEMENTS = 2 ** 27


def _check_counter_space(r: int, c: int) -> None:
    if r * c > MAX_COUNTER_ELEMENTS:
        raise ValueError(
            f"fault_inject counter space exhausted: {r}x{c} = {r * c} elements "
            f"> 2^27; split the leaf into chunks of <= {MAX_COUNTER_ELEMENTS} "
            f"elements (each with a distinct seed) to keep faults i.i.d.")


def fault_inject_pallas(bits: jnp.ndarray, *, seed: int, ber: float,
                        positions: Sequence[int], block_r: int = 256,
                        block_c: int = 256, interpret: bool = True):
    """bits uint16 [R, C] -> bits with field positions flipped at rate ber."""
    r, c = bits.shape
    _check_counter_space(r, c)
    block_r, block_c = _pick_blocks(bits, block_r, block_c)
    assert r % block_r == 0 and c % block_c == 0
    threshold = min(int(round(ber * 2 ** 32)), 2 ** 32 - 1)
    grid = (r // block_r, c // block_c)
    return pl.pallas_call(
        functools.partial(_fault_kernel, seed=seed, threshold=threshold,
                          positions=tuple(positions), n_cols=c,
                          block_r=block_r, block_c=block_c),
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(bits.shape, bits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="fault_inject",
    )(bits)


# ---------------------------------------------------------------------------
# Trial-batched variant with *traced* seeds/threshold (the sweep-engine path).
#
# The static kernel above bakes (seed, ber) into the compiled artifact — one
# compile per sweep cell. Here both live in an SMEM scalar block instead:
# scalars[0] is the uint32 Bernoulli threshold (round(ber * 2^32)),
# scalars[1:3] are the fault-model parameters (m_thr, m_len — zeros for
# i.i.d.) and scalars[3 + t] is trial t's seed, so a whole (trial × element
# × bit) fault plane evaluates under ONE compilation, with BER, model
# parameters and trial count swept as ordinary device values. The model
# *kind*/*axis* are static (they pick the compiled threshold code path, like
# ``dynamic`` in the cim_read kernel). The grid grows a leading trial
# dimension; every (seed, element, bit) stream is identical to the static
# kernel's, so trial t of the batched call is bit-exact with a static call
# at seed = seeds[t] (for the default i.i.d. model), and a non-i.i.d. model
# only ever *lowers* the per-element threshold (subset-of-iid contract of
# ``repro.core.faultmodels``).
# ---------------------------------------------------------------------------

SCALAR_B_THR = 0      # uint32 Bernoulli threshold round(ber * 2^32)
SCALAR_B_M_THR = 1    # fault model: burst hit threshold / correlated Q16
SCALAR_B_M_LEN = 2    # fault model: burst run length / correlated period
SCALAR_B_SEEDS = 3    # trial seeds start here


def _fault_kernel_batched(scalars_ref, bits_ref, o_ref, *,
                          positions: Tuple[int, ...], n_cols: int,
                          block_r: int, block_c: int,
                          model_kind: str = "iid", model_axis: str = "row",
                          col_div: int = 1):
    t = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    threshold = scalars_ref[SCALAR_B_THR]
    seed = scalars_ref[SCALAR_B_SEEDS + t]
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 0) \
        + jnp.uint32(i * block_r)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 1) \
        + jnp.uint32(j * block_c)
    elem = rows * jnp.uint32(n_cols) + cols

    if model_kind not in ("iid", "drift"):
        # lazy import: repro.core.faultmodels imports hash_u32 from here
        from repro.core.faultmodels import scale_elem_thresholds
        threshold = scale_elem_thresholds(
            elem, threshold, seed, kind=model_kind, axis=model_axis,
            m_thr=scalars_ref[SCALAR_B_M_THR],
            m_len=scalars_ref[SCALAR_B_M_LEN],
            width=n_cols, col_div=col_div)

    mask = jnp.zeros((block_r, block_c), jnp.uint32)
    for p in positions:
        z = elem * jnp.uint32(32) + jnp.uint32(p)
        z = z ^ (seed * jnp.uint32(0x9E3779B9))
        r = hash_u32(z)
        flip = (r < threshold).astype(jnp.uint32)
        mask = mask | (flip << p)

    o_ref[0] = bits_ref[...] ^ mask.astype(bits_ref.dtype)


def fault_inject_batched_pallas(bits: jnp.ndarray, seeds: jnp.ndarray,
                                threshold: jnp.ndarray, *,
                                positions: Sequence[int], block_r: int = 256,
                                block_c: int = 256, interpret: bool = True,
                                m_thr=0, m_len=0, model_kind: str = "iid",
                                model_axis: str = "row", col_div: int = 1):
    """bits uint [R, C], seeds uint32 [T] -> [T, R, C] faulted copies.

    ``seeds``, ``threshold`` and the fault-model parameters ``m_thr``/
    ``m_len`` are traced operands (SMEM scalars): one compile covers every
    (BER, model parameter, trial) the caller sweeps over. ``model_kind``/
    ``model_axis`` are static; ``col_div`` gives the macro-column unit width
    of the plane (words per column group for packed codeword planes).
    """
    r, c = bits.shape
    t = seeds.shape[0]
    _check_counter_space(r, c)
    block_r, block_c = _pick_blocks(bits, block_r, block_c)
    scalars = jnp.concatenate([
        jnp.asarray(threshold, jnp.uint32).reshape(1),
        jnp.asarray(m_thr, jnp.uint32).reshape(1),
        jnp.asarray(m_len, jnp.uint32).reshape(1),
        seeds.astype(jnp.uint32)])
    grid = (t, r // block_r, c // block_c)
    return pl.pallas_call(
        functools.partial(_fault_kernel_batched, positions=tuple(positions),
                          n_cols=c, block_r=block_r, block_c=block_c,
                          model_kind=model_kind, model_axis=model_axis,
                          col_div=col_div),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_r, block_c), lambda t, i, j: (i, j))],
        out_specs=pl.BlockSpec((1, block_r, block_c), lambda t, i, j: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((t, r, c), bits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="fault_inject_trials",
    )(scalars, bits)
