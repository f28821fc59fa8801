"""Pallas TPU kernel: block-shared-exponent (One4N / BFP) matmul.

TPU-native realization of the Unicorn-CIM macro (DESIGN.md §2): weights live
in SRAM-image form — a sign+mantissa plane (uint16: bit15 = sign, bits 0..9 =
fp16 mantissa) plus ONE shared biased exponent per ``n_group`` rows (the
input-channel direction, exactly the paper's Fig. 3 ① grouping). The kernel
streams HBM->VMEM tiles, dequantizes in VMEM (exponent applied as an exact
power-of-two scale) and feeds the MXU with fp32 accumulation:

    mantissa multiplication array  -> MXU dot on the dequantized tile
    exponent summation/alignment   -> folded into the pow2 scale (exact)
    sign processing unit (XOR)     -> sign factor in the dequant

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") with output revisiting —
the [bm, bn] fp32 accumulator stays in VMEM across the K loop.

Block constraints: bm/bn multiples of 128 (MXU-aligned), bk a multiple of
``n_group`` so each K tile covers whole exponent groups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_tile(man, exp, n_group: int):
    """man uint16 [bk, bn] (sign|mantissa), exp uint8 [bk//n_group, bn] -> f32."""
    sign = jnp.where((man >> 15) == 1, -1.0, 1.0).astype(jnp.float32)
    frac = 1.0 + (man & 0x3FF).astype(jnp.float32) * (1.0 / 1024.0)
    scale = jnp.exp2(exp.astype(jnp.float32) - 15.0)     # [bk/n, bn]
    bk, bn = man.shape
    scale_full = jnp.broadcast_to(scale[:, None, :], (bk // n_group, n_group, bn))
    scale_full = scale_full.reshape(bk, bn)
    return sign * frac * scale_full


def _bfp_matmul_kernel(x_ref, man_ref, exp_ref, o_ref, *, n_group: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _dequant_tile(man_ref[...], exp_ref[...], n_group)
    o_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)


def bfp_matmul_pallas(x, man, exp, *, n_group: int = 8,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 512, interpret: bool = True):
    """x [M, K] float; man uint16 [K, N]; exp uint8 [K//n_group, N] -> [M, N] f32."""
    m, k = x.shape
    k2, n = man.shape
    assert k == k2 and exp.shape == (k // n_group, n)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % n_group == 0

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_bfp_matmul_kernel, n_group=n_group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // n_group, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, man, exp)
