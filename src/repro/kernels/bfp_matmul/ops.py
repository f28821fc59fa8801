"""jit'd public wrapper for the BFP matmul kernel.

``interpret`` (see :func:`repro.kernels.resolve_interpret`) runs the kernel
body on the CPU off-TPU; on a TPU the kernel always runs compiled by Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.bfp_matmul.kernel import bfp_matmul_pallas
from repro.kernels.bfp_matmul.ref import bfp_matmul_ref, dequant_ref, pack_bfp  # noqa: F401


def _round_up(x: int, m: int) -> int:
    return math.ceil(x / m) * m


@functools.partial(jax.jit, static_argnames=("n_group", "block_m", "block_n",
                                             "block_k", "interpret"))
def bfp_matmul(x, man, exp, *, n_group: int = 8, block_m: int = 128,
               block_n: int = 128, block_k: int = 512,
               interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    return bfp_matmul_pallas(x, man, exp, n_group=n_group, block_m=block_m,
                             block_n=block_n, block_k=block_k,
                             interpret=interpret)


def cim_linear(x, man, exp, *, n_group: int = 8, use_kernel: bool = True,
               with_info: bool = False):
    """Linear layer consuming the BFP weight planes directly (no fp16
    rematerialization in HBM) — the serving-path integration point.

    Arbitrary M/K/N are zero-padded up to tile boundaries (padded activations
    are zero, so the result is unchanged) instead of silently falling back to
    the dequantized reference; the kernel therefore runs whenever
    ``use_kernel`` is set. ``with_info=True`` additionally returns
    ``{'used_kernel': bool}`` so callers/tests can assert the kernel path.
    """
    b_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n_out = man.shape[1]
    if use_kernel:
        m, k = x2.shape
        bm = min(128, _round_up(m, 8))
        bk = max(n_group, (min(512, k) // n_group) * n_group)
        bn = 128
        m_t, k_t, n_t = _round_up(m, bm), _round_up(k, bk), _round_up(n_out, bn)
        xp = jnp.pad(x2, ((0, m_t - m), (0, k_t - k)))
        manp = jnp.pad(man, ((0, k_t - k), (0, n_t - n_out)))
        expp = jnp.pad(exp, ((0, k_t // n_group - exp.shape[0]),
                             (0, n_t - n_out)))
        out = bfp_matmul(xp, manp, expp, n_group=n_group, block_m=bm,
                         block_n=bn, block_k=bk)
        out = out[:m, :n_out].reshape(*b_shape, n_out)
        return (out, {"used_kernel": True}) if with_info else out
    out = (x2 @ dequant_ref(man, exp, n_group)).reshape(*b_shape, n_out)
    return (out, {"used_kernel": False}) if with_info else out
