"""jit'd public wrapper for the fault-injection kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bitops import FP16, FloatFormat
from repro.kernels import resolve_interpret
from repro.kernels.fault_inject.kernel import (fault_inject_batched_pallas,
                                               fault_inject_pallas)
from repro.kernels.fault_inject.ref import fault_inject_ref  # noqa: F401


def ber_to_threshold(ber) -> jnp.ndarray:
    """Traced BER -> uint32 Bernoulli threshold (flip iff hash < threshold).

    Matches the static kernel's ``round(ber * 2^32)`` up to float32 rounding;
    saturates to 0xFFFFFFFF (flip always) near ber=1 because float32 cannot
    represent 2^32 - 1."""
    t = jnp.round(jnp.asarray(ber, jnp.float32) * jnp.float32(2.0 ** 32))
    return jnp.where(t >= jnp.float32(4294967040.0), jnp.uint32(0xFFFFFFFF),
                     t.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("seed", "ber", "positions",
                                             "interpret"))
def fault_inject_bits(bits, *, seed: int, ber: float, positions,
                      interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    return fault_inject_pallas(bits, seed=seed, ber=ber,
                               positions=tuple(positions), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("positions", "interpret",
                                             "model", "col_div"))
def fault_inject_bits_batched(bits, seeds, threshold, *, positions,
                              interpret: bool | None = None, model=None,
                              col_div: int = 1):
    """Trial-batched injection: bits [R, C] -> [T, R, C], one compile total.

    ``seeds`` (uint32 [T]) and ``threshold`` (uint32 scalar, see
    :func:`ber_to_threshold`) are traced — sweeping BER or trial seeds does
    NOT retrigger compilation, which is what lets the sweep engine evaluate a
    whole (BER x trial) plane per arm.

    ``model`` is an optional :class:`repro.core.faultmodels.FaultProcess`
    (hashable, static): burst/correlated compile to per-element thresholds
    inside the kernel (parameters ride in SMEM, so sweeping rate/length does
    not recompile either); drift pre-scales ``threshold`` by its tick.
    ``model=None`` / i.i.d. is bit-identical to the legacy stream."""
    interpret = resolve_interpret(interpret)
    from repro.core import faultmodels as fm
    threshold = fm.compiled_threshold(model, threshold)
    m_thr, m_len = fm.model_scalars(model)
    kind = model.kind if model is not None else "iid"
    axis = model.axis if model is not None else "row"
    return fault_inject_batched_pallas(bits, seeds, threshold,
                                       positions=tuple(positions),
                                       interpret=interpret,
                                       m_thr=m_thr, m_len=m_len,
                                       model_kind=kind, model_axis=axis,
                                       col_div=col_div)


def fault_inject_fp16(w, *, seed: int, ber: float, field: str = "full",
                      fmt: FloatFormat = FP16, interpret: bool | None = None):
    """Field-targeted injection on an fp16-grid float tensor (kernel path)."""
    from repro.core import bitops
    shape = w.shape
    bits = bitops.to_bits(w.reshape(-1, shape[-1]), fmt)
    positions = tuple(int(p) for p in fmt.field_bit_positions(field))
    out = fault_inject_bits(bits, seed=seed, ber=ber, positions=positions,
                            interpret=interpret)
    return jnp.asarray(bitops.from_bits(out, fmt), w.dtype).reshape(shape)
