"""Pallas TPU kernels of the CIM read path (fused decode-on-read matmul,
counter-PRNG fault injection, BFP matmul)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode runs a kernel body on the CPU only: ``None``
    picks Mosaic on a TPU backend and interpret mode elsewhere, and an
    explicit ``interpret=True`` on a TPU is refused rather than run as a
    slow emulation that would hide the device."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the kernels run "
                         "compiled by Mosaic there")
    return bool(interpret)
