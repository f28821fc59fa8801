"""Real-width compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached. These tests lower and compile, with no
chip, the kernels every served token and every sweep cell runs through, at
the published olmo-1b unembed width (K=2048, J=50304): what Mosaic refuses
here (layouts, casts, VMEM) would otherwise only show on the chip. Nothing
runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file. For the same reason the file runs in one worker:
``pytest -n N --dist loadfile`` keeps a file's tests together. The tests
skip only where the TPU compiler library is not installed (a CPU-only jax);
any other failure to describe the chip fails them.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import cim
from repro.kernels.cim_read import ops as cr_ops
from repro.kernels.cim_read.kernel import (cim_read_matmul_one4n,
                                           cim_read_matmul_raw)
from repro.kernels.fault_inject.kernel import (fault_inject_batched_pallas,
                                               fault_inject_pallas)

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    if (importlib.util.find_spec("libtpu") is None
            and not os.environ.get("TPU_LIBRARY_PATH")):
        pytest.skip("the TPU compiler library (libtpu) is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # compiles for a described chip cannot be read back without one: keep
    # them out of any persistent cache this process was given
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _unembed_store(protect):
    cfg = get_config("olmo-1b")
    return jax.eval_shape(
        lambda w: cim.pack(w, cim.CIMConfig(protect=protect)),
        jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size), jnp.float32))


def _compile(fn, args, sharding):
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def _read_args(store, m):
    """Operands of one read, padded to the tile grid as
    ``cim_linear_store`` pads them, plus the kernel's static arguments."""
    bm, bn, bk, hoist = cr_ops.resolve_tiles(store, m)
    cfg = store.cfg
    k_pad, j_pad = store.man.shape
    assert cr_ops.vmem_bytes(cfg, bm, bn, bk, -(-k_pad // bk) * bk,
                             hoist) <= cr_ops.VMEM_BUDGET
    k_t, j_t = -(-k_pad // bk) * bk, -(-j_pad // bn) * bn
    m_t = -(-m // bm) * bm
    n, rw = cfg.n_group, cfg.row_weights
    x = jax.ShapeDtypeStruct((m_t, k_t), jnp.float32)
    man = jax.ShapeDtypeStruct((k_t, j_t), jnp.uint16)
    scalars = jax.ShapeDtypeStruct((9,), jnp.uint32)
    kw = dict(n_group=n, man_bits=cfg.fmt.man_bits,
              exp_bits=cfg.fmt.exp_bits, bias=cfg.fmt.bias, block_m=bm,
              block_n=bn, block_k=bk, hoist=hoist, interpret=False)
    if cfg.protect == "one4n":
        codec = cfg.codec
        cw = jax.ShapeDtypeStruct((k_t // n, j_t // rw, codec.n_segments,
                                   codec.codeword_words), jnp.uint32)
        kw.update(codec=codec, store_g=j_pad // rw, store_j=j_pad)
        return (x, man, cw, scalars), kw
    exp = jax.ShapeDtypeStruct((k_t // n, j_t), jnp.uint8)
    sign = jax.ShapeDtypeStruct((k_t // 32, j_t), jnp.uint32)
    kw.update(store_k=k_pad, store_j=j_pad)
    return (x, man, exp, sign, scalars), kw


@pytest.mark.parametrize("m", [8, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_one4n_read_compiles(one_chip, m, dynamic):
    args, kw = _read_args(_unembed_store("one4n"), m)
    _compile(lambda *a: cim_read_matmul_one4n(*a, dynamic=dynamic, **kw),
             args, one_chip)


@pytest.mark.parametrize("model", [("burst", "col"), ("correlated", "row")],
                         ids=["burst", "correlated"])
def test_one4n_read_fault_models_compile(one_chip, model):
    args, kw = _read_args(_unembed_store("one4n"), 8)
    kind, axis = model
    _compile(lambda *a: cim_read_matmul_one4n(
        *a, dynamic=True, model_kind=kind, model_axis=axis, **kw),
        args, one_chip)


def test_one4n_read_hoisted_compiles(one_chip):
    args, kw = _read_args(_unembed_store("one4n"), 256)
    assert kw["hoist"]
    _compile(lambda *a: cim_read_matmul_one4n(*a, dynamic=True, **kw),
             args, one_chip)


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_raw_read_compiles(one_chip, dynamic):
    args, kw = _read_args(_unembed_store("none"), 8)
    _compile(lambda *a: cim_read_matmul_raw(*a, dynamic=dynamic, **kw),
             args, one_chip)


def test_fault_inject_compiles(one_chip):
    cfg = get_config("olmo-1b")
    bits = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size), jnp.uint16)
    _compile(lambda b: fault_inject_pallas(b, seed=3, ber=1e-4,
                                           positions=tuple(range(10)),
                                           interpret=False),
             [bits], one_chip)


@pytest.mark.parametrize("model", [("iid", "row"), ("burst", "col")],
                         ids=["iid", "burst"])
def test_fault_inject_batched_compiles(one_chip, model):
    cfg = get_config("olmo-1b")
    bits = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size), jnp.uint16)
    seeds = jax.ShapeDtypeStruct((4,), jnp.uint32)
    thr = jax.ShapeDtypeStruct((), jnp.uint32)
    kind, axis = model
    _compile(lambda b, s, t: fault_inject_batched_pallas(
        b, s, t, positions=tuple(range(10)), interpret=False, m_thr=1 << 30,
        m_len=8, model_kind=kind, model_axis=axis), [bits, seeds, thr],
        one_chip)
