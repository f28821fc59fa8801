"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing never touches jax
device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else in the repo sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the repo shards through GSPMD
    (``with_sharding_constraint``, ``shard_map``), which needs Auto axes —
    current jax defaults ``make_mesh`` to Explicit ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return auto_mesh((n // model_axis, model_axis), ("data", "model"))


def make_trial_mesh(n_devices: int = 0):
    """1-D mesh over the Monte-Carlo trial axis (characterization sweeps).

    Fault-injection trials are embarrassingly parallel, so the sweep engine
    shards its trial batch across every available device; a single-device
    mesh degenerates to fully-replicated execution at zero cost.
    """
    n = n_devices or len(jax.devices())
    return auto_mesh((n,), ("trial",))


def make_sweep_mesh(model_axis: int = 1, n_devices: int = 0):
    """2-D ``("trial", "model")`` mesh: Monte-Carlo trials x macro columns.

    One Fig. 6 arm then spans the whole mesh — the sweep engine splits its
    trial batch over "trial" while each CIM deployment's packed planes are
    column-sharded over "model" (``cim.shard_store``), i.e. every trial's
    inject+decode runs across ``model_axis`` emulated macro column groups.
    """
    n = n_devices or len(jax.devices())
    assert n % model_axis == 0
    return auto_mesh((n // model_axis, model_axis), ("trial", "model"))
