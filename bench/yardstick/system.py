"""The system under test, built the way the serving launcher builds it for
``--engine --cim --serve-path fused``: weights on the device, the deployed
image (align, pack, static injection), the serving params with the row
cache or the dynamic-read runtime, and one ``Engine`` with ECC accounting.

This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from yardstick import weights


def model_config(conf: dict):
    from repro.configs import get_config
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    return dataclasses.replace(
        base, **{k: v for k, v in conf["model"].items() if k in fields})


@dataclasses.dataclass
class System:
    cfg: object
    params: dict          # source weights (the reference reads these)
    dep_key: object
    engine: object
    inject: str


def build(conf: dict, mix: dict, seed: int) -> System:
    from repro.launch import engine as engine_lib
    from repro.launch import serve
    from repro.models import lm

    cfg = model_config(conf)
    shapes = jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    params = weights.make(shapes, conf["init"], weights.key_of(seed, 1))
    dep_key = weights.key_of(seed, 2)
    d = conf["deployment"]
    s = mix["serving"]
    dep = serve.make_deployment(
        params, ber=d["ber"], protect=d["protect"], n_group=d["n_group"],
        index=d["index"], key=dep_key, inject_mode=s["inject"],
        field=d["field"])
    sparams = dep.serving_params(**serve.serving_kw(
        ber=d["ber"], key=dep_key, inject_mode=s["inject"], field=d["field"]))
    eng = engine_lib.Engine(cfg, sparams, n_slots=s["slots"],
                            max_len=s["max_len"], chunk=s["chunk"],
                            ecc_accounting=True)
    return System(cfg=cfg, params=params, dep_key=dep_key, engine=eng,
                  inject=s["inject"])


def request(rid: int, tokens, max_new: int, arrival: float = 0.0):
    from repro.launch.engine import Request
    return Request(rid=rid, tokens=tokens, max_new=max_new, arrival=arrival)


def warm(system: System, chunk: int, vocab: int) -> None:
    """Compile and run every program the window drives, once: a prompt of
    one full chunk plus a ragged tail (the prefill shape), two decode steps,
    and the ECC accountants of both reads. The warm request's rid lies
    outside the traffic's range."""
    eng = system.engine
    toks = (np.arange(chunk + 3) * 7919 % vocab).astype(np.int32)
    eng.submit(request(2 ** 30, toks, 3), now=0.0)
    while eng.busy:
        eng.step(now=float("inf"))
    eng.results.clear()


def release(system: System) -> None:
    """Free the program's device state, keeping the source weights."""
    eng = system.engine
    eng.params = eng.caches = None
    eng._ecc_fns = []
    system.engine = None
