#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limit, at the cell's
own size, on the accelerator. The benchmark's runs do not run this.

    python3 bench/tests/calibrate.py knee --workload <cell> \
        --rates 1.0,1.2,1.4 --seconds 30 --seed <n>
    python3 bench/tests/calibrate.py limits --workload <cell> \
        --seeds <a>,<b>,<c> --seconds 25

``knee``: one engine serves the cell's open-loop schedule at each offered
rate in turn (the same Poisson draw, compressed); per rate it prints the
requests still waiting for their first token at the window's close, the
tokens per second and the tails. The knee is the highest rate whose queue
does not grow over the window.

``limits``: per seed, one engine serves three windows at the cell's load:
sound, with every decode token moved to the next id, and with the decode
step returning its cache unchanged. The reference then reads each, and the
control (the reference in the program's place one precision step below)
reads the sound window's prompts and tokens. Each line gives the numbers
compared and whether the harness calls it correct.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402  (sets up the paths and the start time)


def _drain(eng):
    while eng.busy:
        eng.step()
    eng.results.clear()


def _build(cell, conf, mix, seed):
    from yardstick import system
    sysm = system.build(conf, mix, seed)
    system.warm(sysm, mix["serving"]["chunk"], conf["model"]["vocab_size"])
    return sysm


def knee(cell, conf, mix, args):
    from yardstick import traffic, window
    sysm = _build(cell, conf, mix, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        m = copy.deepcopy(mix)
        m["arrivals"]["rate"] = rate
        gen = traffic.Generator(m, conf["model"]["vocab_size"], args.seed,
                                args.seconds)
        out = window.run(sysm.engine, gen, args.seconds, drain_s=0.0)
        o, c = out.window
        waiting = sum(1 for r in out.records.values()
                      if r.due < c and not r.first <= c)
        e2e = window.metrics(out)
        print(json.dumps({
            "rate": rate, "due": e2e["due"], "waiting_at_close": waiting,
            "tok_s": e2e["tok_s"],
            "ttft_p90_s": window.nearest_rank(e2e["ttft"], 90),
            "itl_p95_ms": 1e3 * window.nearest_rank(e2e["itl"], 95)}),
            flush=True)
        _drain(sysm.engine)


def _broken(decode, fault):
    import jax.numpy as jnp

    def bad(params, caches, tokens, active, salts):
        logits, new = decode(params, caches, tokens, active, salts)
        if fault == "token":
            logits = jnp.roll(logits, 1, axis=-1)
        else:
            new = dict(caches, pos=new["pos"])
        return logits, new
    return bad


def limits(cell, conf, mix, lim, args):
    from yardstick import check, system, traffic, window
    for seed in (int(s) for s in args.seeds.split(",")):
        sysm = _build(cell, conf, mix, seed)
        eng = sysm.engine
        decode = eng._decode
        served = {}
        for fault in ("sound", "token", "state"):
            eng._decode = decode if fault == "sound" else _broken(decode,
                                                                  fault)
            gen = traffic.Generator(mix, conf["model"]["vocab_size"], seed,
                                    args.seconds)
            served[fault] = window.run(eng, gen, args.seconds).finished
            _drain(eng)
        system.release(sysm)
        gc.collect()
        ref = check.Reference(conf, sysm.params, sysm.dep_key, sysm.inject,
                              mix["serving"]["chunk"],
                              mix["serving"]["max_len"])
        for fault, fin in served.items():
            rids = check.sample(fin, lim["sample"], seed)
            res = check.compare(ref, fin, rids, control=fault == "sound")
            rows = [(fault, run.checks_of(res, lim))]
            if fault == "sound":
                rows.append(("control", run.checks_of(res, lim, True)))
            for name, checks in rows:
                print(json.dumps({
                    "seed": seed, "run": name,
                    "correct": run.is_correct(checks),
                    "gap_abs": res["gap_abs"],
                    **{k: v["value"] for k, v in checks.items()}}),
                    flush=True)
        del ref, sysm
        gc.collect()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", default="")
    args = ap.parse_args()
    spec, cell, conf, mix, lim = run.cell_spec(args.workload)
    from repro.launch import compile_cache
    compile_cache.configure()
    run.check_device(cell["chips"])
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.what == "knee":
        knee(cell, conf, mix, args)
    else:
        limits(cell, conf, mix, lim, args)


if __name__ == "__main__":
    main()
