#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process sees.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration ``bench/configs/<config>.json``, its
traffic mix ``bench/traffic/<traffic>.json``, its correctness limits
``bench/limits/<cell>.json`` and one reader per metric
``bench/metrics/<metric>.py``.

A run builds the engine from the seed, warms every program the window
drives, serves the traffic for ``--seconds``, then frees the engine and
compares a sample of the served requests with the plain reference. The
last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last); the numbers compared, each beside its
limit, are also the last lines of stderr. With no accelerator, or fewer
chips than the cell asks for, it prints no result and exits 3.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time


def _process_start() -> float:
    """Process start on the ``time.perf_counter`` clock (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_START = _process_start()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str):
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    conf = load_json(ROOT, conf_entry["file"])
    mix = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    limits = load_json(BENCH, "limits", name + ".json")
    return spec, cell, conf, mix, limits


def metrics_for(spec: dict, cell: dict, trace: bool) -> list:
    """The cell's metrics: end-to-end ones untraced, per-layer ones traced."""
    name = cell["name"]

    def applies(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}

    def wanted(m):
        if "workloads" in m:
            return name in m["workloads"]
        return m["moves"] in mine
    return [m for m in spec["per_layer"] if wanted(m)]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        log(f"needs {chips} accelerator chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)")
        sys.exit(3)
    return devs


def run_cell(cell, conf, mix, limits, seed, seconds, trace, devs, peak,
             control=False):
    import jax
    from yardstick import check, system, traffic, window
    from yardstick import trace as tr

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append((time.perf_counter(), secs))
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    sysm = system.build(conf, mix, seed)
    chunk = mix["serving"]["chunk"]
    system.warm(sysm, chunk, conf["model"]["vocab_size"])
    gen = traffic.Generator(mix, conf["model"]["vocab_size"], seed, seconds)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    t_window = time.perf_counter()
    out = window.run(sysm.engine, gen, seconds, spans=trace)
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window + out.window[0] - T_START
    w_lo, w_hi = t_window + out.window[0], t_window + out.window[1]
    n_compiles = sum(1 for t, _ in compiles if w_lo <= t < w_hi)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell["chips"]])
    e2e = window.metrics(out)
    late = sorted(out.lateness)
    log(f"window {seconds} s: {out.steps} engine steps, {e2e['tokens']} "
        f"tokens, {e2e['served']} requests served, {e2e['due']} due, "
        f"{e2e['failed']} without a first token; compiles inside the "
        f"window {n_compiles}")
    if late:
        log(f"generator lateness s: median {window.nearest_rank(late, 50):.4f}"
            f" p99 {window.nearest_rank(late, 99):.4f} max {late[-1]:.4f}")
    log(f"peak device memory {mem} bytes after the window")

    ctx = Context(outcome=out, e2e=e2e, setup_s=setup_s, conf=conf, mix=mix,
                  cell=cell, events=None, trace_window=None,
                  peak=peak)
    extra = {}
    if trace:
        events = tr.load(TRACE_DIR)
        ctx.events = events
        ctx.trace_window = tr.window(events)
        lo, hi = ctx.trace_window
        extra = {"busy_s": tr.busy_s(events, lo, hi), "window_s": hi - lo}
        ctx.breakdown = {"device_ops": tr.top_ops(events, lo, hi),
                         "idle_gaps": tr.idle_gaps(events, lo, hi)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the reference runs on a freed device: only the source weights stay
    finished = out.finished
    system.release(sysm)
    gc.collect()
    ref = check.Reference(conf, sysm.params, sysm.dep_key, sysm.inject,
                          chunk, mix["serving"]["max_len"])
    rids = check.sample(finished, limits["sample"], seed)
    t0 = time.perf_counter()
    res = check.compare(ref, finished, rids, control=control)
    log(f"reference over {res['requests']} requests ({res['tokens']} served "
        f"tokens, rids {rids}) in {time.perf_counter() - t0:.1f} s")
    return ctx, e2e, mem, extra, res


def load_peak(kind: str) -> dict:
    peaks = load_json(BENCH, "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def checks_of(res: dict, limits: dict, control: bool = False) -> dict:
    """The numbers compared, each beside its limit. With ``control`` the
    gap is the control's (``check.compare(..., control=True)``): the
    reference in the program's place, one precision step below, which has
    to come out not correct."""
    gap = res["control_gap"] if control else res["gap"]
    return {"gap": {"value": gap, "limit": limits["gap"]},
            "ecc_mismatch": {"value": res["ecc_mismatch"],
                             "limit": limits["ecc_mismatch"]},
            "compared_tokens": {"value": res["tokens"],
                                "limit": limits["min_tokens"]}}


def is_correct(checks: dict) -> bool:
    return (checks["gap"]["value"] <= checks["gap"]["limit"]
            and checks["ecc_mismatch"]["value"]
            <= checks["ecc_mismatch"]["limit"]
            and checks["compared_tokens"]["value"]
            >= checks["compared_tokens"]["limit"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"no program under {ROOT}/src: run from a checkout")
        return 2
    spec, cell, conf, mix, limits = cell_spec(args.workload)
    from repro.launch import compile_cache
    cache = compile_cache.configure()
    devs = check_device(cell["chips"])
    peak = load_peak(devs[0].device_kind)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}; jax {jax.__version__}")
    ctx, e2e, mem, extra, res = run_cell(
        cell, conf, mix, limits, args.seed, args.seconds, bool(args.trace),
        devs, peak)
    chosen = metrics_for(spec, cell, bool(args.trace))
    metrics = {}
    for m in chosen:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = checks_of(res, limits)
    mode = mix["arrivals"]["mode"]
    result = {
        "correct": is_correct(checks),
        "attempted": e2e["due"] if mode == "poisson" else e2e["served"],
        "failed": e2e["failed"] if mode == "poisson" else 0,
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": int(mem),
                   **extra},
    }
    if args.trace:
        result["breakdown"] = ctx.breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
