"""CPU tests of the readers of the serving engine's own spans
(``engine.<name>``, reduced by ``yardstick.program``) on a hand-built trace.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_program_spans.py
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402
from yardstick import layers  # noqa: E402
from yardstick import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
NEW = ("idle_in_step.chat", "ecc_charge_ms_per_step.chat", "logits_copy_ms.chat", "admit_ms.chat",
       "queue_wait_p90_ms.chat", "slots_per_step.chat")


def ev(plane, line, name, t0, t1, **stats):
    # a live trace's arguments come back as strings (``trace.load``)
    return tr.Event(plane, line, name, t0, t1,
                    tuple((k, str(v)) for k, v in stats.items()))


def device_and_bench():
    """Window [10, 20] s; device ops at [9, 11], [12, 13], [12.5, 14],
    [19, 21]: idle [11, 12] and [14, 19], 60 % of the window."""
    return [
        ev(HOST, "python", "bench.window", 10.0, 20.0),
        ev(HOST, "python", "bench.step", 10.5, 14.5),
        ev(DEV, tr.OPS, "fusion.1", 9.0, 11.0),
        ev(DEV, tr.OPS, "fusion.2", 12.0, 13.0),
        ev(DEV, tr.OPS, "fusion.1", 12.5, 14.0),
        ev(DEV, tr.OPS, "fusion.3", 19.0, 21.0),
    ]


def engine_spans():
    """Steps A [10.5, 14.5] (an admission, then a decode of 3 slots), B
    [15, 18] (an admission), C [18.5, 18.6] (nothing to do), D [19.5,
    20.5] (a decode of 1 slot, past the window's end), and one step before
    the window that the readers leave out."""
    H = "python"
    return [
        ev(HOST, H, "engine.step", 8.0, 9.5),
        ev(HOST, H, "engine.admit", 8.2, 9.0, rid=1, queue_ms=999.0),
        ev(HOST, H, "engine.decode", 9.1, 9.2, active=8),
        ev(HOST, H, "engine.charge_reads", 9.3, 9.4, rid=1),
        # A
        ev(HOST, H, "engine.step", 10.5, 14.5),
        ev(HOST, H, "engine.admit", 10.6, 10.9, rid=6, queue_ms=10.5),
        ev(HOST, H, "engine.prefill", 10.65, 10.8, rid=6, pos=0),
        ev(HOST, H, "engine.decode", 11.0, 11.2, active=3),
        ev(HOST, H, "engine.wait", 11.2, 13.9, of="decode"),
        ev(HOST, H, "engine.copy", 13.9, 14.0, of="decode"),
        ev(HOST, H, "engine.charge_reads", 14.0, 14.1, rid=6),
        ev(HOST, H, "engine.charge_reads", 14.1, 14.3, rid=2),
        # B
        ev(HOST, H, "engine.step", 15.0, 18.0),
        ev(HOST, H, "engine.admit", 15.1, 17.9, rid=7, queue_ms=40.0),
        ev(HOST, H, "engine.prefill", 15.2, 15.5, rid=7, pos=0),
        ev(HOST, H, "engine.charge_reads", 15.5, 15.6, rid=7),
        ev(HOST, H, "engine.wait", 15.6, 17.0, of="prefill"),
        ev(HOST, H, "engine.copy", 17.0, 17.2, of="prefill"),
        # C
        ev(HOST, H, "engine.step", 18.5, 18.6),
        # D
        ev(HOST, H, "engine.step", 19.5, 20.5),
        ev(HOST, H, "engine.decode", 19.6, 19.7, active=1),
        ev(HOST, H, "engine.copy", 19.75, 19.78, of="decode"),
        ev(HOST, H, "engine.charge_reads", 19.8, 19.9, rid=7),
    ]


class _Ctx:
    pass


def ctx_of(events):
    ctx = _Ctx()
    ctx.events = events
    ctx.trace_window = (10.0, 20.0) if events is not None else None
    return ctx


def read(name, ctx):
    return run.reader(name)(ctx)


def test_idle_in_step_is_the_part_of_device_idle_inside_steps():
    ctx = ctx_of(device_and_bench() + engine_spans())
    # idle [11, 12] lies in A; of [14, 19], A holds 0.5 s, B 3 s, C 0.1 s,
    # and 1.4 s lie between steps
    assert read("idle_in_step.chat", ctx) == pytest.approx(46.0)
    assert layers.device_idle(ctx) == pytest.approx(60.0)


def test_idle_in_step_on_two_devices_averages_as_busy_does():
    events = device_and_bench() + engine_spans() + [
        ev("/device:TPU:1", tr.OPS, "fusion.1", 10.0, 20.0)]
    ctx = ctx_of(events)
    assert layers.device_idle(ctx) == pytest.approx(30.0)
    assert read("idle_in_step.chat", ctx) == pytest.approx(23.0)


def test_engine_span_readers():
    ctx = ctx_of(device_and_bench() + engine_spans())
    # working steps A, B, D: charges 0.1 + 0.2, 0.1 and 0.1 s; C did nothing
    assert read("ecc_charge_ms_per_step.chat", ctx) == pytest.approx(
        1e3 * 0.5 / 3)
    # decode copies of 0.1 and 0.03 s; the prefill copy is not one
    assert read("logits_copy_ms.chat", ctx) == pytest.approx(65.0)
    # admissions in the window: 0.3 and 2.8 s
    assert read("admit_ms.chat", ctx) == pytest.approx(1550.0)
    # nearest rank of [10.5, 40.0] at 90 %: the second
    assert read("queue_wait_p90_ms.chat", ctx) == pytest.approx(40.0)
    assert read("slots_per_step.chat", ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("events", [None, "no_engine_spans"])
def test_readers_read_nothing_without_engine_spans(events):
    """A trace of a program that writes no ``engine.*`` span, or no trace:
    every new reader returns None and raises nothing."""
    ctx = ctx_of(None if events is None else device_and_bench())
    for name in NEW:
        assert read(name, ctx) is None, name


def test_new_metrics_are_entries_of_the_chat_cell():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == ["olmo1b-static-chat"]
    cell = {w["name"]: w for w in spec["workloads"]}["olmo1b-static-chat"]
    assert set(NEW) <= {m["name"] for m in run.metrics_for(spec, cell, True)}
