"""Host self time of an engine step: ``Engine.step`` wall minus the
prefill, decode and ECC-accounting spans inside it, per step that did work
(ms)."""
from yardstick import layers


def read(ctx):
    return layers.host_ms_per_step(ctx)
