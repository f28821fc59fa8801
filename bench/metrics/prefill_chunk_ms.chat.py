"""Device time per execution of the engine's prefill-chunk program (ms)."""
from yardstick import layers


def read(ctx):
    return layers.program_ms(ctx, r"prefill_chunk")
