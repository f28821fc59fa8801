"""Device time per execution of the engine's decode-step program (ms)."""
from yardstick import layers


def read(ctx):
    return layers.program_ms(ctx, r"decode_slots")
