"""Mean duration of the decode logits' device-to-host copy, the
``engine.copy`` spans with ``of=decode`` (ms)."""
from yardstick import program


def read(ctx):
    return program.mean_ms(ctx, "engine.copy", of="decode")
