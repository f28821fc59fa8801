"""Share of the traced window with no operation running on the device
while an ``engine.step`` span is open: the device waiting on the engine's
own host work, %."""
from yardstick import program


def read(ctx):
    return program.idle_in_step(ctx)
