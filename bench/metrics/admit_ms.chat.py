"""Mean duration of an ``engine.admit`` span: the stall one admission puts
on every active slot (ms)."""
from yardstick import program


def read(ctx):
    return program.mean_ms(ctx, "engine.admit")
