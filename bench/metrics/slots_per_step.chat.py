"""Mean ``active`` slots over the ``engine.decode`` spans: the decode
batch's occupancy (slots)."""
from yardstick import program


def read(ctx):
    return program.mean_arg(ctx, "engine.decode", "active")
