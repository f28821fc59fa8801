"""90th percentile (nearest rank) of ``queue_ms``, admission start minus
submit time, over the ``engine.admit`` spans starting in the window (ms)."""
from yardstick import program


def read(ctx):
    return program.arg_percentile(ctx, "engine.admit", "queue_ms", 90)
