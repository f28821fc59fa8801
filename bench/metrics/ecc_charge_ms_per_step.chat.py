"""Time in ``engine.charge_reads`` spans inside the engine steps that
prefilled or decoded, per such step (ms)."""
from yardstick import program


def read(ctx):
    return program.ms_per_working_step(ctx, "engine.charge_reads")
