"""Model FLOPs of every prompt and decode token processed in the window
over the window times the chip's bfloat16 peak, %."""
from yardstick import layers


def read(ctx):
    return layers.mfu(ctx)
