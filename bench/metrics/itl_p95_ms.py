"""95th percentile (nearest rank) over every gap between consecutive tokens
of one request, both returned inside the window, in ms."""
import math

from yardstick.window import nearest_rank


def read(ctx):
    v = nearest_rank(ctx.e2e["itl"], 95)
    return 1e3 * v if math.isfinite(v) else None
