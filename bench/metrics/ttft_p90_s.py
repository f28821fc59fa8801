"""90th percentile (nearest rank) over every request due in the window of
first token on the host minus the time the schedule made it due. A request
that got no first token counts as infinitely late."""
import math

from yardstick.window import nearest_rank


def read(ctx):
    v = nearest_rank(ctx.e2e["ttft"], 90)
    return v if math.isfinite(v) else None
