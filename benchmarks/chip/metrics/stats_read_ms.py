"""Host milliseconds per fleet-statistics computation on a cache miss."""
import numpy as np


def read(ctx):
    s = ctx.stats_misses
    return 1e3 * float(np.mean(s)) if s else None
