"""Device milliseconds of Linear Road's toll step per tick: every device
operation that started inside the program's ``plane.tolls`` spans. The
step waits for the plane's step before its span opens and for its own
work before the span closes, so the span holds the toll step's device
work and no other."""
import numpy as np

import trace_reduce as tr

PROGRAM = True


def steps(ctx) -> int:
    spans = ctx.program.get("spans")
    return 0 if spans is None else int(np.count_nonzero(spans["name"] == "plane.tolls"))


def read(ctx):
    n = steps(ctx)
    s = tr.device_s_in(ctx.trace, "plane.tolls")
    return 1e3 * s / n if n and s > 0 else None
