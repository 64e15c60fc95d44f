"""Device milliseconds per sketch fold: every device operation that started
inside the span of ``plane.compute_sketches``, which ends in a blocking
copy of the sketch block."""
import trace_reduce as tr


def read(ctx):
    if not ctx.folds:
        return None
    s = tr.device_s_in(ctx.trace, "bench.fold")
    return 1e3 * s / len(ctx.folds) if s > 0 else None
