"""Share of the window in which no operation ran on the device."""
import trace_reduce as tr


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - tr.busy_s(ctx.trace) / w) if w > 0 else None
