"""Device milliseconds of the signal plane's jitted tick per plane step.

The plane jits its step as ``tick``, so its XLA module is ``jit_tick``.
"""
import trace_reduce as tr


def read(ctx):
    steps = ctx.delta("ticks")
    s = tr.module_s(ctx.trace, "jit_tick")
    return 1e3 * s / steps if steps and s > 0 else None
