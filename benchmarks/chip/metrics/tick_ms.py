"""Host milliseconds per ``FleetGateway.tick()`` in the window."""


def read(ctx):
    return ctx.mean_span_ms("bench.tick")
