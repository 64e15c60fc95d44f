"""Host milliseconds per engine service sweep (``sim.service.tick``)."""


def read(ctx):
    return ctx.mean_span_ms("bench.service")
