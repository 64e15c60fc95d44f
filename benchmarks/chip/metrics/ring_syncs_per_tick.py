"""Whole-ring device-to-host copies per tick (``ring_syncs``)."""


def read(ctx):
    ticks = ctx.delta("ticks")
    return ctx.delta("ring_syncs") / ticks if ticks else None
