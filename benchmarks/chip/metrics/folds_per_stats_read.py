"""Sketch folds run per statistics read served (fleet_stats and quantile)."""


def read(ctx):
    reads = ctx.delta("served.fleet_stats") + ctx.delta("served.quantile")
    return len(ctx.folds) / reads if reads else None
