"""Device milliseconds per fleet-statistics computation outside the fold:
the operations that started inside ``_fleet_stats`` spans but not inside
a fold span (today the three ``merge_*`` programs)."""
import trace_reduce as tr


def read(ctx):
    if not ctx.stats_misses:
        return None
    s = tr.device_s_in(ctx.trace, "bench.stats", outside="bench.fold")
    return 1e3 * s / len(ctx.stats_misses) if s > 0 else None
