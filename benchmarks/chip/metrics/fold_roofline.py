"""Share of the HBM roofline the sketch folds reach.

The least time of a fold is its necessary bytes over peak HBM bandwidth:
4 * N * (W + 3 + bins + K), the f32 window of every online vehicle read
once and the sketch block written once. Exact order statistics need the
whole window, so no correct fold can need fewer bytes; the count depends
only on the request's spec and the fleet, not on how the fold is built.
"""
import trace_reduce as tr


def least_bytes(fold: dict) -> float:
    return 4.0 * fold["online"] * (fold["window"] + 3 + fold["bins"] + fold["quantile_k"])


def read(ctx):
    if not ctx.folds:
        return None
    s = tr.device_s_in(ctx.trace, "bench.fold")
    if s <= 0:
        return None
    least = sum(least_bytes(f) for f in ctx.folds) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
