"""Fleet statistics: fleet_stats and quantile answers against the reference
fold and merge over the same ticks.

* ``stats_miss``: answers whose counts, participants, histogram or
  quantile values differ from the reference;
* ``stats_mean_gap``, ``stats_var_gap``: their mean and variance, relative
  gap to the float64 merge.
"""
from __future__ import annotations

import collections

import numpy as np

from checks import column, plane_tick

KINDS = ("fleet_stats", "quantile")
LIMITS = {
    "stats_miss": 0,
    "stats_mean_gap": 3e-3,
    "stats_var_gap": 1e-2,
}
#: fleet statistics folds (tick, signal, spec) the reference recomputes
STATS_GROUPS = 8


def _spec(ref, p: dict):
    return ref.Spec(int(p.get("window", 64)), int(p.get("bins", 16)),
                    float(p.get("lo", 0.0)), float(p.get("hi", 12.0)),
                    int(p.get("quantile_k", 32)))


def _stats_body(kind: str, p: dict, m) -> dict:
    if kind == "quantile":
        return {"count": m.count, "value": m.quantile(float(p["q"]))}
    qs = [float(v) for v in p.get("quantiles", (0.5, 0.9))]
    return {"count": m.count, "participants": m.participants, "mean": m.mean,
            "var": m.var, "hist": m.hist,
            "quantiles": {f"p{round(100 * v):02d}": m.quantile(v) for v in qs}}


def _rel(got, want) -> float:
    if got is None or want is None:
        return 0.0 if got is None and want is None else float("inf")
    return abs(got - want) / max(abs(want), 1e-30)


def check(ev, items: list, seed: int, lower: bool = False) -> dict:
    """A sample of the folds drawn from the seed, the widest window among
    them, and every answer served from each."""
    ref = ev.ref
    groups = collections.defaultdict(list)
    for kind, p, r in items:
        groups[(plane_tick(r), p["signal"], _spec(ref, p))].append((kind, p, r))
    keys = sorted(groups, key=lambda g: (g[0], g[1], g[2].window))
    rng = np.random.default_rng([int(seed), 0x57A7])
    pick = []
    if keys:
        widest = [g for g in keys if g[2].window == max(k[2].window for k in keys)]
        pick.append(widest[int(rng.integers(len(widest)))])
        rest = [g for g in keys if g != pick[0]]
        pick += [rest[int(i)] for i in rng.permutation(len(rest))[: STATS_GROUPS - 1]]
    miss, mean_gap, var_gap, n = 0, 0.0, 0.0, 0
    everyone = np.arange(ev.n_clients)
    for tau, signal, spec in pick:
        ticks = np.arange(tau - spec.window + 1, tau + 1)
        x = ref.observed(ev.scenario, ev.seed, ev.masks, everyone, ticks)[:, :, column(ev, signal)]
        want = ref.merge(ref.fold(x, spec), spec.quantile_k)
        alt = ref.merge(ref.fold(x, spec, ref.BF16), spec.quantile_k, ref.BF16) if lower \
            else None
        for kind, p, r in groups[(tau, signal, spec)]:
            w = _stats_body(kind, p, want)
            g = _stats_body(kind, p, alt) if lower else r.body
            n += 1
            exact = ("count", "value") if kind == "quantile" else \
                ("count", "participants", "hist", "quantiles")
            miss += any(g[k] != w[k] for k in exact)
            if kind == "fleet_stats":
                mean_gap = max(mean_gap, _rel(g["mean"], w["mean"]))
                var_gap = max(var_gap, _rel(g["var"], w["var"]))
    return {"stats_miss": miss, "stats_mean_gap": mean_gap, "stats_var_gap": var_gap,
            "_stats_checked": n, "_stats_groups": len(pick)}
