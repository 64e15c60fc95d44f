"""What decides ``correct``: the answers the measured window produced, and
the ring the plane wrote, each held against the plain reference.

Every window a request read is rebuilt by the reference from the seed and
the configuration's churn (`reference.churn`, `reference.observed`), so
every answer of the run can be checked, at any tick, and none trusts the
program's own record of which vehicles were online. The program's
ring is compared with the same reference on sampled vehicles, from host
copies taken before and after the window.

Each compared number has a limit, set in PERF.md from two readings: the
largest that sound runs of the program gave over a dozen seeds or more,
and the smallest that the control gave. The control is the reference in
the program's place, computed one precision step below the
configuration's float32: values rounded to bfloat16, and the fold and
merge carried out in bfloat16. A count of exact mismatches has the
limit 0.

* ``ring_gap``: sampled vehicles' ring entries against the reference
  signals, largest gap (relative above 1, absolute below);
* ``ring_nan_miss``: entries whose NaN (offline) state differs from the
  reference's online state at that tick;
* ``stats_miss``: sampled fleet_stats and quantile answers whose counts,
  participants, histogram or quantile values differ from the reference
  fold and merge over the same ticks;
* ``stats_mean_gap``, ``stats_var_gap``: their mean and variance, relative
  gap to the float64 merge;
* ``window_miss``: window reads whose values differ from what the
  vehicle observed; ``signal_gap``: latest-value reads against the
  reference signal; ``gauge_miss``: gauges whose fleet size, online count
  or tick is wrong;
* ``unanswered``, ``failed``: requests that never came back, or came back
  ``ok=False``, of all the run sent.

The answers checked are those of every request of the run, or of
``CHECK_SAMPLE`` of them drawn from the seed where the run sends more.
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np

import reference as ref

#: number -> limit. The readings each was set from are in PERF.md.
LIMITS = {
    "ring_gap": 1e-3,
    "ring_nan_miss": 0,
    "stats_miss": 0,
    "stats_mean_gap": 3e-3,
    "stats_var_gap": 1e-2,
    "window_miss": 0,
    "signal_gap": 1e-3,
    "gauge_miss": 0,
    "unanswered": 0,
    "failed": 0,
}
#: vehicles whose whole ring is held against the reference signals
RING_ROWS = 256
#: fleet statistics folds (tick, signal, spec) the reference recomputes
STATS_GROUPS = 8
#: answers a run keeps for the check, drawn from the seed (all, if fewer)
CHECK_SAMPLE = 20_000


@dataclasses.dataclass
class Evidence:
    """What a run leaves for the check."""

    scenario: str
    seed: int             # the fleet's seed
    n_clients: int
    history: int          # ring slots (the configuration's signal_history)
    rows: np.ndarray      # sampled vehicle rows (RING_ROWS)
    p_leave: float        # the configuration's churn
    p_return: float
    copies: list          # (plane tick, host copy of the ring), the last one final
    answers: list         # (kind, params, response) of the sampled requests
    unanswered: int       # requests never answered
    failed: int           # requests answered ok=False
    masks: ref.Masks | None = None  # the reference's online state, made by `compare`


def check_sample(seed: int, n: int) -> frozenset:
    """Which of a run's ``n`` requests have their answers checked."""
    if n <= CHECK_SAMPLE:
        return frozenset(range(n))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    return frozenset(int(j) for j in rng.choice(n, size=CHECK_SAMPLE, replace=False))


def sample_rows(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5A3])
    return np.sort(rng.choice(n, size=min(RING_ROWS, n), replace=False))


def _col(signal: str) -> int:
    return ref.SIGNALS.index(signal)


def _plane_tick(r) -> int:
    """Reads are served at the admission of tick ``served_tick``, against
    the state the previous tick left: plane tick ``served_tick - 1``."""
    return r.served_tick - 1


# --------------------------------------------------------------------- #
# the ring                                                              #
# --------------------------------------------------------------------- #
def check_ring(ev: Evidence, lower: bool = False) -> dict:
    gap, miss = 0.0, 0
    for t, ring in ev.copies:
        ticks = np.arange(t - ring.shape[0] + 1, t + 1)
        want = ref.observed(ev.scenario, ev.seed, ev.masks, ev.rows, ticks)
        got = ref.to_bf16(want) if lower else ring[ticks % ring.shape[0]][:, ev.rows, :]
        nan_got, nan_want = np.isnan(got), np.isnan(want)
        miss += int(np.count_nonzero(nan_got != nan_want))
        both = ~nan_got & ~nan_want
        gap = max(gap, ref.gap(got[both], want[both]))
    return {"ring_gap": gap, "ring_nan_miss": miss}


# --------------------------------------------------------------------- #
# fleet statistics                                                      #
# --------------------------------------------------------------------- #
def _spec(p: dict) -> ref.Spec:
    return ref.Spec(int(p.get("window", 64)), int(p.get("bins", 16)),
                    float(p.get("lo", 0.0)), float(p.get("hi", 12.0)),
                    int(p.get("quantile_k", 32)))


def _stats_body(kind: str, p: dict, m: ref.Merged) -> dict:
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


def check_stats(ev: Evidence, items: list, seed: int, lower: bool = False) -> dict:
    """A sample of the folds drawn from the seed, the widest window among
    them, and every answer served from each."""
    groups = collections.defaultdict(list)
    for kind, p, r in items:
        groups[(_plane_tick(r), p["signal"], _spec(p))].append((kind, p, r))
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
        x = ref.observed(ev.scenario, ev.seed, ev.masks, everyone, ticks)[:, :, _col(signal)]
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


# --------------------------------------------------------------------- #
# per-vehicle reads and gauges                                          #
# --------------------------------------------------------------------- #
def check_window(ev: Evidence, items: list, seed: int, lower: bool = False) -> dict:
    """Every window read: the last ``k`` ticks the vehicle observed, ``k``
    capped by the ticks recorded, offline ticks skipped."""
    spans = []
    for _, p, r in items:
        tau = _plane_tick(r)
        k = max(0, min(int(p["k"]), tau + 1, ev.history))
        spans.append((int(p["client"]), tau - k + 1, k, _col(p["signal"])))
    rows = np.concatenate([np.full(k, row) for row, _, k, _ in spans]) if spans else []
    ticks = np.concatenate([np.arange(t0, t0 + k) for _, t0, k, _ in spans]) if spans else []
    cols = np.concatenate([np.full(k, c) for _, _, k, c in spans]) if spans else []
    vals = ref.signal_at(ev.scenario, ev.seed, rows, ticks)
    vals = vals[np.arange(len(vals)), np.asarray(cols, int)]
    on = ev.masks.online_pairs(rows, ticks) if len(rows) else np.zeros(0, bool)
    low = ref.to_bf16(vals) if lower else None
    miss, i = 0, 0
    for (_, _, r), (_, _, k, _) in zip(items, spans):
        want = [float(v) for v, o in zip(vals[i: i + k], on[i: i + k]) if o]
        got = [float(v) for v, o in zip(low[i: i + k], on[i: i + k]) if o] if lower \
            else r.body["values"]
        miss += got != want
        i += k
    return {"window_miss": int(miss), "_window_checked": len(items)}


def check_signal(ev: Evidence, items: list, seed: int, lower: bool = False) -> dict:
    rows = [int(p["client"]) for _, p, _ in items]
    ticks = [_plane_tick(r) for _, _, r in items]
    want = ref.signal_at(ev.scenario, ev.seed, rows, ticks)
    want = want[np.arange(len(items)), [_col(p["signal"]) for _, p, _ in items]]
    got = ref.to_bf16(want) if lower else np.asarray(
        [np.nan if r.body["value"] is None else r.body["value"] for _, _, r in items])
    bad = np.isnan(got)
    return {"signal_gap": float("inf") if bad.any() else ref.gap(got, want)}


def check_gauges(ev: Evidence, items: list, seed: int, lower: bool = False) -> dict:
    miss = 0
    for _, _, r in items:
        want = {"clients": ev.n_clients, "online": ev.masks.count(_plane_tick(r)),
                "tick": r.served_tick}
        got = r.body
        if lower:
            low = ref.to_bf16(np.asarray([want["clients"], want["online"]], np.float32))
            got = {"clients": float(low[0]), "online": float(low[1]), "tick": r.served_tick}
        miss += any(got.get(k) != v for k, v in want.items())
    return {"gauge_miss": int(miss)}


#: request kind -> its check. A kind without one (``platform``: broker and
#: store counts with no independent reference) is held to ``ok`` only.
CHECKS = {
    "fleet_stats": check_stats,
    "quantile": check_stats,
    "window": check_window,
    "signal": check_signal,
    "gauges": check_gauges,
}


def compare(ev: Evidence, seed: int, lower: bool = False) -> dict:
    """Every compared number of a run (``_``-prefixed entries are counts of
    what was checked, for the log, and have no limit). ``lower`` puts the
    control in the program's place."""
    if ev.masks is None:
        ev.masks = ref.churn(ev.seed, ev.n_clients, ev.p_leave, ev.p_return,
                             max(t for t, _ in ev.copies))
    out = check_ring(ev, lower)
    by_check = collections.defaultdict(list)
    for kind, p, r in ev.answers:
        if kind in CHECKS and r.ok:
            by_check[CHECKS[kind]].append((kind, p, r))
    for fn, items in by_check.items():
        out.update(fn(ev, items, seed, lower))
    out["unanswered"] = ev.unanswered
    out["failed"] = ev.failed
    return out


def verdict(numbers: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items() if k in LIMITS)
