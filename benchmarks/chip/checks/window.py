"""Window reads: ``window_miss``, reads whose values differ from what the
vehicle observed."""
from __future__ import annotations

import numpy as np

from checks import column, plane_tick

KINDS = ("window",)
LIMITS = {"window_miss": 0}


def check(ev, items: list, seed: int, lower: bool = False) -> dict:
    """Every window read: the last ``k`` ticks the vehicle observed, ``k``
    capped by the ticks recorded, offline ticks skipped."""
    ref = ev.ref
    spans = []
    for _, p, r in items:
        tau = plane_tick(r)
        k = max(0, min(int(p["k"]), tau + 1, ev.history))
        spans.append((int(p["client"]), tau - k + 1, k, column(ev, p["signal"])))
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
