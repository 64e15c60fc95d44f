"""Latest-value reads: ``signal_gap``, the largest gap to the reference
signal (relative above 1, absolute below)."""
from __future__ import annotations

import numpy as np

from checks import column, plane_tick

KINDS = ("signal",)
LIMITS = {"signal_gap": 1e-3}


def check(ev, items: list, seed: int, lower: bool = False) -> dict:
    ref = ev.ref
    rows = [int(p["client"]) for _, p, _ in items]
    ticks = [plane_tick(r) for _, _, r in items]
    want = ref.signal_at(ev.scenario, ev.seed, rows, ticks)
    want = want[np.arange(len(items)), [column(ev, p["signal"]) for _, p, _ in items]]
    got = ref.to_bf16(want) if lower else np.asarray(
        [np.nan if r.body["value"] is None else r.body["value"] for _, _, r in items])
    bad = np.isnan(got)
    return {"signal_gap": float("inf") if bad.any() else ref.gap(got, want)}
