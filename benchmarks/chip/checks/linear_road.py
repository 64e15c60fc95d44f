"""Linear Road's reads, against the reference's replay of the run:

* ``lr_balance_miss``: account_balance answers (type 2) whose balance or
  tick of the last charge differs;
* ``lr_expenditure_miss``: daily_expenditure answers (type 3) whose
  entry of the historical toll table differs;
* ``lr_notice_miss``: toll_notice answers whose tick, segment, toll, LAV,
  count or alert differs.

Each read is served against the state of the tick before the one that
admitted it (`plane_tick`); the reference replays the whole fleet from
tick 0 to the last such tick. All three are exact.
"""
from __future__ import annotations

import numpy as np

from checks import plane_tick

KINDS = ("account_balance", "daily_expenditure", "toll_notice")
LIMITS = {"lr_balance_miss": 0, "lr_expenditure_miss": 0, "lr_notice_miss": 0}
NOTICE = ("tick", "segment", "toll", "lav", "count", "alert")


def check(ev, items: list, seed: int, lower: bool = False) -> dict:
    ref = ev.ref
    xways = int(ev.config["sim"]["expressways"])
    if xways != ref.EXPRESSWAYS:
        raise ValueError(f"{ref.__name__} replays L = {ref.EXPRESSWAYS}, "
                         f"the configuration has L = {xways}")
    asks = [(int(p["client"]), plane_tick(r)) for kind, p, r in items
            if kind != "daily_expenditure"]
    state = ref.replay(ev.seed, ev.n_clients, asks)
    alt = ref.replay(ev.seed, ev.n_clients, asks, lower=True) if lower else None
    spent = [(int(p["client"]), int(p["xway"]), int(p["day"])) for kind, p, _ in items
             if kind == "daily_expenditure"]
    table = iter(ref.expenditure(ev.seed, *np.asarray(spent, np.int64).reshape(-1, 3).T))

    def answer(kind, p, r, states, spend):
        if kind == "daily_expenditure":
            return {"expenditure": spend}
        s = states[(int(p["client"]), plane_tick(r))]
        if kind == "account_balance":
            return {"balance": s["balance"], "last_charge": s["last_charge"]}
        return dict(zip(NOTICE, s["notice"]))

    def rounded(d: dict) -> dict:  # the control's answers, one precision step down
        vals = ref.to_bf16(np.asarray(list(d.values()), np.float32))
        return {k: int(v) for k, v in zip(d, vals)}

    miss = {"account_balance": 0, "daily_expenditure": 0, "toll_notice": 0}
    for kind, p, r in items:
        spend = int(next(table)) if kind == "daily_expenditure" else None
        want = answer(kind, p, r, state, spend)
        got = rounded(answer(kind, p, r, alt, spend)) if lower else r.body
        miss[kind] += got != want
    return {"lr_balance_miss": miss["account_balance"],
            "lr_expenditure_miss": miss["daily_expenditure"],
            "lr_notice_miss": miss["toll_notice"],
            "_lr_checked": len(items), "_lr_ticks_replayed": max((t for _, t in asks), default=0)}
