"""Fleet gauges: ``gauge_miss``, gauges whose fleet size, online count or
tick is wrong."""
from __future__ import annotations

import numpy as np

from checks import plane_tick

KINDS = ("gauges",)
LIMITS = {"gauge_miss": 0}


def check(ev, items: list, seed: int, lower: bool = False) -> dict:
    ref = ev.ref
    miss = 0
    for _, _, r in items:
        want = {"clients": ev.n_clients, "online": ev.masks.count(plane_tick(r)),
                "tick": r.served_tick}
        got = r.body
        if lower:
            low = ref.to_bf16(np.asarray([want["clients"], want["online"]], np.float32))
            got = {"clients": float(low[0]), "online": float(low[1]), "tick": r.served_tick}
        miss += any(got.get(k) != v for k, v in want.items())
    return {"gauge_miss": int(miss)}
