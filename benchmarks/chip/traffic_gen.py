"""The one generator every traffic mix goes through.

A mix is a JSON file under ``traffic/``: an arrival rate, a number of
analyst sessions, and request kinds with their shares and parameters.
Each parameter is either a constant or a draw:

* ``{"choice": [...]}``: uniform over the list;
* ``{"zipf": s, "keys": [...]}``: Zipf with exponent ``s`` over the listed
  keys, the first the most popular (the order is part of the mix);
* ``{"zipf": s, "fleet": true}``: Zipf over the fleet's vehicle rows, the
  ranks mapped to rows through a permutation drawn from the seed.

Every seed sends the same set of requests: ``round(rate * seconds)`` of
them, kinds in their exact shares, and every draw taken at evenly spaced
points of its distribution. The seed decides only the order, which vehicle
holds which rank, and the send times, which are uniform order statistics
over the window: a Poisson process given its count. So two seeds do the
same work, and a run's spread is the system's, not the mix's.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float
    session: str
    kind: str
    params: dict


def _shares(total: int, weights: list[float]) -> list[int]:
    """Split ``total`` in proportion to ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    out = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - out), kind="stable")[: total - out.sum()]:
        out[i] += 1
    return [int(v) for v in out]


def _spread(n: int, cdf: np.ndarray) -> np.ndarray:
    """``n`` indices at evenly spaced points of a discrete distribution."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    return np.minimum(np.searchsorted(cdf, u, side="right"), len(cdf) - 1)


def _zipf_cdf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.cumsum(p / p.sum())


def _draws(spec, n: int, rng: np.random.Generator, fleet: int) -> list:
    if not isinstance(spec, dict):
        return [spec] * n
    if "choice" in spec:
        opts = spec["choice"]
        idx = _spread(n, np.arange(1, len(opts) + 1) / len(opts))
    elif spec.get("fleet"):
        idx = _spread(n, _zipf_cdf(fleet, float(spec["zipf"])))
        opts = rng.permutation(fleet).tolist()  # which vehicle holds each rank
    elif "zipf" in spec:
        opts = spec["keys"]
        idx = _spread(n, _zipf_cdf(len(opts), float(spec["zipf"])))
    else:
        raise ValueError(f"unknown draw {spec!r}")
    return [opts[int(i)] for i in rng.permutation(idx)]


def expand(kind: dict, draws: dict) -> dict:
    """Request parameters from one set of draws; a drawn ``key`` (a dict)
    is spread into the parameters."""
    params = {}
    for k, v in draws.items():
        if k == "key":
            params.update(v)
        else:
            params[k] = v
    params.update(kind.get("const", {}))
    return params


def generate(mix: dict, seed: int, seconds: float, fleet: int) -> list[Request]:
    """The schedule of one run: requests sorted by send time."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    total = int(round(float(mix["rate_per_s"]) * seconds))
    kinds = mix["kinds"]
    counts = _shares(total, [k["share"] for k in kinds])
    reqs: list[tuple[str, dict]] = []
    for kind, n in zip(kinds, counts):
        cols = {name: _draws(spec, n, rng, fleet)
                for name, spec in kind.get("draw", {}).items()}
        for i in range(n):
            reqs.append((kind["kind"], expand(kind, {k: v[i] for k, v in cols.items()})))
    order = rng.permutation(len(reqs))
    sessions = rng.permutation(np.arange(total) % int(mix["sessions"]))
    due = np.sort(rng.uniform(0.0, seconds, size=total))
    return [
        Request(float(due[i]), f"analyst-{int(sessions[i])}", reqs[j][0], reqs[j][1])
        for i, j in enumerate(order)
    ]


def distinct_shapes(mix: dict, fleet: int) -> list[tuple[str, dict]]:
    """One request of every kind and every value of the draws that decide
    what the program compiles or folds (all but the vehicle drawn): the
    warm-up set."""
    out = []
    for kind in mix["kinds"]:
        fixed = {}
        grid: list[dict] = [{}]
        for name, spec in kind.get("draw", {}).items():
            if isinstance(spec, dict) and spec.get("fleet"):
                fixed[name] = 0
                continue
            opts = spec.get("choice", spec.get("keys")) if isinstance(spec, dict) else [spec]
            grid = [{**g, name: o} for g in grid for o in opts]
        for g in grid:
            out.append((kind["kind"], expand(kind, {**fixed, **g})))
    return out
