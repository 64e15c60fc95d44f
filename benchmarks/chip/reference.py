"""The plain reference the chip benchmark holds the fleet platform to.

It imports nothing of the program under test. Three parts:

* `signals`: the seeded drive-cycle signals a vehicle reports at a tick,
  written out from the scenario definition (the ``mixed`` regime fleet
  and its single-regime members), evaluated with jax on the default
  device, whose float32 division and transcendentals are the program's
  (on the host CPU they differ by a few ulps, which the urban stop-go
  cycle amplifies to ~1e-3 of a speed);
* `fold` and `merge`: the per-vehicle windowed sketch (f32 Welford
  moments, edge-comparison histogram, integer-rank quantile summary) and
  the fleet-level merge (float64 moments, integer histogram, weighted
  quantile summary), vectorised over vehicles with numpy;
* `churn`, `Masks` and `observed`: which vehicles were online at each
  tick, rebuilt from the seed and the configuration's ``p_leave`` and
  ``p_return``, and so what each vehicle observed: its signals while
  online, NaN while offline. Every window a request read is rebuilt from
  these, at any tick of the run.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math

import ml_dtypes
import numpy as np

#: column order of the signal ring
SIGNALS = ("Vehicle.Speed", "Vehicle.FuelRate", "Vehicle.RoadGrade",
           "Engine.Temperature")
_HIGHWAY, _URBAN, _IDLE = 0, 1, 2
_REGIME = {"highway": _HIGHWAY, "urban": _URBAN, "idle": _IDLE}
#: share of highway, urban and idle vehicles in the ``mixed`` fleet
_MIX = (0.45, 0.35, 0.20)


# --------------------------------------------------------------------- #
# signals                                                               #
# --------------------------------------------------------------------- #
#: ticks and (vehicle, tick) pairs per reference call: fixed shapes, so
#: each program compiles once and is found in the cache by later runs
TICK_CHUNK, PAIR_CHUNK = 64, 1 << 16


def _signal_fn(scenario: str):
    """(seed, rows uint32 (n,), t int32) -> (n, 4) f32 for one scenario."""
    import jax
    import jax.numpy as jnp

    if scenario != "mixed" and scenario not in _REGIME:
        raise ValueError(f"no reference for scenario {scenario!r}")

    def at(seed, rows, t):
        base = jax.random.PRNGKey(seed)
        ckeys = jax.vmap(lambda i: jax.random.fold_in(base, i))(rows)
        u = jax.vmap(lambda k: jax.random.uniform(k, (6,)))(ckeys)
        if scenario == "mixed":
            c0, c1 = _MIX[0], _MIX[0] + _MIX[1]
            regime = jnp.where(u[:, 0] < c0, _HIGHWAY,
                               jnp.where(u[:, 0] < c1, _URBAN, _IDLE))
        else:
            regime = jnp.full(rows.shape, _REGIME[scenario], jnp.int32)
        cruise = 95.0 + 25.0 * u[:, 1]
        peak = 28.0 + 24.0 * u[:, 1]
        hw_period = 40.0 + 40.0 * u[:, 2]
        ub_period = 8.0 + 10.0 * u[:, 2]
        phase = 2.0 * jnp.pi * u[:, 3]
        grade0 = 0.06 * (u[:, 4] - 0.5)
        noise = 0.3 + 0.7 * u[:, 5]
        tf = t.astype(jnp.float32)
        tkeys = jax.vmap(lambda k: jax.random.fold_in(k, t))(ckeys)
        eps = jax.vmap(lambda k: jax.random.normal(k, (2,)))(tkeys)
        v_hw = cruise + 8.0 * jnp.sin(2.0 * jnp.pi * tf / hw_period + phase)
        v_hw = v_hw + noise * eps[:, 0]
        frac = jnp.mod(tf / ub_period + phase / (2.0 * jnp.pi), 1.0)
        v_ub = jnp.where(frac < 0.6,
                         peak * jnp.sin(jnp.pi * frac / 0.6) + 0.5 * noise * eps[:, 0],
                         0.0)
        speed = jnp.select([regime == _HIGHWAY, regime == _URBAN], [v_hw, v_ub], 0.0)
        speed = jnp.maximum(speed, 0.0)
        grade_osc = 0.02 * jnp.sin(2.0 * jnp.pi * tf / (3.0 * hw_period) + 2.0 * phase)
        grade = jnp.where(regime == _IDLE, 0.0, grade0 + grade_osc)
        fuel = (0.6 + 0.04 * speed + 1.2 * jnp.maximum(grade, 0.0) * speed
                + 0.05 * noise * eps[:, 1])
        fuel = jnp.maximum(fuel, 0.15)
        ambient = jnp.where(regime == _IDLE, -5.0, 15.0)
        target = jnp.where(regime == _IDLE, 55.0, 90.0)
        tau = jnp.where(regime == _IDLE, 120.0, 40.0)
        temp = ambient + (target - ambient) * (1.0 - jnp.exp(-tf / tau))
        return jnp.stack([speed, fuel, grade, temp], axis=1).astype(jnp.float32)

    return at


@functools.lru_cache(maxsize=None)
def _programs(scenario: str):
    import jax

    at = _signal_fn(scenario)
    grid = jax.jit(jax.vmap(at, in_axes=(None, None, 0)))
    pairs = jax.jit(jax.vmap(lambda seed, r, t: at(seed, r[None], t)[0],
                             in_axes=(None, 0, 0)))
    return grid, pairs


def signals(scenario: str, seed: int, rows, ticks) -> np.ndarray:
    """(len(ticks), len(rows), 4) f32: what each vehicle reports at each
    tick (ticks below 0 read as tick 0; the caller masks them)."""
    grid, _ = _programs(scenario)
    rows = np.asarray(rows, np.uint32)
    ticks = np.maximum(np.asarray(ticks, np.int32), 0)
    pad = (-len(ticks)) % TICK_CHUNK
    t = np.concatenate([ticks, np.zeros(pad, np.int32)])
    out = [np.asarray(grid(np.uint32(seed), rows, t[i: i + TICK_CHUNK]))
           for i in range(0, len(t), TICK_CHUNK)]
    return np.concatenate(out)[: len(ticks)]


def signal_at(scenario: str, seed: int, rows, ticks) -> np.ndarray:
    """(n, 4) f32: vehicle ``rows[i]`` at tick ``ticks[i]``, pair by pair."""
    _, pairs = _programs(scenario)
    rows = np.asarray(rows, np.uint32)
    ticks = np.maximum(np.asarray(ticks, np.int32), 0)
    n = len(rows)
    pad = (-n) % PAIR_CHUNK
    r = np.concatenate([rows, np.zeros(pad, np.uint32)])
    t = np.concatenate([ticks, np.zeros(pad, np.int32)])
    out = [np.asarray(pairs(np.uint32(seed), r[i: i + PAIR_CHUNK], t[i: i + PAIR_CHUNK]))
           for i in range(0, len(r), PAIR_CHUNK)]
    return np.concatenate(out)[:n] if out else np.zeros((0, 4), np.float32)


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest gap between two float arrays, as a share of the reference
    value where that exceeds 1 and absolute below (signals near zero, such
    as a road grade, would make a purely relative gap meaningless)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


# --------------------------------------------------------------------- #
# what a vehicle observed: its signals while it was online               #
# --------------------------------------------------------------------- #
class Masks:
    """Which vehicles were online at each plane tick, kept where it
    changed. A vehicle observes nothing (NaN) while it is offline."""

    def __init__(self):
        self.ticks: list[int] = []
        self.masks: list[np.ndarray] = []
        self._stack = None

    def record(self, t: int, online: np.ndarray) -> None:
        if not self.masks or not np.array_equal(online, self.masks[-1]):
            self.ticks.append(int(t))
            self.masks.append(np.array(online, bool))
            self._stack = None

    def _at(self, ticks: np.ndarray) -> np.ndarray:
        if self._stack is None:
            self._stack = np.stack(self.masks)
        return np.maximum(np.searchsorted(self.ticks, ticks, side="right") - 1, 0)

    def online(self, rows, ticks) -> np.ndarray:
        """(len(ticks), len(rows)) bool; False before tick 0."""
        ticks = np.asarray(ticks)
        idx = self._at(ticks)
        out = self._stack[idx][:, np.asarray(rows)]
        out[ticks < 0] = False
        return out

    def online_pairs(self, rows, ticks) -> np.ndarray:
        """(n,) bool: vehicle ``rows[i]`` online at tick ``ticks[i]``."""
        ticks = np.asarray(ticks)
        idx = self._at(ticks)
        return self._stack[idx, np.asarray(rows)] & (ticks >= 0)

    def count(self, t: int) -> int:
        """Vehicles online at plane tick ``t``."""
        idx = self._at(np.asarray([t]))[0]
        return int(np.count_nonzero(self._stack[idx]))


def churn(seed: int, n: int, p_leave: float, p_return: float, t_last: int) -> Masks:
    """Which vehicles are online at each tick up to ``t_last``, from the
    configuration's churn alone. Every vehicle starts online. Its ignition
    flips after a wait of Geometric(p) ticks, ``p`` being ``p_leave`` while
    it is online and ``p_return`` while it is offline (a probability of 0
    never flips), each wait drawn by inverse CDF from one uniform of the
    vehicle's own stream, ``default_rng((seed, 0xC0FFEE, row))``. A flip at
    tick ``t`` holds from plane tick ``t`` on."""
    flips: dict[int, list[int]] = collections.defaultdict(list)
    if p_leave > 0.0:
        for row in range(n):
            rng = np.random.default_rng((int(seed), 0xC0FFEE, row))
            t, on = 0, True
            while True:
                p = p_leave if on else p_return
                if p <= 0.0:
                    break
                u = float(rng.random())
                t += 1 if p >= 1.0 else 1 + int(math.floor(
                    min(math.log1p(-u) / math.log1p(-p), 2.0**53)))
                if t > t_last:
                    break
                flips[t].append(row)
                on = not on
    masks = Masks()
    state = np.ones(n, bool)
    masks.record(0, state)
    for t in sorted(flips):
        state[flips[t]] = ~state[flips[t]]
        masks.record(t, state)
    return masks


def observed(scenario: str, seed: int, masks: Masks, rows, ticks) -> np.ndarray:
    """(len(ticks), len(rows), 4) f32 as the ring should hold it: the
    signals, NaN where the vehicle was offline or before tick 0."""
    vals = signals(scenario, seed, rows, ticks)
    on = masks.online(rows, ticks)
    return np.where(on[:, :, None], vals, np.float32(np.nan))


# --------------------------------------------------------------------- #
# per-vehicle fold and fleet merge                                       #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Spec:
    window: int
    bins: int
    lo: float
    hi: float
    quantile_k: int

    def edges(self) -> np.ndarray:
        width = (self.hi - self.lo) / self.bins
        return (self.lo + width * np.arange(1, self.bins)).astype(np.float32)


@dataclasses.dataclass
class Fold:
    counts: np.ndarray  # (n,) int64
    means: np.ndarray   # (n,) f32
    m2s: np.ndarray     # (n,) f32
    hists: np.ndarray   # (n, bins) int64
    qvals: np.ndarray   # (n, K) f32, NaN where count == 0


def fold(x: np.ndarray, spec: Spec, dtype=np.float32) -> Fold:
    """Fold each column of a (W, n) window (NaN = not observed) the way a
    vehicle folds its own window: Welford in ``dtype`` (f32, as the
    configuration states), sample by sample."""
    x = np.asarray(x, np.float32).astype(dtype)
    W, n = x.shape
    valid = ~np.isnan(x)
    one = np.ones(n, dtype)
    zero = np.zeros(n, dtype)
    c, mean, m2 = zero.copy(), zero.copy(), zero.copy()
    with np.errstate(invalid="ignore", divide="ignore"):
        for s in range(W):
            ok = valid[s]
            v = np.where(ok, x[s], zero)
            cn = c + one
            d = v - mean
            mn = mean + d / cn
            m2n = m2 + d * (v - mn)
            c = np.where(ok, cn, c)
            mean = np.where(ok, mn, mean)
            m2 = np.where(ok, m2n, m2)
    counts = valid.sum(axis=0).astype(np.int64)
    x = x.astype(np.float32)
    edges = spec.edges()
    ge = np.stack([(valid & (x >= e)).sum(axis=0) for e in edges]) if len(edges) else \
        np.zeros((0, n), np.int64)
    upper = np.concatenate([counts[None], ge], axis=0)
    lower = np.concatenate([ge, np.zeros((1, n), np.int64)], axis=0)
    hists = (upper - lower).T.astype(np.int64)
    srt = np.sort(x.T, axis=1)  # NaN last
    K = spec.quantile_k
    j = np.arange(K)[None, :]
    ranks = np.minimum((2 * j + 1) * counts[:, None] // (2 * K),
                       np.maximum(counts[:, None] - 1, 0))
    qvals = np.take_along_axis(srt, ranks, axis=1)
    qvals[counts == 0] = np.nan
    return Fold(counts, mean.astype(np.float32), m2.astype(np.float32), hists,
                qvals.astype(np.float32))


@dataclasses.dataclass
class Merged:
    participants: int
    count: int
    mean: float | None
    var: float | None
    hist: list[int]
    qv: np.ndarray
    qw: np.ndarray

    def quantile(self, q: float) -> float | None:
        if self.qv.size == 0 or not self.qw[-1] > 0:
            return None
        target = min(max(float(q), 0.0), 1.0) * float(self.qw[-1])
        i = min(int(np.searchsorted(self.qw, target, side="left")), len(self.qv) - 1)
        while i > 0 and not np.isfinite(self.qv[i]):
            i -= 1
        return float(self.qv[i])


def merge(f: Fold, quantile_k: int, dtype=np.float64) -> Merged:
    """Pool every vehicle's sketch: exact counts, moments summed in
    ``dtype`` (float64 for the reference), and the weighted union of the
    quantile summaries (each of a vehicle's K values stands for count / K
    of its samples), its weights summed in ``dtype`` too."""
    c = f.counts.astype(dtype)
    total = c.sum(dtype=dtype)
    mean = var = None
    if f.counts.sum() > 0:
        mu = f.means.astype(dtype)
        m = (c * mu).sum(dtype=dtype) / total
        m2 = f.m2s.astype(dtype).sum(dtype=dtype) + (c * (mu - m) * (mu - m)).sum(dtype=dtype)
        mean, var = float(m), float(m2 / total)
    vals = f.qvals.reshape(-1)
    w = np.repeat(f.counts.astype(np.float32) / np.float32(quantile_k), quantile_k)
    w = np.where(np.isnan(vals), np.float32(0.0), w).astype(np.float32)
    order = np.argsort(vals, kind="stable")
    return Merged(
        participants=int(np.count_nonzero(f.counts)),
        count=int(f.counts.sum()),
        mean=mean,
        var=var,
        hist=[int(v) for v in f.hists.sum(axis=0)],
        qv=vals[order],
        qw=np.cumsum(w[order].astype(dtype), dtype=dtype).astype(np.float64),
    )


#: the precision one step below the configuration's float32, for controls
BF16 = ml_dtypes.bfloat16


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round f32 values to bfloat16 (nearest, ties to even) and back: the
    precision one step below the configuration's, for the controls."""
    a = np.ascontiguousarray(x, np.float32)
    bits = a.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = bits.astype(np.uint32).view(np.float32)
    return np.where(np.isnan(a), a, out)
