"""The plain reference of the ``linear-road-L2`` configuration: Linear Road
(Arasu et al., VLDB 2004) at L = 2, in numpy, importing nothing of the
program under test.

Two parts:

* the reports: what each vehicle reports at each 30 s tick, written out
  from the scenario's definition, in int64 arithmetic, every value an
  integer. Each vehicle makes back-to-back trips of 40 ticks with a
  seeded phase: an entrance-lane report, travel in one lane, an
  exit-lane report. A trip draws its expressway, direction, lane and
  speeds. Each (xway, dir) has a jam of 15 segments that moves every 120
  ticks. Vehicles 0-255 of every 1,024 crawl at 1-3 mph inside one
  segment of the jam; the pairs among vehicles 256-511 sometimes crawl
  there together and stop at one position for 3-12 ticks (an accident);
  everyone else drives at 45-75 mph, at 20-35 mph while inside the jam.
  A vehicle moves by 44 feet per mph of the speed it reported, tick by
  tick. All vehicles are always online (`churn`);
* `Replay`: Linear Road's continuous queries over those reports, tick by
  tick, with integer sums: each segment's count (distinct vehicles of
  minute m-1) and LAV (the floor of the mean speed over the reports of
  minutes m-5 .. m-1: a mean weighted by reports, where the paper
  averages per-vehicle averages per minute), stopped vehicles (the same
  xway, dir, lane and position in 4 reports running) and accidents (2 or
  more stopped at one position), and per vehicle the notices, alerts,
  pending tolls, charges and balances. It asserts that no toll or balance
  reaches 2**31. `expenditure` rebuilds the historical toll table.

`signal_at`, `observed`, `gap` and `to_bf16` serve the checks every
configuration shares (the ring and latest-value reads). The control
(``lower``) replays with the reports rounded to bfloat16, as a ring one
precision step below the configuration's float32 would hold them.
"""
from __future__ import annotations

import numpy as np

from reference import gap, to_bf16  # noqa: F401  (the shared checks call them here)

#: column order of the signal ring
SIGNALS = ("Vehicle.Speed", "LR.XWay", "LR.Dir", "LR.Lane", "LR.Seg", "LR.Pos")
#: this configuration's L: the reference is this configuration's own
EXPRESSWAYS = 2

SEGMENTS, SEGMENT_FT = 100, 5280
ROAD_FT = SEGMENTS * SEGMENT_FT
FT_PER_MPH_TICK = 44
ENTRY_LANE, EXIT_LANE = 0, 4
TRIP, EPOCH_TRIPS, JAM = 40, 3, 15
BLOCK, QUEUE, PAIRS, ACCIDENT_ODDS = 1024, 256, (256, 512), 8
LAV_TICKS, DAYS = 10, 69
MASK32 = 0xFFFFFFFF


# --------------------------------------------------------------------- #
# the reports                                                           #
# --------------------------------------------------------------------- #
def _fmix(x):
    x = np.asarray(x, np.uint64) & MASK32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & MASK32
    x ^= x >> 15
    x = (x * 0x846CA68B) & MASK32
    return x ^ (x >> 16)


def _hash(seed: int, *words) -> np.ndarray:
    """A 32-bit draw from the seed and the words (int arrays, broadcast),
    as uint64 values below 2**32."""
    x = _fmix(np.uint64((seed & MASK32) ^ 0x9E3779B9))
    x = _fmix(x ^ np.uint64((seed >> 32) & MASK32))
    for w in words:
        x = _fmix(x ^ (np.asarray(w, np.int64).astype(np.uint64) & MASK32))
    return x


def _who(v: np.ndarray) -> np.ndarray:
    """The number a vehicle's draws go by: a pair's even member's."""
    pair = (v >= PAIRS[0]) & (v < PAIRS[1])
    return np.where(pair, v // 2 * 2, v)


def _phase(seed: int, who: np.ndarray) -> np.ndarray:
    """Where in its trip each vehicle is at tick 0 (a pair shares it)."""
    return (_hash(seed, who, 1) % TRIP).astype(np.int64)


def _trips(seed: int, v: np.ndarray, who: np.ndarray, k: np.ndarray) -> dict:
    """What trip ``k`` of each vehicle ``v`` draws, and where it enters."""
    pair = (v >= PAIRS[0]) & (v < PAIRS[1])
    crash = pair & (_hash(seed, who, k, 2) % ACCIDENT_ODDS == 0)
    trip = np.where(crash, who, v)

    def between(salt, lo, hi):
        return lo + (_hash(seed, trip, k, salt) % (hi - lo + 1)).astype(np.int64)

    tr = {"xway": between(3, 0, EXPRESSWAYS - 1), "dir": between(4, 0, 1),
          "lane": between(5, 1, 3), "crawl": between(8, 1, 3), "halt": between(9, 3, 12),
          "fast": between(11, 45, 75), "slow": between(12, 20, 35),
          "queue": (v % BLOCK < QUEUE) | crash, "crash": crash}
    tr["at"] = between(10, 1, TRIP - 14) + 12 - tr["halt"]
    jam = (_hash(seed, tr["xway"], tr["dir"], k // EPOCH_TRIPS, 6)
           % (SEGMENTS - JAM + 1)).astype(np.int64)
    tr["lo"], tr["hi"] = jam * SEGMENT_FT, (jam + JAM) * SEGMENT_FT
    start = between(7, 0, 2**30)
    crawl_seg = jam + (v // BLOCK) % JAM
    tr["entry"] = np.where(
        tr["queue"],
        crawl_seg * SEGMENT_FT + start % (SEGMENT_FT - FT_PER_MPH_TICK * tr["crawl"] * (TRIP - 1)),
        start % (ROAD_FT - FT_PER_MPH_TICK * tr["fast"] * (TRIP - 1)))
    return tr


def _speed(tr: dict, x: np.ndarray, tau: np.ndarray) -> np.ndarray:
    """The speed a vehicle reports at ``x`` (its direction's coordinate)
    and ``tau``, which carries it to its next position."""
    stopped = tr["crash"] & (tau >= tr["at"]) & (tau < tr["at"] + tr["halt"])
    queue = np.where(stopped, 0, tr["crawl"])
    drive = np.where((x >= tr["lo"]) & (x < tr["hi"]), tr["slow"], tr["fast"])
    return np.where(tr["queue"], queue, drive)


def _columns(tr: dict, x: np.ndarray, tau: np.ndarray) -> np.ndarray:
    lane = np.where(tau == 0, ENTRY_LANE, np.where(tau == TRIP - 1, EXIT_LANE, tr["lane"]))
    pos = np.where(tr["dir"] == 0, x, ROAD_FT - 1 - x)
    return np.stack([_speed(tr, x, tau), tr["xway"], tr["dir"], lane,
                     pos // SEGMENT_FT, pos], axis=1)


def reports(seed: int, rows, ticks) -> np.ndarray:
    """(n, 6) int64: vehicle ``rows[i]`` at tick ``ticks[i]`` (broadcast),
    columns as `SIGNALS`: its trip walked from the entrance tick by tick."""
    v, t = np.broadcast_arrays(np.asarray(rows, np.int64), np.asarray(ticks, np.int64))
    v, t = v.reshape(-1), t.reshape(-1)
    who = _who(v)
    k, tau = np.divmod(t + _phase(seed, who), TRIP)
    tr = _trips(seed, v, who, k)
    x = tr["entry"].copy()
    for step in range(TRIP - 1):
        x = np.where(step < tau, x + FT_PER_MPH_TICK * _speed(tr, x, step), x)
    return _columns(tr, x, tau)


def signal_at(scenario: str, seed: int, rows, ticks) -> np.ndarray:
    """(n, 6) f32: vehicle ``rows[i]`` at tick ``ticks[i]``."""
    _own(scenario)
    return reports(seed, rows, ticks).astype(np.float32)


class _AllOnline:
    """Every vehicle reports at every tick from tick 0 on."""

    def __init__(self, n: int):
        self.n = n

    def online(self, rows, ticks) -> np.ndarray:
        ticks = np.asarray(ticks)
        return np.broadcast_to((ticks >= 0)[:, None], (len(ticks), len(np.asarray(rows)))).copy()

    def online_pairs(self, rows, ticks) -> np.ndarray:
        return np.asarray(ticks) >= 0

    def count(self, t: int) -> int:
        return self.n


def churn(seed: int, n: int, p_leave: float, p_return: float, t_last: int) -> _AllOnline:
    if p_leave or p_return:
        raise ValueError("Linear Road's vehicles never go offline")
    return _AllOnline(n)


def observed(scenario: str, seed: int, masks, rows, ticks) -> np.ndarray:
    """(len(ticks), len(rows), 6) f32 as the ring should hold it."""
    _own(scenario)
    ticks = np.asarray(ticks)
    vals = reports(seed, np.asarray(rows)[None, :], np.maximum(ticks, 0)[:, None])
    vals = vals.reshape(len(ticks), -1, len(SIGNALS)).astype(np.float32)
    return np.where(masks.online(rows, ticks)[:, :, None], vals, np.float32(np.nan))


def _own(scenario: str) -> None:
    if scenario != "linear-road":
        raise ValueError(f"this reference is Linear Road's, not {scenario!r}'s")


def expenditure(seed: int, rows, xways, days) -> np.ndarray:
    """The historical toll table's entries: what vehicle ``rows[i]`` spent
    on ``xways[i]`` on ``days[i]`` (1 .. 69), drawn from 0-99."""
    return (_hash(seed, rows, days, xways, 0x5EED7011) % 100).astype(np.int64)


class Fleet:
    """The whole fleet's reports, tick after tick from tick 0: each trip
    drawn when it starts, each vehicle moved by its last speed."""

    def __init__(self, seed: int, n: int):
        self.seed, self.v = seed, np.arange(n, dtype=np.int64)
        self.t = 0
        self.who = _who(self.v)
        self.phase = _phase(seed, self.who)
        self.k = self.phase // TRIP
        self.trip = _trips(seed, self.v, self.who, self.k)
        r = reports(seed, self.v, 0)
        self.x = np.where(r[:, 2] == 0, r[:, 5], ROAD_FT - 1 - r[:, 5])
        self.last = r

    def next(self) -> np.ndarray:
        """The reports of the next tick, (n, 6) int64."""
        self.t += 1
        k, tau = np.divmod(self.t + self.phase, TRIP)
        self.x = self.x + FT_PER_MPH_TICK * self.last[:, 0]
        new = k != self.k
        if new.any():
            fresh = _trips(self.seed, self.v[new], self.who[new], k[new])
            for key, val in fresh.items():
                self.trip[key][new] = val
            self.x[new] = fresh["entry"]
        self.k = k
        self.last = _columns(self.trip, self.x, tau)
        return self.last


# --------------------------------------------------------------------- #
# the continuous queries                                                #
# --------------------------------------------------------------------- #
class Replay:
    """Linear Road's segment statistics, accidents, notices, tolls and
    balances over ``n`` vehicles, fed one tick's reports at a time from
    tick 0 (`feed`); the queries run from tick 1 on.

    A notice is ``(tick, segment, toll, lav, count, alert)``: the tick of
    the crossing, the segment entered, its toll, LAV and count, and the
    segment of the nearest accident within 4 downstream (-1: none)."""

    def __init__(self, n: int, expressways: int = EXPRESSWAYS):
        self.n, self.keys = n, 2 * expressways * SEGMENTS
        self.seen: dict[int, np.ndarray] = {}      # tick -> (n, 6) reports
        self.keys_at: dict[int, np.ndarray] = {}   # tick -> (n,) segment keys
        self.places: dict[int, np.ndarray] = {}    # tick -> (n,) positions, as `place`
        self.sums: dict[int, np.ndarray] = {}      # tick -> (keys, 2) speed sum, reports
        self.balance = np.zeros(n, np.int64)
        self.last_charge = np.full(n, -1, np.int64)
        self.pending = np.zeros(n, np.int64)
        self.notice = np.zeros((n, 6), np.int64)
        self.notice[:, 0] = self.notice[:, 5] = -1

    @staticmethod
    def key(r: np.ndarray) -> np.ndarray:
        """The (xway, dir, seg) of each report as one number."""
        return (r[:, 1] * 2 + r[:, 2]) * SEGMENTS + r[:, 4]

    @staticmethod
    def place(r: np.ndarray) -> np.ndarray:
        """The (xway, dir, lane, pos) of each report as one number."""
        return ((r[:, 1] * 2 + r[:, 2]) * 5 + r[:, 3]) * ROAD_FT + r[:, 5]

    def stats(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """Each segment's (count, lav) for the minute of tick ``t``."""
        first = t // 2 * 2
        tot = sum((self.sums[u] for u in range(first - LAV_TICKS, first) if u in self.sums),
                  np.zeros((self.keys, 2), np.int64))
        lav = np.zeros(self.keys, np.int64)
        np.floor_divide(tot[:, 0], tot[:, 1], out=lav, where=tot[:, 1] > 0)
        count = np.zeros(self.keys, np.int64)
        a, b = self.keys_at.get(first - 2), self.keys_at.get(first - 1)
        if a is not None:  # a vehicle counts once per segment it reported in
            count += np.bincount(a, minlength=self.keys)
        if b is not None:
            count += np.bincount(b[b != a] if a is not None else b, minlength=self.keys)
        return count, lav

    def accidents(self, t: int) -> np.ndarray:
        """(keys,) bool: the segments that hold an accident at tick ``t``."""
        flag = np.zeros(self.keys, bool)
        last = [self.places.get(u) for u in range(t - 3, t + 1)]
        if any(p is None for p in last):
            return flag
        stopped = (last[0] == last[3]) & (last[1] == last[3]) & (last[2] == last[3])
        where, held = np.unique(last[3][stopped], return_counts=True)
        for place in where[held >= 2]:
            rest, pos = divmod(int(place), ROAD_FT)
            flag[rest // 5 * SEGMENTS + pos // SEGMENT_FT] = True
        return flag

    def nearest(self, flag: np.ndarray) -> np.ndarray:
        """(keys,): the nearest accident segment within 4 downstream of
        each segment (itself included), -1 where none."""
        out = np.full(self.keys, -1, np.int64)
        for key in range(self.keys):
            xd, seg = divmod(key, SEGMENTS)
            ahead = range(seg, min(seg + 5, SEGMENTS)) if xd % 2 == 0 \
                else range(seg, max(seg - 5, -1), -1)
            for s in ahead:
                if flag[xd * SEGMENTS + s]:
                    out[key] = s
                    break
        return out

    def feed(self, t: int, r: np.ndarray) -> None:
        """Tick ``t``'s reports, (n, 6) as `reports` gives them."""
        k = self.key(r)
        self.seen[t], self.keys_at[t], self.places[t] = r, k, self.place(r)
        # float64 holds these integer sums exactly (below 2**53)
        speed_sum = np.bincount(k, weights=r[:, 0], minlength=self.keys).astype(np.int64)
        self.sums[t] = np.stack([speed_sum, np.bincount(k, minlength=self.keys)], axis=1)
        for old in (self.seen, self.keys_at, self.places, self.sums):
            old.pop(t - 12, None)
        if t == 0:
            return
        count, lav = self.stats(t)
        alert = self.nearest(self.accidents(t))
        toll = np.where((lav < 40) & (count > 50) & (alert < 0), 2 * (count - 50) ** 2, 0)
        assert toll.max() < 2**31, "a toll passed 2**31"
        prev = self.seen[t - 1]
        cross = (k != self.keys_at[t - 1]) | (r[:, 3] == ENTRY_LANE)
        # leaving other than by the exit lane pays the notified toll
        pay = cross & (prev[:, 3] != EXIT_LANE) & (self.pending > 0)
        self.balance[pay] += self.pending[pay]
        self.last_charge[pay] = t
        assert self.balance.max(initial=0) < 2**31, "a balance passed 2**31"
        self.pending[cross] = 0
        told = cross & (r[:, 3] != EXIT_LANE)
        kt = k[told]
        self.pending[told] = toll[kt]
        self.notice[told] = np.stack([np.full(len(kt), t), r[told, 4], toll[kt], lav[kt],
                                      count[kt], alert[kt]], axis=1)


def replay(seed: int, n: int, asks, lower: bool = False) -> dict:
    """The toll state of each asked ``(row, tick)``: ``{"balance",
    "last_charge", "notice"}`` after that tick's queries, replayed from
    tick 0 over the whole fleet. ``lower`` puts the control in the
    program's place: reports rounded to bfloat16 before the queries."""
    asks = sorted(set(asks), key=lambda a: a[1])
    out: dict = {}
    if not asks:
        return out
    fleet, rp = Fleet(seed, n), Replay(n)
    j = 0
    for t in range(asks[-1][1] + 1):
        r = fleet.last if t == 0 else fleet.next()
        if lower:
            r = to_bf16(r.astype(np.float32)).astype(np.int64)
        rp.feed(t, r)
        while j < len(asks) and asks[j][1] == t:
            row = asks[j][0]
            out[asks[j]] = {"balance": int(rp.balance[row]),
                            "last_charge": int(rp.last_charge[row]),
                            "notice": tuple(int(v) for v in rp.notice[row])}
            j += 1
    return out
