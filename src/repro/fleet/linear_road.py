"""Linear Road's variable tolling, on the device.

Linear Road (A. Arasu, M. Cherniack, E. Galvez, D. Maier, A. Maskey,
E. Ryvkina, M. Stonebraker, R. Tibbetts, "Linear Road: A Stream Data
Management Benchmark", VLDB 2004) tolls L expressways of 100 one-mile
segments a direction. Every vehicle reports (time, vehicle, speed, xway,
lane, dir, seg, pos) every 30 s; here one plane tick is one such round,
and the ``linear-road`` scenario (`repro.fleet.scenarios`) writes the
reports into the signal ring as the columns `COLUMNS`. After each plane
step `Tolls.step` runs one jitted program, `toll_step`, over the ring on
the device, sharded over the ``clients`` mesh like the ring:

* segment statistics, grouped by (xway, dir, seg) and minute (2 ticks):
  ``count``, the distinct vehicles that reported in minute m-1, and
  ``LAV``, the floor of the mean speed over the reports of minutes
  m-5 .. m-1. The paper averages per-vehicle averages per minute; this
  reads it as a mean weighted by reports, in integer sums;
* stopped vehicles: the same (xway, dir, lane, pos) in the last 4
  reports; an accident is 2 or more stopped vehicles at one such
  position, and clears once one of them moves (a group-by on position);
* notices: a vehicle that enters a segment (its segment changes, or it
  comes in by the entrance lane) outside the exit lane is told the
  segment's toll, ``2 * (count - 50) ** 2`` where LAV < 40, count > 50
  and no accident lies within 4 segments downstream, else 0, and is
  alerted to the nearest such accident;
* charging: the notified toll is charged when the vehicle's next
  crossing leaves the segment other than by the exit lane, that is when
  its report before the crossing was not in the exit lane.

Tolls, balances and every count are int32. The state (balance, tick of
the last charge, the pending toll and the latest notice per vehicle) and
the historical toll table stay on the device; reads gather one vehicle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.sharding import fleet as fleet_sharding

#: one-mile segments per direction, and positions in feet
SEGMENTS = 100
SEGMENT_FT = 5280
ROAD_FT = SEGMENTS * SEGMENT_FT  # positions 0 .. 527,999
#: feet a vehicle covers in one 30 s tick at 1 mph
FT_PER_MPH_TICK = 44
#: lanes: 0 is the entrance ramp, 1-3 travel, 4 the exit ramp
ENTRY_LANE, EXIT_LANE, LANES = 0, 4, 5

#: the plane's columns under the ``linear-road`` scenario, in ring order
COLUMNS = ("Vehicle.Speed", "LR.XWay", "LR.Dir", "LR.Lane", "LR.Seg", "LR.Pos")
SPEED, XWAY, DIR, LANE, SEG, POS = range(len(COLUMNS))

TICKS_PER_MINUTE = 2
#: LAV's window: the five minutes before the current one
LAV_TICKS = 5 * TICKS_PER_MINUTE
#: ring slots the rules read: LAV's window, the current minute, and the
#: last 4 reports for stopped vehicles all lie in the last 12 ticks
HISTORY = LAV_TICKS + TICKS_PER_MINUTE
LAV_LIMIT, FREE_COUNT = 40, 50
STOPPED_REPORTS = 4
ACCIDENT_REACH = 4
#: days of the historical toll table (type-3 queries: day 1 .. 69)
DAYS = 69
#: fields of a notice, in the order the state keeps them
NOTICE = ("tick", "segment", "toll", "lav", "count", "alert")


# --------------------------------------------------------------------- #
# seeded integer draws, shared with the scenario's trip generator        #
# --------------------------------------------------------------------- #
def mix32(x: jax.Array) -> jax.Array:
    """A 32-bit integer finaliser (xorshift-multiply): uint32 -> uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def draw(seed: int, *words) -> jax.Array:
    """A uint32 draw from the seed and ``words`` (int32 or uint32 arrays,
    broadcast together), each folded in by xor and `mix32`."""
    x = mix32(jnp.uint32((seed & 0xFFFFFFFF) ^ 0x9E3779B9))
    x = mix32(x ^ jnp.uint32((seed >> 32) & 0xFFFFFFFF))
    for w in words:
        x = mix32(x ^ jnp.asarray(w).astype(jnp.uint32))
    return x


def history_table(seed: int, rows: jax.Array, expressways: int) -> jax.Array:
    """(expressways, DAYS, len(rows)) int32: the tolls each vehicle spent
    on each expressway on day 1 .. 69, drawn from 0-99."""
    x = jnp.arange(expressways, dtype=jnp.int32)[:, None, None]
    d = jnp.arange(1, DAYS + 1, dtype=jnp.int32)[None, :, None]
    return (draw(seed, rows[None, None, :], d, x, 0x5EED7011) % 100).astype(jnp.int32)


# --------------------------------------------------------------------- #
# the toll step                                                          #
# --------------------------------------------------------------------- #
def initial_state(capacity: int) -> dict:
    """No vehicle has paid or been notified yet (tick -1, alert -1)."""
    notice = np.zeros((len(NOTICE), capacity), np.int32)
    notice[NOTICE.index("tick")] = -1
    notice[NOTICE.index("alert")] = -1
    return {
        "balance": np.zeros(capacity, np.int32),
        "last_charge": np.full(capacity, -1, np.int32),
        "pending": np.zeros(capacity, np.int32),
        "notice": notice,
        #: crossings, tolls_charged, accidents, alerts, cumulative
        "counters": np.zeros(4, np.int32),
    }


def _reports(ring: jax.Array, t: jax.Array) -> dict:
    """The ring's reports as int32 fields, (HISTORY, capacity) each, with
    ``valid`` false where a slot holds no report (before tick 0, or NaN).
    Slot s holds tick ``t - (t - s) % H``."""
    h = ring.shape[0]
    tick = t - (t - jnp.arange(h, dtype=jnp.int32)) % h
    valid = (tick >= 0)[:, None] & ~jnp.isnan(ring[..., SPEED])
    r = jnp.where(valid[..., None], ring, 0.0).astype(jnp.int32)
    f = {name: r[..., j] for name, j in
         (("speed", SPEED), ("xway", XWAY), ("dir", DIR), ("lane", LANE),
          ("seg", SEG), ("pos", POS))}
    f["key"] = (f["xway"] * 2 + f["dir"]) * SEGMENTS + f["seg"]
    f["valid"] = valid
    f["tick"] = tick
    return f


def _at(f: dict, u: jax.Array) -> dict:
    """The reports of tick ``u`` (one of the last H ticks): (capacity,)."""
    h = f["valid"].shape[0]
    return {k: v[u % h] for k, v in f.items() if k != "tick"}


def _segment_stats(f: dict, t: jax.Array, n_keys: int):
    """(count, lav), (n_keys,) int32 each, for the current minute."""
    m2 = (t // TICKS_PER_MINUTE) * TICKS_PER_MINUTE  # the minute's first tick
    in_lav = f["valid"] & ((f["tick"] >= m2 - LAV_TICKS) & (f["tick"] < m2))[:, None]
    speed_sum = _sum_by_key(f["speed"], f["key"], in_lav, n_keys)
    reports = _sum_by_key(1, f["key"], in_lav, n_keys)
    lav = jnp.where(reports > 0, speed_sum // jnp.maximum(reports, 1), 0)
    # minute m-1: each vehicle counted once in each segment it reported in
    a, b = _at(f, m2 - 2), _at(f, m2 - 1)
    second = b["valid"] & ~(a["valid"] & (a["key"] == b["key"]))
    count = _sum_by_key(1, a["key"], a["valid"], n_keys) + _sum_by_key(1, b["key"], second, n_keys)
    return count, lav


def _sum_by_key(values, keys: jax.Array, keep: jax.Array, n_keys: int) -> jax.Array:
    """(n_keys,) int32: ``values`` summed by ``keys`` where ``keep``."""
    data = jnp.where(keep, values, 0).astype(jnp.int32).reshape(-1)
    ids = jnp.where(keep, keys, n_keys).reshape(-1)
    return jax.ops.segment_sum(data, ids, num_segments=n_keys + 1)[:n_keys]


def _accidents(f: dict, t: jax.Array, n_keys: int):
    """(accident flag per segment key (n_keys,) bool, accident positions).

    Stopped: the same (xway, dir, lane, pos) in each of the last 4
    reports. Stopped vehicles are grouped by that position with one sort;
    a position held by 2 or more is an accident."""
    place = ((f["xway"] * 2 + f["dir"]) * LANES + f["lane"]) * ROAD_FT + f["pos"]
    h = place.shape[0]
    now = place[t % h]
    stopped = f["valid"][t % h]
    for back in range(1, STOPPED_REPORTS):
        u = (t - back) % h
        stopped = stopped & f["valid"][u] & (place[u] == now)
    none = jnp.iinfo(jnp.int32).max
    s = jnp.sort(jnp.where(stopped, now, none))
    same = (s[1:] == s[:-1]) & (s[1:] != none)
    first = same & jnp.concatenate([jnp.ones(1, bool), s[1:-1] != s[:-2]])
    seg_key = (s[1:] // (LANES * ROAD_FT)) * SEGMENTS + (s[1:] % ROAD_FT) // SEGMENT_FT
    flag = jax.ops.segment_max(same.astype(jnp.int32), jnp.where(same, seg_key, n_keys),
                               num_segments=n_keys + 1)[:n_keys] > 0
    return flag, jnp.sum(first, dtype=jnp.int32)


def _nearest_downstream(flag: jax.Array, expressways: int) -> jax.Array:
    """(n_keys,) int32: the nearest segment holding an accident within
    `ACCIDENT_REACH` segments downstream (higher segments in direction 0,
    lower in direction 1), the segment itself included; -1 if none."""
    acc = flag.reshape(2 * expressways, SEGMENTS)
    east = (jnp.arange(2 * expressways) % 2 == 0)[:, None]
    seg = jnp.arange(SEGMENTS, dtype=jnp.int32)[None, :]
    near = jnp.full(acc.shape, -1, jnp.int32)
    for off in range(ACCIDENT_REACH, -1, -1):  # the nearest written last
        down = jnp.where(east, seg + off, seg - off)
        ok = (down >= 0) & (down < SEGMENTS)
        hit = ok & jnp.take_along_axis(acc, jnp.clip(down, 0, SEGMENTS - 1), axis=1)
        near = jnp.where(hit, down, near)
    return near.reshape(-1)


def toll_step(state: dict, ring: jax.Array, t: jax.Array, *, expressways: int) -> dict:
    """One tick of Linear Road's continuous queries over the ring, for
    the reports of plane tick ``t``: the state after that tick."""
    n_keys = 2 * expressways * SEGMENTS
    f = _reports(ring, t)
    count, lav = _segment_stats(f, t, n_keys)
    flag, accidents = _accidents(f, t, n_keys)
    near = _nearest_downstream(flag, expressways)
    toll = jnp.where((lav < LAV_LIMIT) & (count > FREE_COUNT) & (near < 0),
                     2 * (count - FREE_COUNT) ** 2, 0)

    cur, prev = _at(f, t), _at(f, t - 1)
    # entering a segment: it changed, or the vehicle came in by a ramp
    cross = cur["valid"] & (~prev["valid"] | (cur["key"] != prev["key"])
                            | (cur["lane"] == ENTRY_LANE))
    # leaving other than by the exit lane pays what was notified
    paid = jnp.where(cross & prev["valid"] & (prev["lane"] != EXIT_LANE), state["pending"], 0)
    notify = cross & (cur["lane"] != EXIT_LANE)
    key = jnp.clip(cur["key"], 0, n_keys - 1)
    fields = jnp.stack([jnp.broadcast_to(t, key.shape), cur["seg"], toll[key], lav[key],
                        count[key], near[key]])
    alerted = notify & (near[key] >= 0)
    return {
        "balance": state["balance"] + paid,
        "last_charge": jnp.where(paid > 0, t, state["last_charge"]),
        "pending": jnp.where(notify, toll[key], jnp.where(cross, 0, state["pending"])),
        "notice": jnp.where(notify[None, :], fields, state["notice"]),
        "counters": state["counters"] + jnp.stack([
            jnp.sum(cross, dtype=jnp.int32), jnp.sum(paid > 0, dtype=jnp.int32),
            accidents, jnp.sum(alerted, dtype=jnp.int32)]),
    }


def state_shardings(mesh) -> dict:
    """The toll state's layout: per-vehicle arrays split over ``clients``
    like the ring's rows; the counters replicated."""
    rows = fleet_sharding.mask_sharding(mesh)
    return {
        "balance": rows, "last_charge": rows, "pending": rows,
        "notice": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, fleet_sharding.CLIENT_AXIS)),
        "counters": fleet_sharding.replicated(mesh),
    }


def table_sharding(mesh):
    """The historical table, (xway, day, vehicle): vehicles split."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, None, fleet_sharding.CLIENT_AXIS))


class Tolls:
    """Linear Road's toll state for a fleet whose ring has ``capacity``
    rows, on ``mesh``, with its historical toll table drawn from ``seed``.

    `step` runs after each plane step; the reads (`balance`, `notice`,
    `expenditure`) gather one vehicle's entries on the device and copy
    only those to the host. The counters read the device state."""

    def __init__(self, capacity: int, seed: int, expressways: int, mesh=None):
        if expressways < 1:
            raise ValueError("expressways must be >= 1")
        self.capacity = int(capacity)
        self.expressways = int(expressways)
        self.mesh = mesh if mesh is not None else fleet_sharding.client_mesh(jax.devices()[:1])
        sh = state_shardings(self.mesh)
        self.state = jax.device_put(initial_state(self.capacity), sh)
        self.table = jax.jit(
            history_table, static_argnums=(0, 2), out_shardings=table_sharding(self.mesh),
        )(seed, jnp.arange(self.capacity, dtype=jnp.int32), self.expressways)
        # the step's XLA module is ``jit_toll_step``
        self._step = functools.partial(
            jax.jit(toll_step, static_argnames="expressways", out_shardings=sh,
                    donate_argnums=(0,)),
            expressways=self.expressways)
        self._balance = jax.jit(lambda s, r: jnp.stack([s["balance"][r], s["last_charge"][r]]))
        self._notice = jax.jit(lambda s, r: s["notice"][:, r])
        self._expenditure = jax.jit(lambda tab, r, x, d: tab[x, d - 1, r])

    def step(self, ring: jax.Array, t: int) -> None:
        """Run the rules over ``ring`` after plane tick ``t``. The plane's
        step is waited for first, so that the ``plane.tolls`` span holds
        the toll step's device work and no other; the step is waited for
        inside it, so that the tick's notices exist when the tick ends."""
        if ring.shape[0] < HISTORY or ring.shape[1] != self.capacity:
            raise ValueError(
                f"the toll step needs a ring of >= {HISTORY} slots and "
                f"{self.capacity} rows, got {ring.shape[:2]}")
        jax.block_until_ready(ring)
        with obs.span("plane.tolls"):
            self.state = self._step(self.state, ring, jnp.int32(t))
            jax.block_until_ready(self.state)

    # -- reads: one vehicle each ------------------------------------------ #
    def _row(self, row: int) -> jax.Array:
        if not 0 <= row < self.capacity:
            raise ValueError(f"no vehicle row {row}")
        return jnp.int32(row)

    def balance(self, row: int) -> dict:
        """Type 2: the vehicle's balance and the tick of its last charge."""
        b, last = np.asarray(self._balance(self.state, self._row(row))).tolist()
        return {"balance": b, "last_charge": last}

    def notice(self, row: int) -> dict:
        """The vehicle's latest toll notice (tick -1 before its first)."""
        return dict(zip(NOTICE, np.asarray(self._notice(self.state, self._row(row))).tolist()))

    def expenditure(self, row: int, xway: int, day: int) -> dict:
        """Type 3: what the vehicle spent on ``xway`` on ``day`` (1 is
        yesterday, 69 ten weeks ago)."""
        if not 0 <= xway < self.expressways:
            raise ValueError(f"no expressway {xway}")
        if not 1 <= day <= DAYS:
            raise ValueError(f"day must be 1..{DAYS}, got {day}")
        v = self._expenditure(self.table, self._row(row), jnp.int32(xway), jnp.int32(day))
        return {"expenditure": int(v)}

    # -- counters (device reads, off the tick path) ----------------------- #
    def _counter(self, i: int) -> int:
        return int(self.state["counters"][i])

    @property
    def crossings(self) -> int:
        """Segment crossings seen, exit-lane ones included."""
        return self._counter(0)

    @property
    def tolls_charged(self) -> int:
        """Non-zero tolls charged."""
        return self._counter(1)

    @property
    def accidents(self) -> int:
        """Accident positions, summed over ticks."""
        return self._counter(2)

    @property
    def alerts(self) -> int:
        """Notices that carried an accident alert."""
        return self._counter(3)
