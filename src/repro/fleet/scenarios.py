"""Seeded drive-cycle scenario generators for the fleet signal plane.

The paper's operational case study is streaming statistics over
fuel-consumption signals from real driving; the old simulator fed every
vehicle a hand-rolled ``constant(0.01 * (i % 7))`` road-grade iterator.
These generators produce physically-flavoured, *seeded* signal streams for
the whole fleet at once — each scenario is a pure function
``(seed, client, t) -> signals`` evaluated as one jit step over the
``(n_clients, n_signals)`` plane per tick:

* ``highway``    — cruise near a per-vehicle set speed with slow speed and
                   road-grade oscillation;
* ``urban``      — stop-go duty cycles: accelerate, brake, idle at lights;
* ``idle``       — cold idle: stationary, warming engine, idle fuel burn;
* ``mixed``      — every vehicle seeded into one of the above regimes
                   (the realistic fleet default for analytics);
* ``road-grade`` — the legacy constant per-vehicle grade (exactly
                   ``0.01 * (i % 7)``), time-invariant: the simulator's
                   default, preserving the fault-free == lossy aggregate
                   property that the resiliency tests pin down;
* ``linear-road`` — Linear Road's position reports (VLDB 2004): trips
                   on L expressways with jams and accidents, in columns
                   of their own (`repro.fleet.linear_road.COLUMNS`),
                   every value an integer computed in integer arithmetic.

Determinism and row stability: per-client randomness is derived with
``fold_in(key(seed), client_index)`` and per-tick noise with a further
``fold_in(·, t)``, so the same (seed, i) yields the same stream at any
fleet size — a vehicle joining mid-experiment never perturbs existing
rows (`FleetSignalPlane.add_client` relies on this).

`scripted_brokers` renders the same streams through the legacy
per-vehicle `ScriptedSignalBroker` path; the parity tests prove the two
pipelines are payload-indistinguishable.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.signals import FleetSignalPlane, ScriptedSignalBroker
from repro.fleet import linear_road as lr

#: canonical signal names every scenario publishes, column order fixed
SIGNALS: tuple[str, ...] = (
    "Vehicle.Speed",          # km/h
    "Vehicle.FuelRate",       # L/h
    "Vehicle.RoadGrade",      # dimensionless slope
    "Engine.Temperature",     # deg C
)

_HIGHWAY, _URBAN, _IDLE = 0, 1, 2

#: regime mix of the ``mixed`` fleet
_MIX = (0.45, 0.35, 0.20)

SCENARIOS = ("road-grade", "highway", "urban", "idle", "mixed", "linear-road")


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named, seeded drive-cycle family. `step_fn(n)` is the scenario's
    pure jax step — a traceable `t -> (n, len(SIGNALS))` float32 matrix —
    shared verbatim by both plane implementations, so the single-host and
    the sharded plane are bit-for-bit identical by construction.
    `series(n)` wraps it for the host plane (jit + numpy)."""

    name: str
    seed: int = 0
    #: the scenario's columns: `SIGNALS`, or Linear Road's own
    signals: tuple[str, ...] | None = None
    #: Linear Road's L (``linear-road`` only)
    expressways: int = 2

    def __post_init__(self):
        if self.signals is None:
            own = lr.COLUMNS if self.name == "linear-road" else SIGNALS
            object.__setattr__(self, "signals", own)

    def step_fn(self, n_clients: int) -> Callable[[jax.Array], jax.Array]:
        if self.name == "road-grade":
            return _constant_road_grade_step(n_clients)
        if self.name == "linear-road":
            return _linear_road_step(n_clients, self.seed, self.expressways)
        if self.name not in SCENARIOS:
            raise ValueError(
                f"unknown scenario {self.name!r}; pick one of {SCENARIOS}"
            )
        return _drive_cycle_step(self.name, n_clients, self.seed)

    def series(self, n_clients: int) -> Callable[[int], np.ndarray]:
        if self.name == "road-grade":
            # host fast path: the step is constant in t, so the hot tick
            # returns one cached numpy array — no jit dispatch, no
            # device->host copy (same bits as the sharded step, which
            # jnp.asarrays this very array)
            vals = _constant_road_grade_values(n_clients)
            return lambda t: vals
        step = jax.jit(self.step_fn(n_clients))

        def series(t: int) -> np.ndarray:
            return np.asarray(step(jnp.int32(t)))

        return series

    def plane(self, n_clients: int, *, history: int = 256) -> FleetSignalPlane:
        return FleetSignalPlane(
            self.signals,
            self.series(n_clients),
            history=history,
            grow_fn=self.series,
        )

    def sharded_plane(
        self, n_clients: int, *, history: int = 256, mesh=None
    ) -> "ShardedSignalPlane":
        """The same scenario over a device-sharded plane: the per-tick
        step is jit'd once with in/out shardings over a client-axis mesh
        (`repro.sharding.fleet`), so each device advances only its rows."""
        from repro.core.plane_sharded import ShardedSignalPlane

        return ShardedSignalPlane(
            self.signals,
            n_clients,
            self.step_fn,
            history=history,
            mesh=mesh,
        )


#: plane implementations `build_plane` can select
PLANES = ("host", "sharded")


def build_plane(
    name: str,
    n_clients: int,
    seed: int = 0,
    *,
    history: int = 256,
    plane: str = "host",
    mesh=None,
    expressways: int = 2,
) -> FleetSignalPlane:
    """The one-liner the simulator uses. ``plane`` picks the single-host
    columnar plane (default) or the device-sharded plane."""
    scen = Scenario(name, seed, expressways=expressways)
    if plane == "host":
        return scen.plane(n_clients, history=history)
    if plane == "sharded":
        return scen.sharded_plane(n_clients, history=history, mesh=mesh)
    raise ValueError(f"unknown plane {plane!r}; pick one of {PLANES}")


# --------------------------------------------------------------------- #
# the legacy constant default                                            #
# --------------------------------------------------------------------- #
def _constant_road_grade_values(n: int) -> np.ndarray:
    """Time-invariant per-vehicle signals; `Vehicle.RoadGrade` reproduces
    the historical ``constant(0.01 * (i % 7))`` exactly. Constant in t so
    runs whose rounds consume different tick counts (lossy vs fault-free)
    still see identical payload inputs."""
    i = np.arange(n, dtype=np.float32)
    grade = np.float32(0.01) * (i % np.float32(7))
    speed = np.full(n, 80.0, np.float32)
    fuel = (0.6 + 0.04 * speed + 60.0 * np.maximum(grade, 0.0)).astype(np.float32)
    temp = np.full(n, 90.0, np.float32)
    return np.stack([speed, fuel, grade, temp], axis=1).astype(np.float32)


def _constant_road_grade_step(n: int) -> Callable[[jax.Array], jax.Array]:
    vals = _constant_road_grade_values(n)

    def step(t: jax.Array) -> jax.Array:
        return jnp.asarray(vals)

    return step


# --------------------------------------------------------------------- #
# drive cycles: one pure step for the whole fleet                        #
# --------------------------------------------------------------------- #
def _drive_cycle_step(
    name: str, n: int, seed: int
) -> Callable[[jax.Array], jax.Array]:
    """The scenario's pure per-tick function, `t -> (n, n_signals)` f32.

    Everything — per-client keys included — is computed *inside* the
    returned function from the scalar seed, so the function carries no
    captured device buffers: the host plane jits it plain, the sharded
    plane jits the identical function with client-axis in/out shardings
    (every op is elementwise per row, so partitioning inserts no
    collectives), and the two evaluate bit-for-bit the same."""

    def step(t: jax.Array) -> jax.Array:
        base = jax.random.PRNGKey(seed)
        idx = jnp.arange(n, dtype=jnp.uint32)
        ckeys = jax.vmap(lambda i: jax.random.fold_in(base, i))(idx)
        u = jax.vmap(lambda k: jax.random.uniform(k, (6,)))(ckeys)  # (n, 6)

        if name == "mixed":
            c0, c1 = _MIX[0], _MIX[0] + _MIX[1]
            regime = jnp.where(
                u[:, 0] < c0, _HIGHWAY, jnp.where(u[:, 0] < c1, _URBAN, _IDLE)
            )
        else:
            regime = jnp.full(
                (n,),
                {"highway": _HIGHWAY, "urban": _URBAN, "idle": _IDLE}[name],
                jnp.int32,
            )

        cruise = 95.0 + 25.0 * u[:, 1]        # highway set speed, km/h
        peak = 28.0 + 24.0 * u[:, 1]          # urban peak between stops
        hw_period = 40.0 + 40.0 * u[:, 2]     # highway oscillation, ticks
        ub_period = 8.0 + 10.0 * u[:, 2]      # urban stop-go cycle, ticks
        phase = 2.0 * jnp.pi * u[:, 3]
        grade0 = 0.06 * (u[:, 4] - 0.5)
        noise = 0.3 + 0.7 * u[:, 5]

        tf = t.astype(jnp.float32)
        tkeys = jax.vmap(lambda k: jax.random.fold_in(k, t))(ckeys)
        eps = jax.vmap(lambda k: jax.random.normal(k, (2,)))(tkeys)  # (n, 2)

        # highway: cruise + slow sinusoid + noise
        v_hw = cruise + 8.0 * jnp.sin(2.0 * jnp.pi * tf / hw_period + phase)
        v_hw = v_hw + noise * eps[:, 0]
        # urban: duty cycle — moving 60% of the cycle, stopped at "lights"
        frac = jnp.mod(tf / ub_period + phase / (2.0 * jnp.pi), 1.0)
        moving = frac < 0.6
        v_ub = jnp.where(
            moving,
            peak * jnp.sin(jnp.pi * frac / 0.6) + 0.5 * noise * eps[:, 0],
            0.0,
        )
        speed = jnp.select(
            [regime == _HIGHWAY, regime == _URBAN], [v_hw, v_ub], 0.0
        )
        speed = jnp.maximum(speed, 0.0)

        grade_osc = 0.02 * jnp.sin(2.0 * jnp.pi * tf / (3.0 * hw_period) + 2.0 * phase)
        grade = jnp.where(regime == _IDLE, 0.0, grade0 + grade_osc)

        # fuel rate: idle burn + speed term + uphill load + combustion noise
        fuel = (
            0.6
            + 0.04 * speed
            + 1.2 * jnp.maximum(grade, 0.0) * speed
            + 0.05 * noise * eps[:, 1]
        )
        fuel = jnp.maximum(fuel, 0.15)

        # engine warmup toward the regime's steady temperature
        ambient = jnp.where(regime == _IDLE, -5.0, 15.0)
        target = jnp.where(regime == _IDLE, 55.0, 90.0)
        tau = jnp.where(regime == _IDLE, 120.0, 40.0)
        temp = ambient + (target - ambient) * (1.0 - jnp.exp(-tf / tau))

        return jnp.stack([speed, fuel, grade, temp], axis=1).astype(jnp.float32)

    return step


# --------------------------------------------------------------------- #
# Linear Road: trips, jams and accidents in integer arithmetic           #
# --------------------------------------------------------------------- #
#: ticks of a trip: the entrance-lane report, travel, the exit-lane report
LR_TRIP = 40
#: trips per jam epoch: each (xway, dir) jam moves every 120 ticks (1 h)
LR_EPOCH_TRIPS = 3
#: segments a jam covers
LR_JAM = 15
#: vehicles are numbered in blocks; the first LR_QUEUE of each block crawl
#: in a jam's queue, one segment of the jam per block
LR_BLOCK, LR_QUEUE = 1024, 256
#: the accident pairs: vehicles 2p and 2p+1 of this range share their trip
#: timing, and one trip in LR_ACCIDENT_ODDS of a pair is a crash in the
#: queue of block 0
LR_PAIRS = (256, 512)
LR_ACCIDENT_ODDS = 8


def _linear_road_step(n: int, seed: int, expressways: int) -> Callable[[jax.Array], jax.Array]:
    """Linear Road position reports, `t -> (n, 6)` f32 in `lr.COLUMNS`
    order; vehicle v's row is a closed form of (seed, v, t).

    Each vehicle makes back-to-back trips of `LR_TRIP` ticks, shifted by a
    seeded phase: an entrance-lane report, travel in one lane (1-3), an
    exit-lane report, then the next trip's entrance at the next tick, so
    every vehicle reports every tick (the peak hour's steady state). Each
    trip draws its expressway, direction, lane and speeds. Each (xway,
    dir) has a jam of `LR_JAM` segments per epoch. A free trip drives at
    45-75 mph, through a jam at 20-35 mph. A queue trip crawls at 1-3
    mph inside its block's jam segment for the whole trip, so a jam's
    occupancy is a share of the fleet at any fleet size. An accident trip
    is a queue trip of a pair that stops at one position for 3-12 ticks.
    A reported speed is the one that carries the vehicle to its next
    position: ``x(tau + 1) = x(tau) + 44 * speed(tau)`` feet, in the
    direction's own coordinate (position 527,999 - x westbound)."""

    def step(t: jax.Array) -> jax.Array:
        v = jnp.arange(n, dtype=jnp.int32)
        pair = (v >= LR_PAIRS[0]) & (v < LR_PAIRS[1])
        who = jnp.where(pair, v - v % 2, v)
        s = t + (lr.draw(seed, who, 1) % LR_TRIP).astype(jnp.int32)
        k, tau = s // LR_TRIP, s % LR_TRIP
        accident = pair & (lr.draw(seed, who, k, 2) % LR_ACCIDENT_ODDS == 0)
        queue = (v % LR_BLOCK < LR_QUEUE) | accident
        trip = jnp.where(accident, who, v)

        def pick(salt, lo, hi):  # a whole number in [lo, hi] per trip
            return lo + (lr.draw(seed, trip, k, salt) % (hi - lo + 1)).astype(jnp.int32)

        xway = pick(3, 0, expressways - 1)
        dirn = pick(4, 0, 1)
        lane = pick(5, 1, 3)
        j0 = (lr.draw(seed, xway, dirn, k // LR_EPOCH_TRIPS, 6)
              % (lr.SEGMENTS - LR_JAM + 1)).astype(jnp.int32)
        start = pick(7, 0, 2**30)

        # queue and accident trips, inside one jam segment
        vq = pick(8, 1, 3)
        dq = lr.FT_PER_MPH_TICK * vq
        qseg = j0 + (v // LR_BLOCK) % LR_JAM
        stop = pick(9, 3, 12)
        tau_s = pick(10, 1, LR_TRIP - 2 - 12) + (12 - stop)
        halted = accident & (tau >= tau_s) & (tau < tau_s + stop)
        moved = jnp.where(accident, jnp.minimum(tau, tau_s) + jnp.maximum(tau - tau_s - stop, 0),
                          tau)
        xq = qseg * lr.SEGMENT_FT + start % (lr.SEGMENT_FT - dq * (LR_TRIP - 1)) + dq * moved
        speed_q = jnp.where(halted, 0, vq)

        # free trips: fast, slowed through the jam [a, b)
        vf, vs = pick(11, 45, 75), pick(12, 20, 35)
        df, dz = lr.FT_PER_MPH_TICK * vf, lr.FT_PER_MPH_TICK * vs
        a, b = j0 * lr.SEGMENT_FT, (j0 + LR_JAM) * lr.SEGMENT_FT
        x0 = start % (lr.ROAD_FT - df * (LR_TRIP - 1))
        t1 = jnp.where(x0 >= a, 0, (a - x0 + df - 1) // df)  # ticks to reach the jam
        x1 = x0 + df * t1
        t2 = jnp.where(x1 >= b, 0, (b - x1 + dz - 1) // dz)  # ticks inside it
        x2 = x1 + dz * t2
        xf = jnp.where(tau <= t1, x0 + df * tau,
                       jnp.where(tau <= t1 + t2, x1 + dz * (tau - t1), x2 + df * (tau - t1 - t2)))
        speed_f = jnp.where((tau >= t1) & (tau < t1 + t2), vs, vf)

        x = jnp.where(queue, xq, xf)
        speed = jnp.where(queue, speed_q, speed_f)
        lane = jnp.where(tau == 0, lr.ENTRY_LANE, jnp.where(tau == LR_TRIP - 1, lr.EXIT_LANE, lane))
        pos = jnp.where(dirn == 0, x, lr.ROAD_FT - 1 - x)
        cols = (speed, xway, dirn, lane, pos // lr.SEGMENT_FT, pos)
        return jnp.stack(cols, axis=1).astype(jnp.float32)

    return step


# --------------------------------------------------------------------- #
# legacy-path adapters (parity testing, per-vehicle scripting)           #
# --------------------------------------------------------------------- #
def scenario_trace(
    scenario: Scenario, n_clients: int, n_ticks: int
) -> np.ndarray:
    """Materialize `(n_ticks, n_clients, n_signals)` of the scenario —
    tick 0 is the plane's initial state."""
    series = scenario.series(n_clients)
    return np.stack([series(t) for t in range(n_ticks)], axis=0)


def scripted_brokers(
    scenario: Scenario, n_clients: int, n_ticks: int
) -> list[ScriptedSignalBroker]:
    """The same streams through the legacy per-vehicle iterator path.
    Broker i's iterator for signal j yields the identical float32 values
    the plane's row i column j takes at ticks 0..n_ticks-1 (then holds)."""
    trace = scenario_trace(scenario, n_clients, n_ticks)
    return [
        ScriptedSignalBroker(
            {
                name: iter([float(v) for v in trace[:, i, j]])
                for j, name in enumerate(scenario.signals)
            }
        )
        for i in range(n_clients)
    ]
