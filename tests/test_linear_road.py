"""Linear Road's toll step (`repro.fleet.linear_road`) against the plain
reference kept with the chip benchmark
(``benchmarks/chip/reference_linear_road.py``, numpy only).

* seeded data at 1,024 vehicles and L = 2: the ring, every vehicle's
  notice, pending toll and balance, and the historical table agree with
  the reference at every tick for 40 ticks, and the run exercised jams,
  tolls, accidents and alerts;
* hand-built reports, one case per rule, run through the program and the
  reference alike;
* the gateway's three Linear Road reads;
* the step on 8 simulated CPU devices equals the one-device result.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import linear_road as lr
from repro.fleet.scenarios import Scenario
from repro.fleet.simulator import Backends, FleetSimulator, SimConfig
from repro.serve import FleetGateway

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
import reference_linear_road as ref  # noqa: E402

SEED, N, TICKS = 17621, 1024, 40


def make_sim(n=N, seed=SEED, mesh=None):
    return FleetSimulator(SimConfig(
        n_clients=n, seed=seed, scenario="linear-road", expressways=2, signal_history=12,
        resync_period=64, backends=Backends(plane="sharded")), mesh=mesh)


# --------------------------------------------------------------------- #
# seeded data: the program against the reference, tick by tick          #
# --------------------------------------------------------------------- #
def test_program_matches_reference_every_tick():
    sim = make_sim()
    fleet, rp = ref.Fleet(SEED, N), ref.Replay(N)
    rp.feed(0, fleet.last)
    ring = np.asarray(sim.plane.device_ring())
    assert np.array_equal(ring[0], fleet.last.astype(np.float32))
    tolled = alerted = crossed = charged = 0
    for t in range(1, TICKS + 1):
        sim.tick()
        prev, paid = fleet.last, rp.balance.copy()
        r = fleet.next()
        rp.feed(t, r)
        crossed += int(np.count_nonzero((rp.key(r) != rp.key(prev)) | (r[:, 3] == 0)))
        charged += int(np.count_nonzero(rp.balance > paid))
        ring = np.asarray(sim.plane.device_ring())
        assert np.array_equal(ring[t % lr.HISTORY], r.astype(np.float32)), t
        st = jax.device_get(sim.tolls.state)
        for name in ("balance", "last_charge", "pending"):
            assert np.array_equal(st[name], getattr(rp, name)), (t, name)
        assert np.array_equal(st["notice"].T, rp.notice), t
        tolled += int(np.count_nonzero((rp.notice[:, 0] == t) & (rp.notice[:, 2] > 0)))
        alerted += int(np.count_nonzero((rp.notice[:, 0] == t) & (rp.notice[:, 5] >= 0)))
    # the closed form the plane evaluates is the reference's walk
    assert np.array_equal(ref.reports(SEED, np.arange(N), TICKS), fleet.last)
    table = np.asarray(sim.tolls.table)
    rows, days = np.arange(N)[None, :], np.arange(1, lr.DAYS + 1)[:, None]
    for x in range(2):
        assert np.array_equal(table[x], ref.expenditure(SEED, rows, x, days))
    # the counters, and jams, tolls, charges, accidents and alerts all happened
    tolls = sim.tolls
    assert (tolls.crossings, tolls.tolls_charged, tolls.alerts) == (crossed, charged, alerted)
    assert tolled > 20 and alerted > 20 and charged > 20 and tolls.accidents > 0


def test_reports_are_integers_on_the_road():
    step = Scenario("linear-road", SEED).step_fn(N)
    assert Scenario("linear-road").signals == lr.COLUMNS
    for t in (0, 1, 39, 40, 121):
        r = np.asarray(step(jnp.int32(t)))
        assert np.array_equal(r, np.round(r))
        speed, xway, dirn, lane, seg, pos = r.T
        assert ((0 <= pos) & (pos < lr.ROAD_FT)).all() and (seg == pos // lr.SEGMENT_FT).all()
        assert set(np.unique(xway)) <= {0, 1} and set(np.unique(dirn)) <= {0, 1}
        assert set(np.unique(lane)) <= set(range(lr.LANES)) and (speed <= 75).all()


# --------------------------------------------------------------------- #
# one hand-built case per rule                                          #
# --------------------------------------------------------------------- #
V, A1, A2, CROWD = 0, 1, 2, 3
JAM_SEG = 10


def report(speed, seg, off, lane=2):
    """xway 0, direction 0 (positions increase downstream)."""
    return [speed, 0, 0, lane, seg, seg * lr.SEGMENT_FT + off]


def scene(crowd=51, crowd_speed=10, stop_seg=None, stopped_reports=4, exit_lane=False,
          ticks=7):
    """Reports by tick. ``crowd`` vehicles sit at distinct positions of
    segment 10 at ``crowd_speed``; vehicle V drives in segment 9 during
    minutes 0-1, enters segment 10 at tick 4 and leaves it at tick 6,
    into segment 11 or, with ``exit_lane``, by its exit ramp at tick 5;
    A1 and A2 stand together in ``stop_seg`` for the last
    ``stopped_reports`` reports up to tick 4, and drive far away
    otherwise."""
    out = {}
    for t in range(ticks):
        rows = [None] * (CROWD + crowd)
        if t < 4:
            rows[V] = report(10, JAM_SEG - 1, 100 + 440 * t)
        elif t == 4 or (t == 5 and not exit_lane):
            rows[V] = report(10, JAM_SEG, 50 + 440 * (t - 4))
        elif t == 5:
            rows[V] = report(10, JAM_SEG, 500, lane=lr.EXIT_LANE)
        else:
            rows[V] = report(10, 30, 7, lane=0) if exit_lane else report(10, JAM_SEG + 1, 5)
        for j, a in enumerate((A1, A2)):
            still = stop_seg is not None and 4 - stopped_reports < t <= 4
            rows[a] = report(0, stop_seg, 1234) if still else report(60, 60 + j, 2640 * (t % 2))
        for i in range(crowd):
            rows[CROWD + i] = report(crowd_speed, JAM_SEG, 1000 + 20 * i)
        out[t] = np.asarray(rows, np.int64)
    return out


def run_both(reports: dict, upto: int):
    """The program's and the reference's state after tick ``upto``."""
    n = len(reports[0])
    step = jax.jit(lr.toll_step, static_argnames="expressways")
    state = jax.tree.map(jnp.asarray, lr.initial_state(n))
    ring = np.full((lr.HISTORY, n, len(lr.COLUMNS)), np.nan, np.float32)
    rp = ref.Replay(n, expressways=1)
    for t in range(upto + 1):
        ring[t % lr.HISTORY] = reports[t]
        rp.feed(t, reports[t])
        if t:
            state = step(state, jnp.asarray(ring), jnp.int32(t), expressways=1)
    st = jax.device_get(state)
    for name in ("balance", "last_charge", "pending"):
        assert np.array_equal(st[name], getattr(rp, name)), name
    assert np.array_equal(st["notice"].T, rp.notice)
    return dict(zip(lr.NOTICE, st["notice"][:, V].tolist())), int(st["balance"][V])


@pytest.mark.parametrize("crowd,toll", [(50, 0), (51, 2)])
def test_toll_starts_above_fifty_vehicles(crowd, toll):
    notice, _ = run_both(scene(crowd=crowd), 4)
    assert notice == {"tick": 4, "segment": JAM_SEG, "toll": toll, "lav": 10,
                      "count": crowd, "alert": -1}


@pytest.mark.parametrize("speed,toll", [(39, 2), (40, 0)])
def test_toll_needs_lav_below_forty(speed, toll):
    notice, _ = run_both(scene(crowd_speed=speed), 4)
    assert (notice["lav"], notice["toll"]) == (speed, toll)


def test_each_vehicle_counts_once_a_minute():
    """The crowd reports twice in segment 10 during minute 1: 51, not 102."""
    notice, _ = run_both(scene(crowd=51), 4)
    assert notice["count"] == 51


@pytest.mark.parametrize("exit_lane,paid", [(False, 2), (True, 0)])
def test_leaving_by_the_exit_lane_pays_nothing(exit_lane, paid):
    _, balance = run_both(scene(exit_lane=exit_lane), 6)
    assert balance == paid


@pytest.mark.parametrize("stop_seg,toll,alert", [(JAM_SEG + 4, 0, JAM_SEG + 4),
                                                 (JAM_SEG + 5, 2, -1)])
def test_accident_within_four_segments_downstream(stop_seg, toll, alert):
    notice, _ = run_both(scene(stop_seg=stop_seg), 4)
    assert (notice["toll"], notice["alert"]) == (toll, alert)


@pytest.mark.parametrize("reports,toll,alert", [(3, 2, -1), (4, 0, JAM_SEG + 2)])
def test_stopped_takes_four_identical_reports(reports, toll, alert):
    notice, _ = run_both(scene(stop_seg=JAM_SEG + 2, stopped_reports=reports), 4)
    assert (notice["toll"], notice["alert"]) == (toll, alert)


# --------------------------------------------------------------------- #
# the gateway's reads                                                    #
# --------------------------------------------------------------------- #
def test_gateway_serves_linear_road_reads_against_the_previous_tick():
    sim = make_sim(n=64)
    gw = FleetGateway(sim)
    for _ in range(12):
        gw.tick()
    s = gw.session("motorist")
    asks = [s.ask("account_balance", client=5), s.ask("toll_notice", client=5, window=10),
            s.ask("daily_expenditure", client=5, xway=1, day=69)]
    before = jax.device_get(sim.tolls.state)
    gw.tick()
    bal, notice, spent = (a.response for a in asks)
    assert all(r.ok for r in (bal, notice, spent))
    assert bal.body == {"balance": int(before["balance"][5]),
                        "last_charge": int(before["last_charge"][5])}
    assert notice.body == dict(zip(lr.NOTICE, before["notice"][:, 5].tolist()))
    assert spent.body == {"expenditure": int(ref.expenditure(SEED, 5, 1, 69))}
    bad = [s.ask("daily_expenditure", client=5, xway=2, day=3),
           s.ask("daily_expenditure", client=5, xway=0, day=70),
           s.ask("toll_notice", client=5, window=8),
           s.ask("account_balance", client=64)]
    gw.tick()
    assert not any(b.response.ok for b in bad)


def test_tolls_need_the_sharded_plane():
    with pytest.raises(ValueError, match="sharded"):
        FleetSimulator(SimConfig(n_clients=8, scenario="linear-road", signal_history=12))


def test_a_fleet_without_tolls_refuses_linear_road_reads():
    sim = FleetSimulator(SimConfig(n_clients=8, scenario="mixed", signal_history=16))
    gw = FleetGateway(sim)
    t = gw.session("a").ask("account_balance", client=1)
    gw.tick()
    assert sim.tolls is None and not t.response.ok


# --------------------------------------------------------------------- #
# sharded over 8 simulated devices                                      #
# --------------------------------------------------------------------- #
EIGHT = """
import json, jax, numpy as np
from repro.fleet.simulator import Backends, FleetSimulator, SimConfig
from repro.sharding.fleet import client_mesh
out = {}
for d in (1, 8):
    sim = FleetSimulator(SimConfig(n_clients=1024, seed=17621, scenario="linear-road",
                                   signal_history=12, resync_period=64,
                                   backends=Backends(plane="sharded")),
                         mesh=client_mesh(jax.devices()[:d]))
    for _ in range(24):
        sim.tick()
    st = jax.device_get(sim.tolls.state)
    out[d] = {k: np.asarray(v).tolist() for k, v in st.items()}
    out[f"devices{d}"] = len(sim.tolls.state["balance"].sharding.device_set)
print(json.dumps({"same": out[1] == out[8], "devices": [out["devices1"], out["devices8"]],
                  "charged": out[8]["counters"][1]}))
"""


def test_toll_step_on_eight_devices_equals_one():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(ROOT / "src")}
    done = subprocess.run([sys.executable, "-c", EIGHT], env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["devices"] == [1, 8] and got["same"] and got["charged"] > 0
