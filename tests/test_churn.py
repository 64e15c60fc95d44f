"""Event-driven churn: the O(events) heap schedule must reproduce the
O(N)-scan dense oracle's toggle sequence exactly at a fixed seed, stay
row-stable under membership growth, and keep the simulator deterministic
(and actually churning) end to end."""
import numpy as np
import pytest

from repro.fleet import FedConfig, FleetSimulator, SimConfig
from repro.fleet.churn import DenseChurn, EventChurn, geometric_gap, make_churn


def _drive(churn, n=32, ticks=200, external=()):
    """Run a toy world against a churn schedule: apply due toggles, feed
    the resulting state back via notify (as FleetPool does), and inject
    external power flips at scripted (tick, index) points."""
    online = {f"v{i}": True for i in range(n)}
    for i in range(n):
        churn.watch(f"v{i}", i, True, now=0)
    external = {(t, f"v{i}") for t, i in external}
    log = []
    for t in range(1, ticks + 1):
        for cid in churn.pop_due(t):
            online[cid] = not online[cid]
            idx = int(cid[1:])
            churn.notify(cid, idx, online[cid])
            log.append((t, cid, online[cid]))
        for t_ext, cid in sorted(external):
            if t_ext == t:
                online[cid] = not online[cid]
                churn.notify(cid, int(cid[1:]), online[cid])
                log.append((t, cid, online[cid], "external"))
    return log


# --------------------------------------------------------------------- #
# the satellite contract: heap == dense scan, bit for bit                #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("p_leave,p_return", [(0.05, 0.3), (0.5, 0.5), (0.01, 0.0)])
def test_event_heap_matches_dense_scan(p_leave, p_return):
    a = _drive(EventChurn(11, p_leave, p_return))
    b = _drive(DenseChurn(11, p_leave, p_return))
    assert a == b
    assert len(a) > 0  # churn actually happened


def test_parity_survives_external_power_flips():
    ext = [(10, 3), (10, 7), (55, 3), (90, 0)]
    a = _drive(EventChurn(5, 0.04, 0.25), external=ext)
    b = _drive(DenseChurn(5, 0.04, 0.25), external=ext)
    assert a == b


def test_zero_probabilities_schedule_nothing():
    assert _drive(EventChurn(0, 0.0, 0.0)) == []
    # p_return=0: a vehicle that leaves never returns via churn
    log = _drive(EventChurn(2, 0.2, 0.0), n=8, ticks=120)
    went_off = {cid for _, cid, on, *_ in log if not on}
    came_back = {cid for _, cid, on, *_ in log if on}
    assert went_off and not came_back


def test_streams_are_per_vehicle_and_row_stable():
    """Adding vehicle k never perturbs vehicles < k: per-vehicle seeded
    streams, exactly the scenario generators' row-stability contract."""
    small = _drive(EventChurn(7, 0.1, 0.3), n=4, ticks=80)
    large = _drive(EventChurn(7, 0.1, 0.3), n=9, ticks=80)
    assert [e for e in large if int(e[1][1:]) < 4] == small


def test_geometric_gap_inverse_cdf():
    assert geometric_gap(0.0, 0.5) == 1  # u=0 is the earliest success
    assert geometric_gap(0.999, 1.0) == 1  # p=1 fires next tick
    # median of Geometric(0.5) is 1; u just under the CDF step lands 1
    assert geometric_gap(0.49, 0.5) == 1
    assert geometric_gap(0.51, 0.5) == 2
    # tiny p gives long horizons, never zero or negative
    assert geometric_gap(0.5, 0.001) >= 1
    # a subnormal p (a hypothesis draw) is "never", not an OverflowError
    assert geometric_gap(0.5, 2.225073858507203e-309) == 1 + 2**53


def test_make_churn_selects_and_rejects():
    assert isinstance(make_churn("event", 0, 0.1, 0.1), EventChurn)
    assert isinstance(make_churn("dense", 0, 0.1, 0.1), DenseChurn)
    with pytest.raises(ValueError, match="unknown churn"):
        make_churn("poisson", 0, 0.1, 0.1)


# --------------------------------------------------------------------- #
# simulator integration                                                  #
# --------------------------------------------------------------------- #
def _run_sim(churn_kind, **overrides):
    cfg = dict(
        n_clients=24, seed=9, p_leave=0.05, p_return=0.3, churn=churn_kind
    )
    cfg.update(overrides)
    sim = FleetSimulator(SimConfig(**cfg))
    drv = sim.run_federated(
        FedConfig(
            local_steps=2, local_lr=0.2, deadline_fraction=0.5,
            deadline_pumps=48,
        ),
        dim=8,
        rounds=3,
        n_samples=8,
    )
    counters = (sim.broker.published, sim.broker.delivered, sim.broker.dropped)
    return drv.w.copy(), counters, sim


def test_simulator_event_churn_matches_dense_churn_oracle():
    w_e, c_e, _ = _run_sim("event")
    w_d, c_d, _ = _run_sim("dense")
    assert np.array_equal(w_e, w_d)
    assert c_e == c_d


def test_simulator_churn_is_deterministic_and_still_churns():
    w1, c1, sim = _run_sim("event")
    w2, c2, _ = _run_sim("event")
    assert np.array_equal(w1, w2) and c1 == c2
    assert any(
        r.online_at_start < 24 or r.participants < r.online_at_start
        for r in sim.metrics.rounds
    )


def test_new_vehicles_join_the_churn_schedule():
    """A vehicle added mid-experiment is auto-watched via the pool's
    power-on hook and can be toggled by churn."""
    sim = FleetSimulator(
        SimConfig(n_clients=4, seed=3, p_leave=0.9, p_return=0.9)
    )
    cid = sim.pool.add_vehicle()
    assert cid in sim.churn._online
    offline_seen = False
    for _ in range(30):
        sim.tick()
        offline_seen |= sim.pool.vehicles[cid].client is None
    assert offline_seen
