"""Fleet simulator: determinism, fleet-scale idempotent ingestion, churn,
stragglers, delayed delivery, and the vectorized aggregation path."""
import numpy as np
import pytest

from repro.core import Broker, FaultPlan, seeded_fault_plan
from repro.fleet import (
    FedConfig,
    FleetSimulator,
    SimConfig,
    aggregate_packed,
    aggregate_reference,
    stack_deltas,
)
from repro.fleet.rounds import pack_delta, unpack_delta


# --------------------------------------------------------------------- #
# broker: delay + seeded schedules                                       #
# --------------------------------------------------------------------- #
def test_delayed_messages_release_in_order():
    delays = {0: 2, 1: 1}  # by msg_id; msg 2 undelayed
    broker = Broker(FaultPlan(delay=lambda m: delays.get(m.msg_id, 0)))
    sub = broker.subscribe("t")
    broker.publish("t", "a")  # msg 0: due at tick 2
    broker.publish("t", "b")  # msg 1: due at tick 1
    broker.publish("t", "c")  # msg 2: immediate
    assert [m.value for m in sub.drain()] == ["c"]
    assert broker.in_flight == 2
    broker.advance(1)
    assert [m.value for m in sub.drain()] == ["b"]
    broker.advance(1)
    assert [m.value for m in sub.drain()] == ["a"]
    assert broker.in_flight == 0


def test_seeded_fault_plan_is_deterministic_and_seed_sensitive():
    a = seeded_fault_plan(1, p_drop=0.5, max_delay=3)
    b = seeded_fault_plan(1, p_drop=0.5, max_delay=3)
    c = seeded_fault_plan(2, p_drop=0.5, max_delay=3)
    from repro.core.broker import Message

    msgs = [Message("t", None, i) for i in range(200)]
    assert [a.drop(m) for m in msgs] == [b.drop(m) for m in msgs]
    assert [a.delay(m) for m in msgs] == [b.delay(m) for m in msgs]
    assert [a.drop(m) for m in msgs] != [c.drop(m) for m in msgs]
    rate = sum(a.drop(m) for m in msgs) / len(msgs)
    assert 0.3 < rate < 0.7
    assert all(0 <= a.delay(m) <= 3 for m in msgs)


def test_exact_topic_index_matches_wildcards_too():
    broker = Broker()
    exact = broker.subscribe("clients/v1/clock")
    wild = broker.subscribe("clients/*/clock")
    broker.publish("clients/v1/clock", 1)
    broker.publish("clients/v2/clock", 2)
    assert [m.value for m in exact.drain()] == [1]
    assert [m.value for m in wild.drain()] == [1, 2]
    broker.unsubscribe(exact)
    broker.publish("clients/v1/clock", 3)
    assert len(exact) == 0


# --------------------------------------------------------------------- #
# vectorized aggregation                                                 #
# --------------------------------------------------------------------- #
def test_batched_aggregation_matches_reference():
    rng = np.random.default_rng(0)
    msgs = [
        pack_delta(rng.standard_normal(1000).astype(np.float32), row=256)
        for _ in range(32)
    ]
    assert np.allclose(
        aggregate_packed(msgs), aggregate_reference(msgs), atol=1e-6
    )
    w = rng.random(32).astype(np.float32)
    assert np.allclose(
        aggregate_packed(msgs, w), aggregate_reference(msgs, w), atol=1e-6
    )


def test_reference_aggregate_does_not_drift_over_many_clients():
    """The oracle must hold to 1e-6 where the batched path is held to it:
    an f32 running sum over thousands of equal-weight clients drifted."""
    d = np.linspace(-0.5, 0.5, 256).astype(np.float32)
    msg = pack_delta(d, row=256)
    got = aggregate_reference([msg] * 3000, np.ones(3000, np.float32))
    assert np.abs(got - unpack_delta(msg)).max() < 1e-7


def test_heterogeneous_shapes_fall_back_to_reference():
    rng = np.random.default_rng(1)
    msgs = [
        pack_delta(rng.standard_normal(512).astype(np.float32), row=256),
        pack_delta(rng.standard_normal(768).astype(np.float32), row=256),
    ]
    assert stack_deltas(msgs) is None
    with pytest.raises(ValueError):
        # mixed lengths cannot be averaged — both paths must agree on that
        aggregate_packed(msgs)


# --------------------------------------------------------------------- #
# the fleet-scale properties                                             #
# --------------------------------------------------------------------- #
FED = FedConfig(local_steps=3, local_lr=0.2, deadline_fraction=1.0)


def _run(cfg: SimConfig, fed: FedConfig = FED, rounds: int = 2):
    sim = FleetSimulator(cfg)
    driver = sim.run_federated(fed, dim=16, rounds=rounds, n_samples=16)
    return sim, driver


def test_lossy_256_client_round_matches_fault_free():
    """Idempotent ingestion at fleet scale: a seeded lossy broker schedule
    (drops, duplicates, delays) must converge to the *exact* aggregate of
    the fault-free run — the paper's resiliency argument, mechanized."""
    _, lossy = _run(
        SimConfig(
            n_clients=256, seed=3, p_drop=0.2, p_duplicate=0.1, max_delay=3
        )
    )
    _, clean = _run(SimConfig(n_clients=256, seed=3))
    assert np.array_equal(lossy.w, clean.w)
    assert all(r["participants"] == 256 for r in lossy.history)


def test_same_seed_same_aggregate():
    cfg = SimConfig(
        n_clients=64,
        seed=11,
        p_drop=0.15,
        p_duplicate=0.05,
        max_delay=2,
        p_leave=0.01,
        p_return=0.3,
        straggler_fraction=0.2,
    )
    fed = FedConfig(
        local_steps=3, local_lr=0.2, deadline_fraction=0.7, deadline_pumps=48
    )
    _, a = _run(cfg, fed, rounds=3)
    _, b = _run(cfg, fed, rounds=3)
    assert np.array_equal(a.w, b.w)
    assert [r["participants"] for r in a.history] == [
        r["participants"] for r in b.history
    ]


def test_stragglers_get_canceled_and_rounds_still_converge():
    sim, driver = _run(
        SimConfig(
            n_clients=48, seed=5, straggler_fraction=0.25, straggler_period=8
        ),
        FedConfig(
            local_steps=3,
            local_lr=0.2,
            deadline_fraction=0.7,
            deadline_pumps=32,
        ),
        rounds=3,
    )
    assert sum(r["canceled"] for r in driver.history) > 0
    assert (
        driver.history[-1]["dist_to_optimum"]
        < driver.history[0]["dist_to_optimum"]
    )
    s = sim.metrics.summary()
    assert s["rounds"] == 3 and s["canceled_total"] > 0


def test_churn_mid_round_never_stalls_the_fleet():
    sim, driver = _run(
        SimConfig(n_clients=32, seed=9, p_leave=0.05, p_return=0.3),
        FedConfig(
            local_steps=2,
            local_lr=0.2,
            deadline_fraction=0.5,
            deadline_pumps=48,
        ),
        rounds=3,
    )
    assert all(r["participants"] >= 1 for r in driver.history)
    # churn actually happened: someone was offline or missed a round
    assert any(
        r.online_at_start < 32 or r.participants < r.online_at_start
        for r in sim.metrics.rounds
    )
    assert len(sim.metrics.rounds) == 3


def test_new_vehicles_can_join_mid_experiment():
    sim, driver = _run(SimConfig(n_clients=8, seed=1), rounds=1)
    cid = sim.pool.add_vehicle()
    sim.pool.vehicles[cid].client.run_until_idle()
    rec = driver.run_round(1, pump=sim.tick)
    assert rec["participants"] == 9
