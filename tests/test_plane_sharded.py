"""Device-sharded signal plane: bit-for-bit parity with the single-host
plane (values, windows, offline NaN masks) at N=1024, shard-aware
geometric growth, simulator integration, and a hypothesis property test
over random fleets. Runs on any device count — the CI `multi-device`
lane runs it under XLA_FLAGS=--xla_force_host_platform_device_count=8 so
the shards genuinely span devices."""
import jax
import numpy as np
import pytest

from repro.core.plane_sharded import ShardedSignalPlane
from repro.core.signals import FleetSignalPlane, SignalHandler
from repro.fleet import FedConfig, FleetSimulator, SimConfig
from repro.fleet.scenarios import SIGNALS, Scenario, build_plane
from repro.sharding import fleet as fleet_sharding

NDEV = len(jax.devices())


def _pair(name="mixed", n=8, seed=13, history=32):
    scen = Scenario(name, seed=seed)
    return scen.plane(n, history=history), scen.sharded_plane(n, history=history)


# --------------------------------------------------------------------- #
# the tentpole contract: sharded == host, bit for bit                    #
# --------------------------------------------------------------------- #
def test_sharded_plane_matches_host_plane_at_fleet_scale():
    """N=1024 over every available device (8 in the CI multi-device
    lane): values matrix, reads, and history windows are identical after
    dozens of ticks."""
    host, sharded = _pair(n=1024, history=16)
    assert sharded._capacity % NDEV == 0
    for _ in range(20):
        host.step()
        sharded.step()
    assert np.array_equal(host.values, sharded.values)
    for row in (0, 1, 500, 1023):
        for sig in SIGNALS:
            assert host.read(row, sig) == sharded.read(row, sig)
            assert host.window(row, sig, 12) == sharded.window(row, sig, 12)


def test_sharded_step_spans_every_device():
    _, sharded = _pair(n=64)
    sharded.step()
    assert sharded.devices == NDEV
    assert len(sharded._dvalues.sharding.device_set) == NDEV
    assert len(sharded._dhist.sharding.device_set) == NDEV


def test_offline_nan_masking_matches_host_plane():
    """Ring masking parity: a powered-off row's window after re-ignition
    only shows powered-on observations, exactly like the host plane."""
    host, sharded = _pair(n=6, history=64)
    for p in (host, sharded):
        for _ in range(3):
            p.step()
        p.set_online(2, False)
        for _ in range(4):
            p.step()
        p.set_online(2, True)
        for _ in range(2):
            p.step()
    for row in range(6):
        for sig in SIGNALS:
            assert host.window(row, sig, 64) == sharded.window(row, sig, 64)
    # values keep advancing fleet-globally on both planes
    assert np.array_equal(host.values, sharded.values)


def test_uploaded_offline_mask_ignores_later_toggles():
    """The mask a step was given is a snapshot of the host mask at that
    step: toggles after it must not reach it. (device_put copies host
    memory asynchronously, so an in-place toggle right after a step could
    land in that step's mask: the golden sharded restore test flaked.)"""
    plane = Scenario("mixed", 0).sharded_plane(1 << 20, history=2)
    for trial in range(8):
        plane.set_online(0, bool(trial % 2))
        plane.step()
        want = plane._offline.copy()
        plane._offline[1:] = not plane._offline[1]  # every row toggles
        assert np.array_equal(np.asarray(plane._doffline), want), trial


def test_plane_signal_view_and_handler_work_unchanged():
    """`autospada.get_signal` / `get_signal_window` plumbing: the same
    SignalHandler-over-PlaneSignalView stack reads the sharded plane."""
    host, sharded = _pair(n=5)
    hh = [SignalHandler(host.view(i)) for i in range(5)]
    hs = [SignalHandler(sharded.view(i)) for i in range(5)]
    for _ in range(7):
        host.step()
        sharded.step()
        for i in range(5):
            for sig in SIGNALS:
                assert hh[i].get(sig) == hs[i].get(sig)
                assert hh[i].window(sig, 4) == hs[i].window(sig, 4)
    assert hs[0].get("Vehicle.DoesNotExist") is None
    assert hs[0].window("Vehicle.DoesNotExist", 4) == []


# --------------------------------------------------------------------- #
# shard-aware growth                                                     #
# --------------------------------------------------------------------- #
def test_capacity_is_always_a_device_count_multiple():
    _, sharded = _pair(n=3)
    assert sharded._capacity % NDEV == 0 and sharded._capacity >= 3
    for _ in range(2 * NDEV + 3):
        sharded.add_client()
    assert sharded._capacity % NDEV == 0
    assert sharded._capacity >= sharded.n_clients


def test_growth_parity_with_host_plane():
    host, sharded = _pair(n=4, history=16)
    host.step()
    sharded.step()
    before = host.values.copy()
    for _ in range(9):
        assert host.add_client() == sharded.add_client()
    # row stability: regrowth recomputed the same tick — old rows intact
    assert np.array_equal(sharded.values[:4], before)
    host.step()
    sharded.step()
    assert host.n_clients == sharded.n_clients == 13
    assert np.array_equal(host.values, sharded.values)
    for row in range(13):
        assert host.window(row, "Vehicle.Speed", 16) == sharded.window(
            row, "Vehicle.Speed", 16
        )
    # a freshly-joined row's history starts at its join tick, not before
    assert len(sharded.window(12, "Vehicle.Speed", 16)) == 2


def test_growth_never_doubles_per_join():
    """Geometric growth survives the sharded layout: N single joins
    recompile the tick O(log N) times, not N times."""
    scen = Scenario("urban", seed=1)
    calls = []

    def counting_builder(cap):
        calls.append(cap)
        return scen.step_fn(cap)

    plane = ShardedSignalPlane(SIGNALS, 4, counting_builder, history=16)
    for _ in range(28):
        plane.add_client()
    assert plane.n_clients == 32
    # initial compile + O(log N) regrows (exact count depends on rounding)
    assert len(calls) <= 6


def test_spare_capacity_rows_fail_fast():
    _, sharded = _pair(n=3)
    sharded.step()
    if sharded._capacity == sharded.n_clients:
        sharded.add_client()  # force spare rows on 1-device meshes
        sharded.step()
    assert sharded._capacity > sharded.n_clients
    for bad in (sharded.n_clients, sharded._capacity - 1, -1):
        with pytest.raises(IndexError, match="out of range"):
            sharded.read(bad, SIGNALS[0])
        with pytest.raises(IndexError, match="out of range"):
            sharded.window(bad, SIGNALS[0], 4)
        with pytest.raises(IndexError, match="out of range"):
            sharded.view(bad)
        with pytest.raises(IndexError, match="out of range"):
            sharded.set_online(bad, False)


def test_traces_stay_on_the_host_plane():
    with pytest.raises(NotImplementedError, match="scenario-backed"):
        ShardedSignalPlane.from_trace(SIGNALS, np.zeros((1, 2, 4)))


# --------------------------------------------------------------------- #
# CSV playback: streamed host rows fed into the sharded ring             #
# --------------------------------------------------------------------- #
_CSVS = [
    "a,b\n1,2\n,3\n4,\n7,8\n",   # blanks hold the previous value
    "a,c\n5,\n,9\n",             # short trace: holds its last row
    "b\n\n6\n",                  # blank line, late first observation
]


def test_sharded_csv_plane_matches_host_plane_bit_for_bit():
    host = FleetSignalPlane.from_csv_fleet(_CSVS)
    shard = ShardedSignalPlane.from_csv_fleet(_CSVS)
    assert shard.names == host.names
    assert shard.n_clients == host.n_clients == len(_CSVS)
    shard.set_online(1, False)
    host.set_online(1, False)
    for t in range(6):  # runs past the longest trace (4 ticks)
        for i in range(host.n_clients):
            for name in host.names:
                assert shard.read(i, name) == host.read(i, name), (t, i, name)
                assert shard.window(i, name, 5) == host.window(i, name, 5)
        if t == 2:
            shard.set_online(1, True)
            host.set_online(1, True)
        host.step()
        shard.step()
    assert np.array_equal(shard.values, host.values, equal_nan=True)


def test_sharded_csv_plane_is_fixed_size():
    shard = ShardedSignalPlane.from_csv_fleet(["a\n1\n2\n"])
    with pytest.raises(ValueError, match="fixed fleet size"):
        shard.add_client()


def test_build_plane_selects_and_rejects():
    assert isinstance(build_plane("mixed", 4, plane="sharded"), ShardedSignalPlane)
    assert not isinstance(build_plane("mixed", 4, plane="host"), ShardedSignalPlane)
    with pytest.raises(ValueError, match="unknown plane"):
        build_plane("mixed", 4, plane="columnar")


def test_round_up_clients():
    mesh = fleet_sharding.client_mesh()
    d = fleet_sharding.device_count(mesh)
    assert fleet_sharding.round_up_clients(1, mesh) == d
    assert fleet_sharding.round_up_clients(d, mesh) == d
    assert fleet_sharding.round_up_clients(d + 1, mesh) == 2 * d
    assert fleet_sharding.round_up_clients(7 * d, mesh) == 7 * d


# --------------------------------------------------------------------- #
# simulator integration                                                  #
# --------------------------------------------------------------------- #
def test_simulator_runs_identically_on_the_sharded_plane():
    """Same SimConfig through both planes: identical final aggregate and
    broker counters — the sharded plane is payload-invisible."""

    def run(plane):
        sim = FleetSimulator(
            SimConfig(
                n_clients=12, seed=21, scenario="mixed", p_drop=0.1,
                max_delay=1, plane=plane,
            )
        )
        drv = sim.run_federated(
            FedConfig(
                local_steps=2, local_lr=0.2, deadline_fraction=0.8,
                deadline_pumps=32,
            ),
            dim=8,
            rounds=2,
            n_samples=8,
        )
        counters = (
            sim.broker.published, sim.broker.delivered, sim.broker.dropped
        )
        return drv.w.copy(), sim.plane.values.copy(), counters

    w_h, v_h, c_h = run("host")
    w_s, v_s, c_s = run("sharded")
    assert np.array_equal(w_h, w_s)
    assert np.array_equal(v_h, v_s)
    assert c_h == c_s


def test_simulator_reignition_window_on_sharded_plane():
    sim = FleetSimulator(
        SimConfig(n_clients=2, seed=0, scenario="mixed", plane="sharded")
    )
    cid = "veh-001"
    for _ in range(4):
        sim.tick()
    sim.pool.power_off(cid)
    for _ in range(3):
        sim.tick()
    sim.pool.power_on(cid)
    sim.pool.vehicles[cid].client.run_until_idle()
    for _ in range(2):
        sim.tick()
    churned = sim.pool.vehicles[cid].client.signal_handler.window(
        "Vehicle.Speed", 64
    )
    assert len(churned) == 7  # 3 ignition-off ticks are not "observed"


# --------------------------------------------------------------------- #
# property test: random fleets, growth, and power patterns               #
# --------------------------------------------------------------------- #
def test_property_random_growth_and_power_patterns_match():
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    ops = st.lists(
        st.one_of(
            st.just(("step",)),
            st.just(("join",)),
            st.tuples(st.just("power"), st.integers(0, 31), st.booleans()),
        ),
        min_size=1,
        max_size=12,
    )

    @settings(
        max_examples=15,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(n=st.integers(1, 9), seed=st.integers(0, 3), script=ops)
    def check(n, seed, script):
        scen = Scenario("mixed", seed=seed)
        host = scen.plane(n, history=8)
        sharded = scen.sharded_plane(n, history=8)
        for op in script:
            if op[0] == "step":
                host.step()
                sharded.step()
            elif op[0] == "join":
                assert host.add_client() == sharded.add_client()
            else:
                _, row, online = op
                row %= host.n_clients
                host.set_online(row, online)
                sharded.set_online(row, online)
        assert np.array_equal(host.values, sharded.values)
        for row in range(host.n_clients):
            for sig in ("Vehicle.Speed", "Vehicle.FuelRate"):
                assert host.read(row, sig) == sharded.read(row, sig)
                assert host.window(row, sig, 8) == sharded.window(row, sig, 8)

    check()
