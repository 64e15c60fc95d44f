"""Fused windowed-sketch kernels: parity with the per-vehicle oracle.

The contract under test (ISSUE/ROADMAP item 2): one fleet-wide device
fold over the signal ring (`compute_sketches`) must match the sandboxed
per-vehicle Python fold (`sketch_reference`, the `ANALYTICS_PAYLOAD`
formula) bit for bit — moments, histogram, and quantile values — across
offline-NaN masking, short histories, and fleet growth; sharded == host;
Pallas kernel == XLA twin; and the sharded analytics path must never
sync the ring to the host. Quantile *queries* after merging carry a
deterministic rank-error bound, pinned by a property test.
"""
import numpy as np
import pytest

from repro.fleet.analytics import AnalyticsConfig, WindowStats
from repro.fleet.scenarios import Scenario
from repro.fleet.simulator import FleetSimulator, SimConfig
from repro.kernels import sketch as sk
from repro.kernels.ops import merge_quantile_sketches
from repro.kernels.sketch import (
    FleetSketches,
    SketchSpec,
    empty_fleet_sketches,
    fold_window,
    sketch_reference,
    sketches_from_device,
)

SIG = "Vehicle.FuelRate"


def _random_window(rng, W, n):
    """A (W, n) time-ordered window with the NaN patterns the ring
    produces: leading not-yet-observed prefixes, offline holes, and a
    fully-empty column."""
    x = rng.normal(5.0, 3.0, (W, n)).astype(np.float32)
    for j in range(n):
        x[: rng.integers(0, W + 1), j] = np.nan  # short history
    x[rng.random((W, n)) < 0.2] = np.nan         # offline ticks
    x[:, 0] = np.nan                             # never-observed client
    return x


def _rows_equal(sk, x, spec):
    for j in range(x.shape[1]):
        xs = [float(v) for v in x[:, j] if not np.isnan(v)]
        assert sk.row(j) == sketch_reference(xs, spec), f"column {j}"


# --------------------------------------------------------------------- #
# kernel-level parity                                                   #
# --------------------------------------------------------------------- #
def test_fold_window_matches_reference_bit_for_bit():
    rng = np.random.default_rng(0)
    spec = SketchSpec(window=37, bins=16, quantile_k=8)
    for _ in range(3):
        x = _random_window(rng, 37, 23)
        out = np.asarray(fold_window(x, spec, backend="xla"))
        assert out.shape == (spec.dim, 23)
        _rows_equal(sketches_from_device(spec, out), x, spec)


def test_pallas_kernel_matches_xla_twin():
    rng = np.random.default_rng(1)
    for spec in (
        SketchSpec(window=24, bins=16, quantile_k=8),
        SketchSpec(window=24, bins=1, quantile_k=4),  # no interior edges
    ):
        # 150 columns: exercises the NaN padding to a 128-client block
        x = _random_window(rng, spec.window, 150)
        a = np.asarray(fold_window(x, spec, backend="xla"))
        b = np.asarray(fold_window(x, spec, backend="pallas"))
        assert np.array_equal(a, b, equal_nan=True)
        _rows_equal(sketches_from_device(spec, b), x, spec)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        fold_window(np.zeros((2, 2), np.float32), SketchSpec(), backend="tdigest")


def test_spec_validation():
    with pytest.raises(ValueError):
        SketchSpec(window=0)
    with pytest.raises(ValueError):
        SketchSpec(bins=0)
    with pytest.raises(ValueError):
        SketchSpec(quantile_k=0)


# --------------------------------------------------------------------- #
# plane-level parity: compute_sketches vs the window() oracle           #
# --------------------------------------------------------------------- #
def _exercise(plane, rng, ticks=20, grow_at=9):
    n = plane.n_clients
    for t in range(ticks):
        plane.step()
        for i in rng.integers(0, n, 4):
            plane.set_online(int(i), bool(rng.random() < 0.5))
        if t == grow_at:
            plane.add_client()
            n = plane.n_clients
    return n


def _assert_matches_window_oracle(plane, spec):
    sk = plane.compute_sketches(SIG, spec)
    for i in range(plane.n_clients):
        ref = sketch_reference(plane.window(i, SIG, spec.window), spec)
        assert sk.row(i) == ref, f"row {i}"
    return sk


def test_host_plane_sketches_match_window_oracle():
    """Offline-NaN masking, short history (window > ring > observed),
    and mid-run fleet growth all reproduce the per-row fold exactly."""
    plane = Scenario("mixed", seed=3).plane(24, history=16)
    _exercise(plane, np.random.default_rng(2))
    # window larger than the ring: clamps like window() does
    for spec in (SketchSpec(window=8, quantile_k=8), SketchSpec(window=64, quantile_k=8)):
        _assert_matches_window_oracle(plane, spec)


def test_host_plane_short_history():
    plane = Scenario("urban", seed=4).plane(8, history=32)
    plane.step()  # hist_len = 2 << window
    _assert_matches_window_oracle(plane, SketchSpec(window=16, quantile_k=4))


def test_sharded_matches_host_and_ring_stays_on_device():
    scen = Scenario("mixed", seed=5)
    host, shard = scen.plane(24, history=16), scen.sharded_plane(24, history=16)
    ra, rb = np.random.default_rng(7), np.random.default_rng(7)
    _exercise(host, ra)
    _exercise(shard, rb)
    spec = SketchSpec(window=12, quantile_k=8)
    hs = _assert_matches_window_oracle(host, spec)

    shard.step()  # leave the ring dirty again after the oracle's window() sync
    host.step()
    syncs0 = shard.ring_syncs
    ss = shard.compute_sketches(SIG, spec)
    hs = host.compute_sketches(SIG, spec)
    # the analytics fast path never moves the ring device->host
    assert shard._hist_dirty and shard.ring_syncs == syncs0
    for field in ("counts", "means", "m2s", "hists"):
        assert np.array_equal(getattr(hs, field), getattr(ss, field)), field
    assert np.array_equal(hs.qvals, ss.qvals, equal_nan=True)


@pytest.mark.parametrize(
    "steps,windows",
    [(10, (8,)), (3, (8,)), (40, (8,)), (40, (4, 16))],
    ids=["steady", "short-history", "wrapped-ring", "two-windows"],
)
def test_sharded_pallas_backend_matches_xla(steps, windows):
    """The fused, shard_mapped Pallas program (interpret mode off-TPU)
    agrees with the sharding-propagated XLA twin on every shard: with
    less history than the window, after the 16-slot ring has wrapped,
    and at two windows over one ring."""
    shard = Scenario("highway", seed=6).sharded_plane(24, history=16)
    for _ in range(steps):
        shard.step()
    for window in windows:
        spec = SketchSpec(window=window, quantile_k=8)
        a = shard.compute_sketches(SIG, spec, backend="xla")
        b = shard.compute_sketches(SIG, spec, backend="pallas")
        for field in ("counts", "means", "m2s", "hists"):
            assert np.array_equal(getattr(a, field), getattr(b, field)), field
        assert np.array_equal(a.qvals, b.qvals, equal_nan=True)


def test_sharded_pallas_fold_builds_once_per_signal_and_window():
    """The Pallas ring fold is one jitted program per static key: new
    ticks and new bin edges reuse it, only a new window builds another.
    The spec's bins, K and the fleet size are this test's own, so no
    other test has built these programs before."""
    shard = Scenario("highway", seed=9).sharded_plane(19, history=16)
    for _ in range(4):
        shard.step()
    before = sk.fold_traces
    for i in range(3):
        spec = SketchSpec(window=6, bins=5, quantile_k=3, lo=float(i), hi=9.0 + i)
        got = shard.compute_sketches(SIG, spec, backend="pallas")
        want = shard.compute_sketches(SIG, spec, backend="xla")
        assert np.array_equal(got.hists, want.hists)  # the edges are not baked in
        shard.step()
    assert sk.fold_traces - before == 1
    shard.compute_sketches(SIG, SketchSpec(window=12, bins=5, quantile_k=3),
                           backend="pallas")
    assert sk.fold_traces - before == 2


def test_unknown_signal_folds_to_empty_sketch():
    plane = Scenario("idle", seed=0).plane(4, history=8)
    sk = plane.compute_sketches("No.Such.Signal", SketchSpec(window=4))
    assert sk.row(0) == sketch_reference([], SketchSpec(window=4))
    assert plane.sketch_row(2, "No.Such.Signal", SketchSpec(window=4))["count"] == 0


def test_sketch_row_cache_is_per_tick_fleet_wide():
    plane = Scenario("mixed", seed=8).plane(8, history=16)
    for _ in range(6):
        plane.step()
    spec = SketchSpec(window=4, quantile_k=4)
    sk = plane.compute_sketches(SIG, spec)
    assert plane.sketch_row(0, SIG, spec) == sk.row(0)
    plane.sketch_row(5, SIG, spec)
    assert len(plane._sketch_cache) == 1  # one fold served both rows
    plane.step()
    stale = plane.sketch_row(0, SIG, spec)
    assert len(plane._sketch_cache) == 1  # old tick evicted, not retained
    assert stale == sketch_reference(plane.window(0, SIG, 4), spec)
    # growth changes n_clients -> new key even at the same tick
    plane.add_client()
    plane.sketch_row(plane.n_clients - 1, SIG, spec)
    assert len(plane._sketch_cache) == 1


def test_empty_fleet_sketches_shapes():
    sk = empty_fleet_sketches(SketchSpec(bins=4, quantile_k=2), 3)
    assert isinstance(sk, FleetSketches) and sk.n_clients == 3
    assert sk.hists.shape == (3, 4) and sk.qvals.shape == (3, 2)


# --------------------------------------------------------------------- #
# payload API: get_signal_sketch fallback == reference                  #
# --------------------------------------------------------------------- #
def test_payload_sketch_fallback_matches_reference():
    from repro.core.payload_api import PayloadContext

    xs = [1.0, 2.5, 11.0, -3.0, 2.5]

    ctx = PayloadContext(
        get_signal=lambda name: xs[-1],
        get_signal_window=lambda name, k: xs[-k:],
        publish=lambda v: None,
    )
    got = ctx.get_signal_sketch("Vehicle.Speed", 5, bins=8, quantile_k=4)
    assert got == sketch_reference(xs, SketchSpec(window=5, bins=8, quantile_k=4))
    # an injected sketch closure that declines (returns None) falls back
    ctx2 = PayloadContext(
        get_signal=lambda name: xs[-1],
        get_signal_window=lambda name, k: xs[-k:],
        get_signal_sketch=lambda *a: None,
        publish=lambda v: None,
    )
    assert ctx2.get_signal_sketch("Vehicle.Speed", 5, bins=8, quantile_k=4) == got


# --------------------------------------------------------------------- #
# the vectorized analytics driver mode, end to end                      #
# --------------------------------------------------------------------- #
def _run_analytics(sketch: bool, **cfg_kw):
    sim = FleetSimulator(
        SimConfig(
            n_clients=16,
            seed=11,
            scenario="mixed",
            p_drop=0.08,
            p_duplicate=0.05,
            max_delay=2,
            p_leave=0.03,
            p_return=0.3,
            straggler_fraction=0.25,
            **cfg_kw,
        )
    )
    driver = sim.run_analytics(
        AnalyticsConfig(sketch=sketch, window=16, quantile_k=8),
        windows=3,
        warmup_ticks=6,
    )
    return sim, driver


@pytest.mark.parametrize("plane", ["host", "sharded"])
def test_driver_sketch_mode_is_bit_for_bit_with_payload_oracle(plane):
    """`AnalyticsConfig(sketch=True)` — one fused device fold per tick —
    publishes the same sketches as the per-sandbox `ANALYTICS_PAYLOAD`
    fold under faults x churn x stragglers x offline masking, so the
    whole campaign (participation, cancels, merged stats, quantiles,
    broker traffic) is identical."""
    sa, da = _run_analytics(False, plane=plane)
    sb, db = _run_analytics(True, plane=plane)
    assert len(da.history) == len(db.history) == 3
    for ra, rb in zip(da.history, db.history):
        assert (ra.participants, ra.canceled, ra.pumps) == (
            rb.participants, rb.canceled, rb.pumps,
        )
        assert ra.count == rb.count
        assert ra.mean == rb.mean and ra.var == rb.var
        assert np.array_equal(ra.hist, rb.hist)
        assert np.array_equal(ra.q_values, rb.q_values, equal_nan=True)
        assert np.array_equal(ra.q_weights, rb.q_weights)
    assert (sa.broker.published, sa.broker.delivered, sa.broker.dropped) == (
        sb.broker.published, sb.broker.delivered, sb.broker.dropped,
    )


def test_driver_progress_gauge_tracks_status_counters():
    sim, driver = _run_analytics(True)
    p = sim.metrics.progress
    assert p is not None and p.round == 2
    last = driver.history[-1]
    assert p.total == last.participants + last.canceled
    assert p.finished == last.participants
    assert p.canceled == last.canceled
    assert p.terminal == p.total and p.active == 0
    assert p.completion == pytest.approx(last.participants / p.total)


# --------------------------------------------------------------------- #
# quantile queries over merged summaries                                #
# --------------------------------------------------------------------- #
def _stats_from_parts(parts, K):
    spec = SketchSpec(window=max(1, max(map(len, parts))), quantile_k=K)
    qvals = [
        (r["qsk"] or [np.nan] * K)
        for r in (sketch_reference(p, spec) for p in parts)
    ]
    counts = [len(p) for p in parts]
    v, w = merge_quantile_sketches(
        np.asarray(qvals, np.float32), np.asarray(counts, np.float32)
    )
    total = sum(counts)
    return WindowStats(
        0, len(parts), 0, 0, total, 0.0, 0.0,
        np.zeros(4, np.int64), q_values=v, q_weights=w,
    )


def test_single_sketch_quantiles_are_exact_order_statistics():
    data = np.arange(64, dtype=np.float32)
    ws = _stats_from_parts([data], K=64)  # K == n: every sample survives
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        assert ws.quantile(q) == float(
            np.quantile(data, q, method="inverted_cdf")
        )


def test_quantile_of_empty_and_zero_count_fleets_is_nan():
    assert np.isnan(WindowStats(0, 0, 0, 0, 0, 0.0, 0.0, np.zeros(4)).quantile(0.5))
    ws = _stats_from_parts([np.array([], np.float32)], K=4)
    assert np.isnan(ws.quantile(0.5))


def test_zero_count_clients_do_not_shift_ranks():
    data = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    with_empty = _stats_from_parts(
        [data, np.array([], np.float32), np.array([], np.float32)], K=4
    )
    without = _stats_from_parts([data], K=4)
    for q in (0.0, 0.5, 1.0):
        assert with_empty.quantile(q) == without.quantile(q)


def _rank_error(data_sorted, est, q):
    n = len(data_sorted)
    r_lo = float(np.sum(data_sorted < est))
    r_hi = float(np.sum(data_sorted <= est))
    target = q * n
    return max(0.0, r_lo - target, target - r_hi)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:  # graceful skip — hypothesis is optional
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_merged_partitions_hold_the_rank_error_bound():
        pass
else:
    @settings(
        max_examples=60,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        seed=st.integers(0, 2**31 - 1),
        n=st.integers(1, 400),
        n_parts=st.integers(1, 8),
        K=st.sampled_from([4, 8, 16, 32]),
    )
    def test_merged_partitions_hold_the_rank_error_bound(seed, n, n_parts, K):
        """Merging any random partition of a sample into K-point
        summaries answers every quantile within rank error
        n/(2K) + n_parts of the exact sorted-array percentile — the
        KLL-style guarantee the fused sketch path rests on."""
        rng = np.random.default_rng(seed)
        data = rng.normal(0.0, 10.0, n).astype(np.float32)
        cuts = np.sort(rng.integers(0, n + 1, n_parts - 1))
        parts = np.split(data, cuts)
        ws = _stats_from_parts(parts, K)
        srt = np.sort(data)
        bound = n / (2 * K) + len(parts)
        for q in (0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0):
            err = _rank_error(srt, ws.quantile(q), q)
            assert err <= bound, (q, err, bound)
