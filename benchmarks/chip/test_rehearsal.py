"""CPU rehearsal of the chip benchmark at a tiny fleet.

Each cell's traffic generator, the run with its reference check, the
control and the trace reduction run here on the CPU with the look for a
chip bypassed (`rehearse.patch`); the faults a cell can have
(`faults.for_cell`, from its configuration and mix) must turn ``correct``
false. Nothing here keys on a cell's name: a cell added to
``BENCHMARK.json`` is rehearsed like the others.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""
from __future__ import annotations

import collections
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import faults  # noqa: E402
import reference  # noqa: E402
import rehearse  # noqa: E402
import run  # noqa: E402
import traffic_gen  # noqa: E402
import verdict  # noqa: E402

CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
SEED = 2**33 + 12345  # wider than 32 bits: any whole number is a valid seed
SECONDS = rehearse.SECONDS


@pytest.fixture
def tiny(monkeypatch):
    """Runs on the CPU at the rehearsal's fleet, for a short window."""
    rehearse.patch(monkeypatch.setattr)


def test_every_seed_sends_the_same_work():
    for w in cells.benchmark()["workloads"]:
        mix = cells.load_cell(w["name"]).mix

        def work(seed):
            # each parameter's values per kind; which vehicle is drawn, and
            # how draws pair up, is the seed's to choose
            reqs = traffic_gen.generate(mix, seed, 30.0, 100_000)
            return collections.Counter(
                (r.kind, k, str(v)) for r in reqs for k, v in r.params.items()
                if k != "client") + collections.Counter(r.kind for r in reqs), len(reqs)

        (a, n1), (b, n2) = work(1), work(SEED)
        assert n1 == n2 == round(mix["rate_per_s"] * 30)
        assert a == b


def test_schedule_is_a_function_of_the_seed():
    mix = cells.load_cell(CELLS[0]).mix
    one = traffic_gen.generate(mix, SEED, 10.0, 1000)
    assert one == traffic_gen.generate(mix, SEED, 10.0, 1000)
    assert one != traffic_gen.generate(mix, SEED + 1, 10.0, 1000)
    assert all(0 <= r.due_s < 10.0 for r in one)
    assert [r.due_s for r in one] == sorted(r.due_s for r in one)


def test_zipf_keys_follow_the_listed_order():
    mix = cells.load_cell(CELLS[0]).mix
    reqs = traffic_gen.generate(mix, 3, 100.0, 10)
    keys = collections.Counter((r.params["signal"], r.params["window"]) for r in reqs)
    listed = [(k["signal"], k["window"]) for k in mix["kinds"][0]["draw"]["key"]["keys"]]
    counts = [keys[k] for k in listed]
    assert counts == sorted(counts, reverse=True) and counts[0] > 2 * counts[-1]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_on_cpu(tiny, workload):
    out = run.run(workload, SEED, SECONDS, False)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in cells.load_cell(workload).end_to_end}
    assert "setup_s" in names and set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_its_layers(tiny, workload):
    out = run.run(workload, SEED + 1, SECONDS, True)
    assert out["correct"], out["checks"]
    cell = cells.load_cell(workload)
    want = {m["name"] for m in cell.per_layer}
    got = set(out["metrics"])
    # the CPU trace has no jit_tick module on a device line of its own
    assert got <= want and want - got <= {"plane_step_ms"}, (want, got)
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(tiny, workload):
    _, ev = run.session(workload, SEED + 2, SECONDS, False)
    assert verdict.verdict(verdict.compare(ev, SEED))
    low = verdict.compare(ev, SEED, lower=True)
    assert not verdict.verdict(low), low


# --------------------------------------------------------------------- #
# where a deployment plugs in                                            #
# --------------------------------------------------------------------- #
#: every limit the accepted cells are held to, as PERF.md sets them
ACCEPTED_LIMITS = {
    "ring_gap": 1e-3, "ring_nan_miss": 0, "stats_miss": 0, "stats_mean_gap": 3e-3,
    "stats_var_gap": 1e-2, "window_miss": 0, "signal_gap": 1e-3, "gauge_miss": 0,
    "unanswered": 0, "failed": 0,
}


def test_merged_limits_keep_every_accepted_limit():
    """The check modules' limits, merged, hold each accepted limit key for
    key, in its own type (a count's limit stays the integer 0); a new
    check module may only add numbers of its own."""
    got = {k: repr(verdict.LIMITS.get(k)) for k in ACCEPTED_LIMITS}
    assert got == {k: repr(v) for k, v in ACCEPTED_LIMITS.items()}
    assert {k: m.__name__ for k, m in verdict.checks_for({}).items()} == {
        "fleet_stats": "checks.stats", "quantile": "checks.stats",
        "window": "checks.window", "signal": "checks.signal", "gauges": "checks.gauges"}


def test_a_kind_with_no_check_cannot_run(tiny, monkeypatch):
    """A mix that sends a kind no check module covers, and that OK_ONLY
    does not name, ends the run with a non-zero code before set-up."""
    assert verdict.unchecked(["platform", "gauges", "window"], {}) == []
    assert verdict.unchecked(["cohort_stats", "gauges"], {}) == ["cohort_stats"]
    load = cells.load_cell

    def with_new_kind(name):
        c = load(name)
        c.mix["kinds"].append({"kind": "cohort_stats", "share": 0.1})
        return c

    def no_setup(*a, **k):
        pytest.fail("set-up began")

    monkeypatch.setattr(cells, "load_cell", with_new_kind)
    monkeypatch.setattr(run, "require_chip", no_setup)
    monkeypatch.setattr(cells, "build", no_setup)
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", CELLS[0], "--seed", str(SEED), "--seconds", "1"])
    assert stop.value.code not in (0, None)


def test_a_configurations_own_check_reaches_only_its_answers(monkeypatch):
    """A check module that a configuration names checks that
    configuration's answers, never another's; one that takes a kind the
    common checks hold is refused."""
    import types

    own = types.SimpleNamespace(__name__="checks.platform_own", KINDS=("platform",),
                                LIMITS={}, check=lambda ev, items, seed, lower=False: {})
    twin = types.SimpleNamespace(__name__="checks.window_twin", KINDS=("window",),
                                 LIMITS={}, check=own.check)
    monkeypatch.setitem(verdict.MODULES, "platform_own", own)
    monkeypatch.setitem(verdict.MODULES, "window_twin", twin)
    for w in CELLS:
        config = cells.load_cell(w).config
        assert set(verdict.checks_for(config)) == {
            "fleet_stats", "quantile", "window", "signal", "gauges"}, w
    assert verdict.checks_for({"checks": ["platform_own"]})["platform"] is own
    with pytest.raises(ValueError):
        verdict.checks_for({"checks": ["window_twin"]})
    with pytest.raises(ValueError):
        verdict.checks_for({"checks": ["no_such_check"]})


@pytest.mark.parametrize("workload", CELLS)
def test_recorder_is_on_only_where_a_reader_asks(tiny, monkeypatch, workload):
    """A traced run switches the program's recorder on exactly where one of
    the cell's metric readers declares ``PROGRAM``; no accepted reader does."""
    import readings

    obs = cells.recorder()
    calls = []
    monkeypatch.setattr(obs, "enable", lambda: calls.append("enable"))
    out = run.run(workload, SEED + 4, SECONDS, True)
    asks = readings.wants_program(m["name"] for m in cells.load_cell(workload).per_layer)
    assert calls == (["enable"] if asks else [])
    assert out["correct"], out["checks"]


def _cell(kinds, p_leave, config_name="no-such-config"):
    return cells.Cell(name="synthetic", chips=1, config_name=config_name,
                      config={"sim": {"p_leave": p_leave}},
                      mix={"kinds": [{"kind": k, "share": 1.0} for k in kinds]},
                      end_to_end=[], per_layer=[])


def test_faults_follow_the_configuration_and_mix():
    """The rule gives the statistics mix without churn, and the dashboard
    mix with and without churn, the faults each was rehearsed with."""
    def names(cell):
        return [f.__name__ for f in faults.for_cell(cell)]

    dashboard = ["gauges", "platform", "signal", "window"]
    assert names(_cell(["fleet_stats", "quantile"], 0.0)) == [
        "stale_step", "half_fold", "altered_answer"]
    assert names(_cell(dashboard, 0.0005)) == [
        "stale_step", "half_step", "altered_answer", "wrong_churn"]
    assert names(_cell(dashboard, 0.0)) == ["stale_step", "half_step", "altered_answer"]


def test_rehearsal_rate_follows_the_mix():
    """The mix's own rate, held between 15 and 60 req/s: the statistics mix
    (1.6 req/s) rehearses at 15, the dashboard (6400 req/s) at 60."""
    assert rehearse.cpu_rate({"rate_per_s": 1.6}) == 15.0
    assert rehearse.cpu_rate({"rate_per_s": 6400.0}) == 60.0
    assert rehearse.cpu_rate({"rate_per_s": 25.0}) == 25.0


# --------------------------------------------------------------------- #
# faults planted under the timed path                                    #
# --------------------------------------------------------------------- #
FAULTS = [(w, f) for w in CELLS for f in faults.for_cell(cells.load_cell(w))]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_fault_turns_correct_false(tiny, monkeypatch, workload, fault):
    rehearse.plant(monkeypatch.setattr, fault)
    out = run.run(workload, SEED + 3, SECONDS, False)
    assert out["correct"] is False, out["checks"]


# --------------------------------------------------------------------- #
# the reference against itself                                           #
# --------------------------------------------------------------------- #
def test_fold_matches_the_per_vehicle_loop():
    rng = np.random.default_rng(0)
    x = rng.normal(5.0, 2.0, size=(40, 7)).astype(np.float32)
    x[rng.random(x.shape) < 0.2] = np.nan
    x[:, 3] = np.nan
    spec = reference.Spec(40, 8, 0.0, 10.0, 5)
    f = reference.fold(x, spec)
    for j in range(x.shape[1]):
        v = x[:, j][~np.isnan(x[:, j])]
        c, mean, m2 = np.float32(0), np.float32(0), np.float32(0)
        for s in v:
            c = c + np.float32(1)
            d = s - mean
            mean = mean + d / c
            m2 = m2 + d * (s - mean)
        assert f.counts[j] == len(v) and f.means[j] == mean and f.m2s[j] == m2
        idx = (v[:, None] >= spec.edges()[None, :]).sum(axis=1)
        assert list(f.hists[j]) == list(np.bincount(idx, minlength=8))
        if len(v):
            ranks = np.minimum((2 * np.arange(5) + 1) * len(v) // 10, len(v) - 1)
            assert list(f.qvals[j]) == list(np.sort(v)[ranks])


def test_reference_churn_is_the_fleets():
    """The reference's churn, rebuilt from the seed and the configuration
    alone, takes the same vehicles offline at the same ticks as the fleet."""
    config = cells.load_cell("dropout-100k.dashboard").config
    config["sim"].update(n_clients=300, p_leave=0.05, p_return=0.3)
    sim, gw = cells.build(config, cells.FLEET_SEED, jax.devices())
    seen = {sim.plane.t: cells.online(sim).copy()}
    for _ in range(60):
        gw.tick()
        seen[sim.plane.t] = cells.online(sim).copy()
    want = reference.churn(cells.FLEET_SEED, 300, 0.05, 0.3, sim.plane.t)
    flips = 0
    for t, on in seen.items():
        assert np.array_equal(want.online(np.arange(300), [t])[0], on), t
        assert want.count(t) == int(on.sum())
        flips += int((~on).sum())
    assert flips > 300  # the churn did real work


def test_bf16_rounds_to_nearest_even():
    x = np.asarray([1.0, 1.00390625, 1.01171875, -3.0, np.nan], np.float32)
    got = reference.to_bf16(x)
    assert list(got[:4]) == [1.0, 1.0, 1.015625, -3.0] and np.isnan(got[4])
