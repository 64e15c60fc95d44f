"""CPU rehearsal of the chip benchmark at a tiny fleet.

Each cell's traffic generator, the run with its reference check, the
control and the trace reduction run here on the CPU with the look for a
chip bypassed; the faults a cell can have must turn ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""
from __future__ import annotations

import collections
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic_gen  # noqa: E402
import verdict  # noqa: E402

CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
SEED = 2**33 + 12345  # wider than 32 bits: any whole number is a valid seed
#: the rehearsal's fleet and load, small enough for the CPU
N, RATE, SECONDS = 1024, {"stats-skew": 15.0, "dashboard": 60.0}, 1.0


@pytest.fixture
def tiny(monkeypatch):
    """Runs on the CPU at N vehicles, for a short window."""
    load = cells.load_cell

    def small(name):
        c = load(name)
        c.config["sim"]["n_clients"] = N
        c.mix["rate_per_s"] = RATE[next(w["traffic"] for w in cells.benchmark()["workloads"]
                                        if w["name"] == name)]
        return c

    monkeypatch.setattr(cells, "load_cell", small)
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peaks_of", lambda kind: {"hbm_bytes_per_s": 819e9})
    return small


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
# faults planted under the timed path                                    #
# --------------------------------------------------------------------- #
def _stale_step(sim, gw):
    """The plane step returns its state unchanged."""
    p = sim.plane
    p._tick_fn = lambda t, hist, off: (p._dvalues, hist)


def _half_step(sim, gw):
    """Half of the fleet left out of each plane step: its ring rows go
    unwritten (NaN), and every read is served from the rest."""
    p = sim.plane
    orig = p._tick_fn
    half = p.n_clients // 2
    blank = jax.jit(lambda h, s: h.at[s, half:, :].set(jnp.nan))

    def step(t, hist, off):
        vals, h = orig(t, hist, off)
        return vals, blank(h, t % h.shape[0])

    p._tick_fn = step


def _half_fold(sim, gw):
    """Half of the fleet left out of each sketch fold: the statistics are
    taken over the rest."""
    p = sim.plane
    orig = p.compute_sketches

    def fold(name, spec, **kw):
        sk = orig(name, spec, **kw)
        half = sk.counts.shape[0] // 2
        sk.counts[half:] = 0
        sk.hists[half:] = 0
        sk.qvals[half:] = np.nan
        return sk

    p.compute_sketches = fold


def _altered_answer(sim, gw):
    """One field of every answer altered where it is produced."""
    for name in ("_read_fleet_stats", "_read_quantile", "_read_window",
                 "_read_signal", "_read_gauges"):
        orig = getattr(gw, name)

        def alter(params, orig=orig):
            body = dict(orig(params))
            for k in ("mean", "value", "online"):
                if isinstance(body.get(k), (int, float)):
                    body[k] = body[k] + 1 if k == "online" else body[k] * (1 + 1e-3) + 1e-3
            if body.get("values"):
                body["values"] = body["values"][:-1] + [body["values"][-1] + 1e-3]
            return body

        setattr(gw, name, alter)


def _wrong_churn(sim, gw):
    """Churn takes the wrong vehicles offline: each ignition toggle lands
    on the next vehicle in the fleet."""
    pool, toggle = sim.pool, sim.churn._toggle
    n = len(pool.vehicles)

    def next_one(cid):
        i = pool.vehicles[cid].metadata["index"]
        toggle(next(c for c, v in pool.vehicles.items()
                    if v.metadata["index"] == (i + 1) % n))

    sim.churn._toggle = next_one


#: the faults each cell can have; neither cell has an exchange between
#: chips to leave out (one chip each)
FAULTS = {
    "fuel-100k.stats-skew": [_stale_step, _half_fold, _altered_answer],
    "dropout-100k.dashboard": [_stale_step, _half_step, _altered_answer, _wrong_churn],
}


@pytest.mark.parametrize("workload,fault", [(w, f) for w in CELLS for f in FAULTS[w]],
                         ids=[f"{w}-{f.__name__[1:]}" for w in CELLS for f in FAULTS[w]])
def test_fault_turns_correct_false(tiny, monkeypatch, workload, fault):
    build = cells.build

    def broken(config, seed, devices):
        sim, gw = build(config, seed, devices)
        fault(sim, gw)
        return sim, gw

    monkeypatch.setattr(cells, "build", broken)
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
