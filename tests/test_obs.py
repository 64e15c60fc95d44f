"""The program tracer (`repro.core.obs`) and the counters beside it.

* off: nothing is recorded and no profiler annotation is entered
* on: spans nest as the layers call each other, self time is duration
  less the children, backend compiles land as ``jax.compile`` spans
* each request is stamped submit <= admit <= done, once per served request
* the gateway's and planes' counters agree with ``served``/``ring_syncs``
* tracing does not change a byte of the response stream
* ``d2h_bytes`` counts exactly the bytes each device->host copy moves
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import obs
from repro.fleet.simulator import Backends, FleetSimulator, SimConfig
from repro.kernels.sketch import SketchSpec
from repro.serve import FleetGateway

SIGNAL = "Vehicle.FuelRate"
WINDOW = 16
PLANES = ("host", "sharded")


@pytest.fixture
def tracer():
    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()


def make_gateway(plane="host", n=48, seed=7):
    sim = FleetSimulator(SimConfig(
        n_clients=n, seed=seed, scenario="mixed", signal_history=32,
        backends=Backends(plane=plane),
    ))
    for _ in range(WINDOW + 2):
        sim.tick()
    return FleetGateway(sim)


def drive(gw, ticks=3):
    """Two sessions of reads over a few ticks, stats keys switching so the
    caches both hit and miss, then one submission to completion."""
    a, b = gw.session("ana"), gw.session("bob")
    for k in range(ticks):
        a.gauges()
        b.fleet_stats(SIGNAL, window=WINDOW)
        a.quantile(SIGNAL, 0.9, window=WINDOW)
        b.quantile(SIGNAL, 0.5, window=WINDOW // 2 if k % 2 else WINDOW)
        a.window(k, SIGNAL, 5)
        b.signal(k + 1, SIGNAL)
        a.platform()
        gw.tick()
    b.submit_window(SIGNAL, window=WINDOW, sketch=True)
    gw.run_until_idle()
    return sorted((r for s in gw._sessions.values() for r in s.inbox),
                  key=lambda r: r.seq)


def spans_named(rec, name):
    return np.flatnonzero(rec["spans"]["name"] == name)


# --------------------------------------------------------------------- #
# off                                                                   #
# --------------------------------------------------------------------- #
def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    obs.disable()
    obs.reset()

    def no_annotation(name):
        raise AssertionError(f"annotation {name!r} entered while off")

    monkeypatch.setattr(obs, "_annotation", no_annotation)
    assert obs.span("gateway.tick") is obs.span("plane.step")
    drive(make_gateway())
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5))  # a fresh compile
    rec = obs.records()
    assert len(rec["spans"]["name"]) == 0
    assert all(len(v) == 0 for v in rec["requests"].values())


# --------------------------------------------------------------------- #
# spans                                                                 #
# --------------------------------------------------------------------- #
def test_nested_spans_carry_parent_and_self_time(tracer):
    with obs.span("a"):
        with obs.span("b"):
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    with obs.span("e"):
        pass
    sp = obs.records()["spans"]
    assert sp["name"].tolist() == ["a", "b", "c", "d", "e"]
    assert sp["parent"].tolist() == [-1, 0, 1, 0, -1]
    assert np.all(sp["t0"] <= sp["t1"])
    dur = sp["t1"] - sp["t0"]
    want = dur - np.asarray([dur[1] + dur[3], dur[2], 0.0, 0.0, 0.0])
    np.testing.assert_allclose(obs.self_seconds(sp), want, rtol=0, atol=1e-12)


def test_reset_forgets_and_an_open_span_records_nothing(tracer):
    with obs.span("before"):
        obs.reset()
        with obs.span("after"):
            pass
    sp = obs.records()["spans"]
    assert sp["name"].tolist() == ["after"] and sp["parent"].tolist() == [-1]


#: (span, the spans it opens inside) as the layers call each other; the
#: vehicles' sketch payloads fold from inside the service sweep
NESTING = [
    ("engine.drain", {"gateway.tick"}),
    ("gateway.admit", {"engine.drain"}),
    ("gateway.stats", {"gateway.admit"}),
    ("gateway.merge", {"gateway.stats"}),
    ("plane.fold", {"gateway.stats", "service.sweep"}),
    ("plane.step", {"gateway.tick"}),
    ("service.sweep", {"gateway.tick"}),
]


@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("child,parents", NESTING, ids=[c for c, _ in NESTING])
def test_layer_spans_nest(tracer, plane, child, parents):
    gw = make_gateway(plane)
    obs.reset()
    drive(gw)
    rec = obs.records()
    sp, idx = rec["spans"], spans_named(rec, child)
    assert len(idx), child
    assert set(sp["name"][sp["parent"][idx]]) == parents
    assert np.all(sp["t0"][sp["parent"][idx]] <= sp["t0"][idx])
    assert np.all(sp["t1"][idx] <= sp["t1"][sp["parent"][idx]])


def drive_tolls(ticks=4):
    """A Linear Road fleet, its toll step after every plane step, and its
    three reads each tick."""
    sim = FleetSimulator(SimConfig(
        n_clients=64, seed=7, scenario="linear-road", signal_history=12,
        backends=Backends(plane="sharded"),
    ))
    gw = FleetGateway(sim)
    s = gw.session("motorist")
    for k in range(ticks):
        s.ask("account_balance", client=k)
        s.ask("toll_notice", client=k + 1)
        s.ask("daily_expenditure", client=k, xway=k % 2, day=k + 1)
        gw.tick()
    return gw


def test_toll_spans_nest_and_record_nothing_while_off(tracer):
    gw = drive_tolls()
    rec = obs.records()
    sp = rec["spans"]
    for child, parent, n in (("plane.tolls", "gateway.tick", gw.sim.t),
                             ("gateway.lr_read", "gateway.admit", 3 * gw.sim.t)):
        idx = spans_named(rec, child)
        assert len(idx) == n, child
        assert set(sp["name"][sp["parent"][idx]]) == {parent}
        assert np.all(sp["t1"][idx] <= sp["t1"][sp["parent"][idx]])
    obs.disable()
    obs.reset()
    drive_tolls()
    assert len(obs.records()["spans"]["name"]) == 0


def test_ring_sync_is_a_span_only_when_it_copies(tracer):
    gw = make_gateway("sharded")
    s = gw.session("ana")
    for k in range(4):
        s.window(k, SIGNAL, 4)
        s.window(k + 1, SIGNAL, 4)  # same tick: the mirror is warm
        gw.tick()
    rec = obs.records()
    idx = spans_named(rec, "plane.ring_sync")
    assert len(idx) == 4 == gw.sim.plane.ring_syncs
    assert set(rec["spans"]["name"][rec["spans"]["parent"][idx]]) == {"gateway.admit"}


def test_backend_compiles_are_spans_without_parent(tracer):
    with obs.span("outer"):
        jax.jit(lambda x: jnp.sin(x) * 7 - 2)(jnp.arange(11.0))
    rec = obs.records()
    idx = spans_named(rec, "jax.compile")
    assert len(idx) >= 1
    sp = rec["spans"]
    assert np.all(sp["parent"][idx] == -1) and np.all(sp["t0"][idx] < sp["t1"][idx])
    outer = spans_named(rec, "outer")[0]
    assert np.all(sp["t1"][idx] <= sp["t1"][outer])


# --------------------------------------------------------------------- #
# request stamps                                                        #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("plane", PLANES)
def test_each_served_request_has_ordered_stamps(tracer, plane):
    gw = make_gateway(plane)
    out = drive(gw)
    req = obs.records()["requests"]
    assert req["seq"].tolist() == [r.seq for r in out]
    assert req["kind"].tolist() == [r.kind for r in out]
    assert len(req["seq"]) == sum(gw.served.values())
    assert np.all(req["t_submit"] <= req["t_admit"])
    assert np.all(req["t_admit"] <= req["t_done"])
    # a submission answers ticks after its admission, a read at once
    sub = req["kind"] == "submit_window"
    assert sub.sum() == 1
    assert np.all(req["t_done"][sub] - req["t_admit"][sub]
                  > np.max(req["t_done"][~sub] - req["t_admit"][~sub]))


def test_requests_submitted_while_off_have_no_record(tracer):
    gw = make_gateway()
    obs.disable()
    gw.session("ana").gauges()
    obs.enable()
    gw.session("ana").platform()
    gw.tick()
    req = obs.records()["requests"]
    assert req["kind"].tolist() == ["platform"]
    assert np.isfinite(req["t_done"]).all()


# --------------------------------------------------------------------- #
# counters                                                              #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("traced", [False, True], ids=["off", "on"])
@pytest.mark.parametrize("plane", PLANES)
def test_counters_agree_with_served_and_ring_syncs(request, plane, traced):
    if traced:
        request.getfixturevalue("tracer")
    gw = make_gateway(plane)
    drive(gw)
    p = gw.sim.plane
    served = gw.served
    stats_reads = served.get("fleet_stats", 0) + served.get("quantile", 0)
    assert gw.admitted == sum(served.values())
    assert gw.stats_hits + gw.stats_misses == stats_reads
    assert gw.stats_hits > 0 and gw.stats_misses > 0
    # every stats miss asks the plane once; the window submission's
    # vehicles ask it once each, all from one fold
    assert p.sketch_misses >= gw.stats_misses
    assert p.sketch_hits > 0
    if traced:
        rec = obs.records()
        assert len(spans_named(rec, "gateway.stats")) == gw.stats_misses
        assert len(spans_named(rec, "plane.fold")) == p.sketch_misses
        assert len(spans_named(rec, "gateway.admit")) <= p.t
    if plane == "sharded":
        assert p.ring_syncs == served["window"]  # one window read a tick
        assert p.d2h_bytes >= p.ring_syncs * p._hist.nbytes


# --------------------------------------------------------------------- #
# the response stream                                                   #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("plane", PLANES)
def test_tracing_changes_no_response_byte(plane):
    def run(on):
        obs.reset()
        if on:
            obs.enable()
        try:
            return [r.encode() for r in drive(make_gateway(plane))]
        finally:
            obs.disable()

    off, on = run(False), run(True)
    assert len(obs.records()["spans"]["name"]) > 0
    obs.reset()
    assert off == on and len(off) > 20


# --------------------------------------------------------------------- #
# device -> host bytes                                                  #
# --------------------------------------------------------------------- #
def test_d2h_bytes_count_each_copy_exactly():
    gw = make_gateway("sharded")
    p = gw.sim.plane
    ring = p._dhist.nbytes
    for _ in range(3):
        gw.tick()
        before, syncs = p.d2h_bytes, p.ring_syncs
        p.window(0, SIGNAL, 4)
        p.window(1, SIGNAL, 4)
        assert p.ring_syncs == syncs + 1
        assert p.d2h_bytes - before == ring
    before = p.d2h_bytes
    p.read(0, SIGNAL)
    p.read(1, SIGNAL)
    assert p.d2h_bytes - before == p._dvalues.nbytes
    before = p.d2h_bytes
    spec = SketchSpec(window=WINDOW)
    p.fleet_sketch(SIGNAL, spec)
    p.fleet_sketch(SIGNAL, spec)  # a cache hit copies nothing
    assert p.d2h_bytes - before == 4 * spec.dim * p._capacity
