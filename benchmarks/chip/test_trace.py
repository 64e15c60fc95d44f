"""The trace reduction, on a trace recorded on a TPU v5e and kept here.

``fixtures/tpu_stats_skew.xplane.pb`` is the profile of a two-second
traced window of ``fuel-100k.stats-skew`` cut to 2,048 vehicles, recorded
with ``run.py``'s own profiler options. It runs on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_trace.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tpu_stats_skew.xplane.pb"


def iv(*pairs):
    return np.asarray(pairs, np.float64).reshape(-1, 2)


def test_interval_arithmetic():
    a = iv((0, 10), (5, 12), (20, 30))
    assert tr.union(a).tolist() == [[0, 12], [20, 30]]
    assert tr.length(a) == 27 and tr.length(tr.union(a)) == 22
    b = iv((8, 22), (25, 26))
    assert tr.intersect(a, b).tolist() == [[8, 12], [20, 22], [25, 26]]
    assert tr.subtract(a, b).tolist() == [[0, 8], [22, 25], [26, 30]]
    assert tr.subtract(a, iv()).tolist() == [[0, 12], [20, 30]]
    assert tr.intersect(a, iv()).shape == (0, 2)


@pytest.fixture(scope="module")
def red():
    return tr.load(FIXTURE)


def test_the_recorded_trace_has_a_tpu_and_the_harness_spans(red):
    assert len(red.devices) == 1 and len(red.devices[0].ops.start) > 0
    for name in ("bench.window", "bench.tick", "bench.service", "bench.plane_step",
                 "bench.stats", "bench.fold"):
        assert len(tr.spans(red, name)), name
    assert 1.5 < red.window_s < 3.0


def test_busy_and_idle_partition_the_window(red):
    busy = tr.busy_s(red)
    assert 0 < busy < red.window_s
    idle = sum(s for _, s in tr.idle_by_span(red, k=100))
    assert idle == pytest.approx(red.window_s - busy, rel=1e-9, abs=1e-9)


def test_layers_find_their_device_time(red):
    fold = tr.device_s_in(red, "bench.fold")
    merge = tr.device_s_in(red, "bench.stats", outside="bench.fold")
    stats = tr.device_s_in(red, "bench.stats")
    assert fold > 0 and merge > 0
    assert fold + merge == pytest.approx(stats, rel=1e-9)
    assert stats <= tr.busy_s(red) * (1 + 1e-9)
    # a program's runs span its ops and the gaps between them
    assert 0 < tr.module_s(red, "jit_tick") < red.window_s


def test_top_ops_are_sorted_and_inside_the_busy_time(red):
    ops = tr.top_ops(red)
    assert 0 < len(ops) <= 10
    secs = [s for _, s in ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= sum(tr.length(np.stack([d.ops.start, d.ops.end], 1))
                            for d in red.devices) * 1e-9 * (1 + 1e-9)
    assert all(":" in name for name, _ in ops)


def test_program_spans_take_idle_first_where_the_trace_has_them():
    """Idle inside a program span goes to it before the harness span
    around it; without program spans the harness spans get it as before."""
    dev = tr.Device(tr.Ops(np.asarray([0.0, 90.0]), np.asarray([10.0, 100.0]), ["a", "b"]),
                    tr.Ops(np.zeros(0), np.zeros(0), []))
    harness = {"bench.window": iv((0, 100)), "bench.tick": iv((10, 90))}
    red = tr.Reduced((0.0, 100.0), [dev], dict(harness))
    assert tr.idle_by_span(red) == [["bench.tick", 80e-9]]
    program = {"spans": {"name": np.asarray(["gateway.admit", "plane.ring_sync"]),
                         "parent": np.asarray([-1, 0])}}
    assert tr.program_order(program) == ("plane.ring_sync", "gateway.admit")
    red = tr.Reduced((0.0, 100.0), [dev], {**harness, "plane.ring_sync": iv((20, 50)),
                                           "gateway.admit": iv((15, 60))},
                     tr.program_order(program))
    got = dict(tr.idle_by_span(red))
    assert got == pytest.approx({"plane.ring_sync": 30e-9, "gateway.admit": 15e-9,
                                 "bench.tick": 35e-9})


def test_program_order_follows_the_recorders_nesting():
    """Innermost first by the ``parent`` column, whatever the names: a
    name nested deeper anywhere goes ahead; ties go by name."""
    program = {"spans": {
        "name": np.asarray(["cohort.tick", "cohort.merge", "cohort.scan", "jax.compile",
                            "cohort.tick", "cohort.scan", "cohort.merge"]),
        "parent": np.asarray([-1, 0, 1, -1, -1, 4, 4])}}
    assert tr.program_order(program) == (
        "cohort.scan", "cohort.merge", "cohort.tick", "jax.compile")
    assert tr.program_order({}) == ()


def test_a_program_span_of_any_name_reaches_the_breakdown(tmp_path):
    """A span the program records under a name the harness has never seen
    is kept from a live trace and charged its idle time, innermost first,
    once the program's records name it; without them it is left out."""
    import time

    import jax
    import jax.numpy as jnp

    import cells

    obs = cells.recorder()
    double = jax.jit(lambda x: x * 2)
    x = jnp.ones(1024)
    double(x).block_until_ready()
    obs.reset()
    obs.enable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("bench.window"):
                with obs.span("cohort.tick"):
                    with obs.span("cohort.segmented_merge"):
                        time.sleep(0.05)
                    double(x).block_until_ready()
                    time.sleep(0.02)
    finally:
        obs.disable()
    program = obs.records()
    obs.reset()
    path = tr.find_xplane(tmp_path)
    red = tr.load(path, program)
    assert red.program_order == ("cohort.segmented_merge", "cohort.tick")
    assert {"cohort.tick", "cohort.segmented_merge"} <= set(red.spans)
    gaps = dict(tr.idle_by_span(red))
    assert gaps["cohort.segmented_merge"] > 0.04 and gaps["cohort.tick"] > 0.01
    assert not {"cohort.tick", "cohort.segmented_merge"} & set(tr.load(path).spans)
