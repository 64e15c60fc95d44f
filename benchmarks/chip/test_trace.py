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
