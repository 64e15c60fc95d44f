"""What a per-layer metric reader is given, and how readers are found.

Each per-layer metric in ``BENCHMARK.json`` is read by
``metrics/<name>.py``, whose ``read(ctx)`` returns the value, or None when
the run gave it nothing to read (the metric is then left out).
"""
from __future__ import annotations

import dataclasses
import importlib.util

import numpy as np

import trace_reduce as tr
from cells import HERE


@dataclasses.dataclass
class Context:
    trace: tr.Reduced
    spans: dict           # harness and layer spans of the window, host clock
    folds: list           # one record per sketch fold in the window
    stats_misses: list    # host seconds of each statistics computation on a miss
    counters: dict        # program counters' increase over the window
    peaks: dict           # the chip's peaks (peaks.json)

    def mean_span_ms(self, name: str) -> float | None:
        s = self.spans.get(name, [])
        return 1e3 * float(np.mean([b - a for a, b in s])) if s else None

    def delta(self, key: str) -> int:
        return int(self.counters.get(key, 0))


def read(name: str, ctx: Context):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)
