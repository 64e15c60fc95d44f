"""What a per-layer metric reader is given, and how readers are found.

Each per-layer metric in ``BENCHMARK.json`` is read by
``metrics/<name>.py``, whose ``read(ctx)`` returns the value, or None when
the run gave it nothing to read (the metric is then left out). A reader
that needs the program's own records sets ``PROGRAM = True``: a traced run
then switches the program's recorder on for its window, and the records
reach every reader as ``ctx.program``. Where no reader of a cell asks,
the recorder stays off and ``ctx.program`` is ``{}``.
"""
from __future__ import annotations

import dataclasses
import functools
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
    #: the program's records of the window (`repro.core.obs.records`)
    program: dict = dataclasses.field(default_factory=dict)

    def mean_span_ms(self, name: str) -> float | None:
        s = self.spans.get(name, [])
        return 1e3 * float(np.mean([b - a for a, b in s])) if s else None

    def delta(self, key: str) -> int:
        return int(self.counters.get(key, 0))


@functools.cache
def _reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def wants_program(names) -> bool:
    """Whether any of these metrics' readers needs the program's records."""
    return any(getattr(_reader(n), "PROGRAM", False) for n in names)


def read(name: str, ctx: Context):
    return _reader(name).read(ctx)
