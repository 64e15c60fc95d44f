"""Program spans, request stamps and compile intervals, off by default.

The gateway, the engine, the signal planes and the simulator open a
`span` around each layer's work (``gateway.tick``, ``engine.drain``,
``gateway.admit``, ``gateway.stats``, ``gateway.merge``, ``plane.fold``,
``plane.step``, ``plane.ring_sync``, ``service.sweep``; under Linear
Road ``plane.tolls``, the toll step after the plane step, and
``gateway.lr_read``, a balance, expenditure or notice read), and the
gateway stamps each request when it is submitted, admitted and answered.
Counters are not kept here: each layer's instance holds its own (the
toll state's ``crossings``, ``tolls_charged``, ``accidents`` and
``alerts``, for example).

Off (the default), `span` returns one preallocated no-op object after a
single module-global bool check, and the stamps return at the same check:
nothing is timed, annotated or allocated.

On (`enable`), each span enters a `jax.profiler.TraceAnnotation` of its
name, so it lands in a profiler trace on the device trace's clock, and
appends ``(name, t0, t1, parent)`` to in-memory columns on
`time.perf_counter`; ``parent`` is the index of the enclosing span (-1 at
the top). Backend compiles are kept as ``jax.compile`` spans
``[t - duration, t]`` with no parent, from a `jax.monitoring` listener;
they exist only in memory. Request stamps are columns too (``array``, one
machine word per field), never an object per request, so a long traced
window adds no long-lived Python objects for the collector to walk.

The tick is single-threaded: one stack of open spans serves the process.
There is no exporter; whoever enabled the tracer reads it through
`records`.
"""
from __future__ import annotations

import time
from array import array

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_on = False
#: bumped by `reset`: a span opened before it records nothing at its close
_gen = 0
_annotation = None
_listening = False

_names: list[str] = []
_t0 = array("d")
_t1 = array("d")
_parent = array("q")
_stack: list[int] = []

_kinds: dict[str, int] = {}
_sub_seq, _sub_kind, _sub_t = array("q"), array("H"), array("d")
_adm_seq, _adm_t = array("q"), array("d")
_done_seq, _done_t = array("q"), array("d")


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "i", "gen", "ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.gen = _gen
        self.i = len(_names)
        _names.append(self.name)
        _t0.append(time.perf_counter())
        _t1.append(np.nan)
        _parent.append(_stack[-1] if _stack else -1)
        _stack.append(self.i)
        return self

    def __exit__(self, *exc):
        if self.gen == _gen:
            _t1[self.i] = time.perf_counter()
            _stack.pop()
        self.ann.__exit__(*exc)
        return False


def span(name: str):
    """A context manager timing one layer's work while the tracer is on."""
    return _Span(name) if _on else _OFF


def _compiled(event: str, duration: float, **kw) -> None:
    if _on and event == COMPILE_EVENT:
        t = time.perf_counter()
        _names.append("jax.compile")
        _t0.append(t - duration)
        _t1.append(t)
        _parent.append(-1)


def enable() -> None:
    """Start recording: spans, request stamps and backend compiles."""
    global _on, _annotation, _listening
    import jax

    _annotation = jax.profiler.TraceAnnotation
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_compiled)
        _listening = True
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until `reset`."""
    global _on
    _on = False


def reset() -> None:
    """Forget every record (spans still open record nothing)."""
    global _gen
    _gen += 1
    _names.clear()
    _stack.clear()
    _kinds.clear()
    for col in (_t0, _t1, _parent, _sub_seq, _sub_kind, _sub_t,
                _adm_seq, _adm_t, _done_seq, _done_t):
        del col[:]


# --------------------------------------------------------------------- #
# request stamps                                                        #
# --------------------------------------------------------------------- #
def stamp_submit(seq: int, kind: str) -> None:
    if _on:
        code = _kinds.setdefault(kind, len(_kinds))
        _sub_seq.append(seq)
        _sub_kind.append(code)
        _sub_t.append(time.perf_counter())


def stamp_admit(seq: int) -> None:
    if _on:
        _adm_seq.append(seq)
        _adm_t.append(time.perf_counter())


def stamp_done(seq: int) -> None:
    if _on:
        _done_seq.append(seq)
        _done_t.append(time.perf_counter())


def _join(seq: np.ndarray, seqs: array, ts: array) -> np.ndarray:
    """``ts`` placed at the rows of ``seq`` (sorted) that ``seqs`` name;
    NaN where a request has no such stamp."""
    out = np.full(len(seq), np.nan)
    s, t = np.array(seqs, np.int64), np.array(ts, np.float64)
    if len(seq) and len(s):
        pos = np.minimum(np.searchsorted(seq, s), len(seq) - 1)
        hit = seq[pos] == s
        out[pos[hit]] = t[hit]
    return out


# --------------------------------------------------------------------- #
# reading                                                               #
# --------------------------------------------------------------------- #
def records() -> dict:
    """What was recorded, as numpy columns.

    ``spans``: ``name``, ``t0``, ``t1``, ``parent`` (one row per span, in
    the order they opened; compiles in the order they ended; ``t1`` NaN
    while a span is open). ``requests``: ``seq``, ``kind``, ``t_submit``,
    ``t_admit``, ``t_done``, one row per request submitted while on, by
    ``seq``; a stamp not yet taken is NaN. Times are `time.perf_counter`
    seconds."""
    # np.array copies: a view would pin the columns' buffers, and the
    # next append would fail
    seq = np.array(_sub_seq, np.int64)
    order = np.argsort(seq, kind="stable")
    seq = seq[order]
    kinds = np.asarray(list(_kinds), dtype=str)
    return {
        "spans": {
            "name": np.asarray(_names, dtype=str),
            "t0": np.array(_t0, np.float64),
            "t1": np.array(_t1, np.float64),
            "parent": np.array(_parent, np.int64),
        },
        "requests": {
            "seq": seq,
            "kind": kinds[np.array(_sub_kind, np.int64)[order]],
            "t_submit": np.array(_sub_t, np.float64)[order],
            "t_admit": _join(seq, _adm_seq, _adm_t),
            "t_done": _join(seq, _done_seq, _done_t),
        },
    }


def self_seconds(spans: dict) -> np.ndarray:
    """Each span's duration less the durations of the spans it encloses
    directly (its own work, not its children's)."""
    dur = spans["t1"] - spans["t0"]
    parent = spans["parent"]
    child = parent >= 0
    return dur - np.bincount(parent[child], weights=dur[child], minlength=len(dur))
