"""From a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, read with `jax.profiler.ProfileData`.
Device operations are the events of each TPU core's ``XLA Ops`` line (on
a trace without a TPU, the CPU backend's per-op events stand in, so the
reduction can be rehearsed). Host spans are the ``bench.*`` annotations
the harness writes around its own calls and around each layer's entry
point, and the program's own spans where its recorder was on and its
records name them; ``bench.window`` marks the measured window.

Times are nanoseconds on the trace's clock; every interval list is a
sorted ``(k, 2)`` array of disjoint ``[start, end)`` pairs once merged.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
#: innermost first: an idle gap is charged to the first of these the host
#: was inside when it fell
SPAN_ORDER = ("bench.fold", "bench.stats", "bench.window_read", "bench.plane_step",
              "bench.service", "bench.submit", "bench.collect", "bench.tick")


@dataclasses.dataclass
class Ops:
    start: np.ndarray   # (k,) ns
    end: np.ndarray     # (k,) ns
    name: list[str]


@dataclasses.dataclass
class Device:
    ops: Ops            # every operation, named ``<module>:<op>``
    modules: Ops        # every run of an XLA program, named by the program


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    devices: list[Device]
    spans: dict[str, np.ndarray]
    #: the program's span names, innermost first (`program_order`)
    program_order: tuple[str, ...] = ()

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _module(name: str) -> str:
    """``jit_tick(4296951652591142569)`` -> ``jit_tick``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.72 = u32[100000]{0} fusion(), ...`` -> ``%fusion.72``."""
    return name.split(" = ", 1)[0]


def program_order(program: dict) -> tuple[str, ...]:
    """The span names of the program's records (`repro.core.obs.records`),
    innermost first: by the deepest any of a name's spans sits in the
    recorder's ``parent`` column, then by name."""
    spans = program.get("spans")
    if spans is None or not len(spans["name"]):
        return ()
    parent = np.asarray(spans["parent"])
    depth = np.zeros(len(parent), np.int64)
    for i, p in enumerate(parent):  # a parent opens before its children
        depth[i] = depth[p] + 1 if p >= 0 else 0
    deepest: dict[str, int] = {}
    for name, d in zip(np.asarray(spans["name"]).tolist(), depth.tolist()):
        deepest[name] = max(d, deepest.get(name, d))
    return tuple(sorted(deepest, key=lambda n: (-deepest[n], n)))


def load(path: Path, program: dict | None = None) -> Reduced:
    """The trace at ``path``, keeping the harness's spans and, given the
    program's records, the host annotations that they name."""
    from jax.profiler import ProfileData

    order = program_order(program or {})
    named = set(order)

    data = ProfileData.from_file(str(path))
    spans: dict[str, list] = {}
    devices, cpu_ops = [], []
    for plane in data.planes:
        is_tpu = bool(_TPU_PLANE.match(plane.name))
        ops, mods = [], []
        for line in plane.lines:
            for ev in line.events:
                name, t, d = ev.name, ev.start_ns, ev.duration_ns
                if name.startswith("bench.") or name in named:
                    spans.setdefault(name, []).append((t, t + d))
                elif is_tpu and line.name == "XLA Ops":
                    ops.append((t, t + d, _op(name)))
                elif is_tpu and line.name == "XLA Modules":
                    mods.append((t, t + d, _module(name)))
                elif not is_tpu and plane.name == "/host:CPU" and _stat(ev, "hlo_op"):
                    cpu_ops.append((t, t + d, _module(str(_stat(ev, "hlo_module"))), name))
        if is_tpu:
            devices.append(_device(ops, mods))
    if not devices:  # a CPU trace: each op is a run of its own module
        devices = [Device(_ops([(s, e, f"{m}:{o}") for s, e, m, o in cpu_ops]),
                          _ops([(s, e, m) for s, e, m, _ in cpu_ops]))]
    if "bench.window" not in spans:
        raise ValueError("trace has no bench.window span")
    w = spans["bench.window"][0]
    return Reduced((w[0], w[1]), devices,
                   {k: np.asarray(sorted(v), np.float64) for k, v in spans.items()}, order)


def _ops(rows: list) -> Ops:
    rows = sorted(rows)
    return Ops(np.asarray([r[0] for r in rows], np.float64).reshape(-1),
               np.asarray([r[1] for r in rows], np.float64).reshape(-1),
               [r[2] for r in rows])


def _device(ops: list, mods: list) -> Device:
    """Name each operation after the program run it started in."""
    m = _ops(mods)
    named = []
    for s, e, op in ops:
        i = int(np.searchsorted(m.start, s, side="right")) - 1
        mod = m.name[i] if i >= 0 and s < m.end[i] else "?"
        named.append((s, e, f"{mod}:{op}"))
    return Device(_ops(named), m)


# --------------------------------------------------------------------- #
# interval arithmetic                                                   #
# --------------------------------------------------------------------- #
def union(iv: np.ndarray) -> np.ndarray:
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def length(iv: np.ndarray) -> float:
    iv = np.asarray(iv).reshape(-1, 2)
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if s < e:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j, 1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < e:
            if b[k, 0] > cur:
                out.append((cur, b[k, 0]))
            cur = max(cur, b[k, 1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out, np.float64).reshape(-1, 2)


# --------------------------------------------------------------------- #
# reductions                                                            #
# --------------------------------------------------------------------- #
def _window(red: Reduced) -> np.ndarray:
    return np.asarray([red.window], np.float64)


def busy(red: Reduced, d: Device) -> np.ndarray:
    """The intervals in which an operation ran on one device, in the window."""
    return intersect(np.stack([d.ops.start, d.ops.end], axis=1), _window(red))


def busy_s(red: Reduced) -> float:
    """Busy seconds in the window, averaged over the devices."""
    return sum(length(busy(red, d)) for d in red.devices) * 1e-9 / len(red.devices)


def spans(red: Reduced, name: str) -> np.ndarray:
    return red.spans.get(name, np.zeros((0, 2)))


def device_s_in(red: Reduced, inside: str, outside: str | None = None) -> float:
    """Device seconds of the operations that started inside the host spans
    ``inside`` and not inside ``outside``, averaged over the devices."""
    keep = union(spans(red, inside))
    if outside is not None:
        keep = subtract(keep, spans(red, outside))
    total = 0.0
    for d in red.devices:
        o = d.ops
        if not len(keep) or not len(o.start):
            continue
        i = np.searchsorted(keep[:, 0], o.start, side="right") - 1
        hit = (i >= 0) & (o.start < keep[np.maximum(i, 0), 1])
        total += length(union(np.stack([o.start[hit], o.end[hit]], axis=1)))
    return total * 1e-9 / len(red.devices)


def module_s(red: Reduced, name: str) -> float:
    """Device seconds in the window of the runs of the XLA program
    ``name``, averaged over the devices."""
    total = 0.0
    for d in red.devices:
        m = d.modules
        hit = np.asarray([x == name for x in m.name], bool)
        if hit.any():
            total += length(intersect(np.stack([m.start[hit], m.end[hit]], axis=1),
                                      _window(red)))
    return total * 1e-9 / len(red.devices)


def top_ops(red: Reduced, k: int = 10) -> list[list]:
    """The device operations that took most time in the window, by
    ``<program>:<op>``."""
    acc: dict[str, float] = {}
    w0, w1 = red.window
    for d in red.devices:
        dur = np.minimum(d.ops.end, w1) - np.maximum(d.ops.start, w0)
        for name, s in zip(d.ops.name, dur):
            if s > 0:
                acc[name] = acc.get(name, 0.0) + float(s) * 1e-9 / len(red.devices)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_by_span(red: Reduced, k: int = 10) -> list[list]:
    """Idle device seconds in the window, charged to the innermost span the
    host was in: the program's own where the trace has them, then the
    harness's (``host.other`` outside all of them)."""
    order = [n for n in red.program_order if n in red.spans] + list(SPAN_ORDER)
    acc: dict[str, float] = {}
    for d in red.devices:
        idle = subtract(_window(red), busy(red, d))
        for name in order:
            s = union(spans(red, name))
            hit = intersect(idle, s)
            acc[name] = acc.get(name, 0.0) + length(hit) * 1e-9 / len(red.devices)
            idle = subtract(idle, s)
        acc["host.other"] = acc.get("host.other", 0.0) + length(idle) * 1e-9 / len(red.devices)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k] if s > 0]
