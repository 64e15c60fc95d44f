"""Cells, configurations and the adapter onto the program under test.

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
its configuration file (``configs/<config>.json``) and its traffic mix
(``traffic/<traffic>.json``). The program is reached only here: the
simulator and gateway it builds, the state the checks read back (the
signal ring, the online mask, the counters), and its own recorder
(`recorder`), which traced runs switch on only where a metric reader asks.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str) -> Cell:
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def here(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if here(m)],
        per_layer=[m for m in bench["per_layer"] if here(m)],
    )


#: The fleet's own seed: its signals and churn, the same in every run, as
#: a benchmark's data set is. The program builds its signal programs with
#: the seed as a compile-time constant, so a fleet drawn from ``--seed``
#: would compile them afresh in the set-up of every new seed. ``--seed``
#: draws the traffic, its send times and the vehicles the check samples.
FLEET_SEED = 17621


def fill_ticks(mix: dict) -> int:
    """Ticks run before the window: enough to fill the longest window the
    mix reads, so that every read sees a full history."""
    from traffic_gen import distinct_shapes

    widths = [max(int(p.get("window", 0)), int(p.get("k", 0)))
              for _, p in distinct_shapes(mix, 1)]
    return max(widths, default=0)


# --------------------------------------------------------------------- #
# the program                                                           #
# --------------------------------------------------------------------- #
def _program_on_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def build(config: dict, seed: int, devices):
    """The simulator and the gateway in front of it, as the configuration
    states them, on a ``clients`` mesh of the configuration's chips."""
    _program_on_path()
    from repro.fleet.simulator import Backends, FleetSimulator, SimConfig
    from repro.serve.gateway import FleetGateway
    from repro.sharding.fleet import client_mesh

    sim = FleetSimulator(
        SimConfig(seed=seed, backends=Backends(**config["backends"]), **config["sim"]),
        mesh=client_mesh(list(devices)[: int(config["mesh_chips"])]),
    )
    return sim, FleetGateway(sim)


def online(sim) -> np.ndarray:
    """(n,) bool: which vehicles are online now."""
    return sim.columns.online[: sim.plane.n_clients]


def ring_copy(sim) -> tuple[int, np.ndarray]:
    """The plane's tick and a host copy of its ring, read off the device
    without touching the plane's own host mirror or its counters."""
    plane = sim.plane
    ring = getattr(plane, "_dhist", None)
    ring = plane._hist if ring is None else ring
    return plane.t, np.asarray(ring)[:, : plane.n_clients]


#: the program's own counters, by owner, read where the instance has them
_GATEWAY_COUNTERS = ("admitted", "stats_hits", "stats_misses")
_PLANE_COUNTERS = ("sketch_hits", "sketch_misses", "d2h_bytes")
_SKETCH_COUNTERS = ("fold_traces",)


def counters(sim, gw) -> dict:
    from repro.kernels import sketch

    out = {
        "ticks": sim.t,
        "ring_syncs": getattr(sim.plane, "ring_syncs", 0),
        **{f"served.{k}": v for k, v in gw.served.items()},
    }
    for owner, names in ((gw, _GATEWAY_COUNTERS), (sim.plane, _PLANE_COUNTERS),
                         (sketch, _SKETCH_COUNTERS)):
        out.update({k: getattr(owner, k) for k in names if hasattr(owner, k)})
    return out


def recorder():
    """The program's own recorder of spans and request stamps
    (`repro.core.obs`), off until a traced run switches it on."""
    _program_on_path()
    from repro.core import obs

    return obs


def drain_inboxes(gw, sessions) -> None:
    """Empty the named sessions' inboxes, as their analysts would."""
    for name in sessions:
        gw.session(name).inbox.clear()


def block(sim) -> None:
    sim.plane.block_until_ready()


# --------------------------------------------------------------------- #
# spans around the program's layers (traced runs only)                   #
# --------------------------------------------------------------------- #
class Probes:
    """Host spans around each layer's entry point, written into the
    profiler's trace and kept in memory. A wrapper goes on an instance
    only where the attribute exists, so a refactor that renames a layer
    leaves its metric empty instead of failing the run.

    Given the program's ``recorder``, the probes switch it on, fresh, for
    as long as they record, and read it back with `program`."""

    def __init__(self, recorder=None):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.spans: dict[str, list[tuple[float, float]]] = collections.defaultdict(list)
        self.folds: list[dict] = []
        self.stats_misses: list[float] = []
        self._last_stats = None
        self.recorder = recorder
        if recorder is not None:
            recorder.reset()
            recorder.enable()
        self.on = True

    def stop(self) -> None:
        """Record nothing more: the window has closed."""
        self.on = False
        if self.recorder is not None:
            self.recorder.disable()

    def program(self) -> dict:
        """The program's own records of the window, or ``{}`` where the
        recorder stayed off."""
        return {} if self.recorder is None else self.recorder.records()

    def wrap(self, obj, attr: str, name: str, after=None) -> None:
        f = getattr(obj, attr, None)
        if f is None:
            return

        def wrapped(*a, **k):
            t0 = time.perf_counter()
            with self._annotate(name):
                r = f(*a, **k)
            t1 = time.perf_counter()
            if self.on:
                self.spans[name].append((t0, t1))
                if after is not None:
                    after(a, r, t1 - t0)
            return r

        setattr(obj, attr, wrapped)

    def install(self, sim, gw) -> None:
        plane = sim.plane
        self.wrap(sim.service, "tick", "bench.service")
        self.wrap(plane, "step", "bench.plane_step")
        self.wrap(plane, "window", "bench.window_read")

        def fold_done(a, r, dt):
            spec = a[1]
            self.folds.append({
                "window": int(spec.window), "bins": int(spec.bins),
                "quantile_k": int(spec.quantile_k),
                "online": int(np.count_nonzero(online(sim))), "host_s": dt,
            })

        def stats_done(a, r, dt):
            if r is not self._last_stats:
                self.stats_misses.append(dt)
            self._last_stats = r

        self.wrap(plane, "compute_sketches", "bench.fold", fold_done)
        self.wrap(gw, "_fleet_stats", "bench.stats", stats_done)


class _Span:
    def __init__(self, probes: Probes | None, name: str):
        self.p, self.name = probes, name

    def __enter__(self):
        if self.p is not None:
            self.t0 = time.perf_counter()
            self.a = self.p._annotate(self.name)
            self.a.__enter__()

    def __exit__(self, *exc):
        if self.p is not None:
            self.a.__exit__(*exc)
            if self.p.on:
                self.p.spans[self.name].append((self.t0, time.perf_counter()))


def span(probes: Probes | None, name: str) -> _Span:
    """A harness span, recorded only when probes are installed."""
    return _Span(probes, name)
