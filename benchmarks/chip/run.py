"""Chip benchmark of the fleet platform: open-loop analyst reads against a
running fleet, through the analyst gateway.

    python3 benchmarks/chip/run.py --workload fuel-100k.stats-skew \\
        --seed 7 --seconds 30 --trace 0

One run: build the cell's fleet from its configuration file, fill the
signal ring, warm every request shape of the cell's traffic mix (set-up,
``setup_s``), then for ``--seconds`` send the mix on a wall-clock
schedule that does not slow when the gateway does, ticking the fleet
between arrivals. Once the window has closed, every outstanding request
is waited for, the device's peak memory is read, and the answers and the
signal ring are held against the plain reference (``verdict.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
compared numbers with their limits (``checks``). The run exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell asks,
and before any set-up where the mix sends a request kind that none of the
configuration's modules under ``checks/`` checks (`verdict.checks_for`)
and ``verdict.OK_ONLY`` does not name.

A traced run switches the program's own recorder on for its window only
where one of the cell's metric readers asks for it (``PROGRAM = True``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import traffic_gen  # noqa: E402
import verdict  # noqa: E402

#: how long after the window's close an answer is still waited for
DRAIN_S = 60.0
#: where the traced run writes its profile, inside the checkout
TRACE_DIR = cells.ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The TPU devices, or exit: this benchmark never falls back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU found (platform {devs[0].platform!r}); no result")
        raise SystemExit(3)
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devs)}; no result")
        raise SystemExit(3)
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(cells.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCount:
    """Traces, lowerings and backend compilations while ``on``, by kind."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax

        self.on = False
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def reset(self) -> None:
        self.n, self.seconds = dict.fromkeys(self.EVENTS.values(), 0), 0.0

    def _seen(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n[self.EVENTS[event]] += 1
            self.seconds += duration

    def __str__(self) -> str:
        return f"{sum(self.n.values())} ({self.n}, {self.seconds:.3f} s)"


class GcPauses:
    """Python's garbage collections while ``on``: how many of each
    generation, and how long they held the process."""

    def __init__(self):
        self.on, self.pauses, self._t0 = False, [], None
        gc.callbacks.append(self._seen)

    def _seen(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on and self._t0 is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def __str__(self) -> str:
        full = [s for g, s in self.pauses if g == 2]
        return (f"{len(self.pauses)} collections, {sum(s for _, s in self.pauses):.3f} s; "
                f"{len(full)} full, longest {1e3 * max(full, default=0.0):.1f} ms")


class Fleet:
    """The program under test: the simulator and the gateway in front of it."""

    def __init__(self, cell: cells.Cell, devices):
        self.sim, self.gw = cells.build(cell.config, cells.FLEET_SEED, devices)
        self.n = self.sim.plane.n_clients

    def online(self) -> int:
        """Vehicles online at the plane's latest step."""
        return int(np.count_nonzero(cells.online(self.sim)))

    def tick(self) -> None:
        self.gw.tick()


def warm(fleet: Fleet, mix: dict) -> None:
    """Serve one request of every shape the mix sends, so that every
    program the window runs is compiled (or loaded from the cache) now."""
    tickets = [fleet.gw.submit("warm-up", kind, params)
               for kind, params in traffic_gen.distinct_shapes(mix, fleet.n)]
    for _ in range(2):
        fleet.tick()
    bad = [t.response for t in tickets if not (t.done and t.response.ok)]
    if bad:
        raise SystemExit(f"bench: the mix sends requests the gateway refuses: {bad[:3]}")
    cells.block(fleet.sim)


def measure(fleet: Fleet, schedule, seconds: float, probes, keep=frozenset()) -> dict:
    """The measured window: submit what is due, tick, collect; then wait
    for what is still out. Latency runs from each request's scheduled send
    time to the moment the harness first sees it answered. Of the answers,
    the harness keeps those of the requests in ``keep`` (indices into the
    schedule) for the check, and drains every session's inbox as an
    analyst would, so that the run holds no more answers than that."""
    gw, span = fleet.gw, cells.span
    sessions = sorted({r.session for r in schedule})
    pending: list[tuple] = []  # (ticket, due time, schedule index)
    due_s: list[float] = []    # of each answered request, in answer order
    lat: list[float] = []
    kept: list[tuple] = []     # (request, response) of the requests in ``keep``
    late: list[float] = []
    failed = 0

    def submit(j, t0):
        req, now = schedule[j], time.perf_counter()
        late.append(now - (t0 + req.due_s))
        pending.append((gw.submit(req.session, req.kind, req.params), t0 + req.due_s, j))

    def collect():
        nonlocal failed
        now = time.perf_counter()
        still = []
        for ticket, due, j in pending:
            if ticket.done:
                due_s.append(due)
                lat.append(now - due)
                failed += not ticket.response.ok
                if j in keep:
                    kept.append((schedule[j], ticket.response))
            else:
                still.append((ticket, due, j))
        pending[:] = still
        cells.drain_inboxes(gw, sessions)

    i, steps, rows = 0, 0, 0
    with span(probes, "bench.window"):
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            with span(probes, "bench.submit"):
                now = time.perf_counter()
                while i < len(schedule) and t0 + schedule[i].due_s <= now:
                    submit(i, t0)
                    i += 1
            with span(probes, "bench.tick"):
                gw.tick()
            rows += fleet.online()
            steps += 1
            with span(probes, "bench.collect"):
                collect()
        t_end = time.perf_counter()
    if probes is not None:
        probes.stop()
    for j in range(i, len(schedule)):  # due inside the window, sent at its close
        submit(j, t0)
    waited_until = time.perf_counter() + DRAIN_S
    while pending and time.perf_counter() < waited_until:
        fleet.tick()
        collect()
    now = time.perf_counter()
    return {
        "t0": t0, "t_end": t_end, "steps": steps, "rows": rows, "late": late,
        "due_s": due_s, "latency_s": lat + [now - due for _, due, _ in pending],
        "kept": kept, "failed": failed, "unanswered": len(pending),
    }


def end_to_end(setup_s: float, win: dict) -> dict:
    lat_ms = 1e3 * np.asarray(win["latency_s"], np.float64)
    p50, p95 = (np.percentile(lat_ms, [50, 95]) if len(lat_ms) else (math.nan, math.nan))
    return {
        "setup_s": setup_s,
        "read_p50_ms": float(p50),
        "read_p95_ms": float(p95),
        "signal_rows_per_s": win["rows"] / (win["t_end"] - win["t0"]),
    }


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def peaks_of(kind: str) -> dict:
    """The chip's published peaks; a chip missing from the table is an error."""
    table = json.loads((cells.HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _num(v):
    return v if isinstance(v, int) or math.isfinite(v) else str(v)


def session(workload: str, seed: int, seconds: float, trace: bool):
    """One run up to the check: the result without ``correct`` and
    ``checks``, and the evidence the check reads."""
    cell = cells.load_cell(workload)
    unchecked = verdict.unchecked((k["kind"] for k in cell.mix["kinds"]), cell.config)
    if unchecked:
        log(f"the mix sends {unchecked}, which no module under checks/ checks; no result")
        raise SystemExit(2)
    devices = require_chip(cell.chips)[: cell.chips]
    import jax

    log(f"compile cache: {enable_compile_cache()}")
    compiles, pauses = CompileCount(), GcPauses()
    compiles.on = True
    t = [time.perf_counter()]
    fleet = Fleet(cell, devices)
    t.append(time.perf_counter())
    for _ in range(cells.fill_ticks(cell.mix)):
        fleet.tick()
    cells.block(fleet.sim)
    t.append(time.perf_counter())
    warm(fleet, cell.mix)
    t.append(time.perf_counter())
    schedule = traffic_gen.generate(cell.mix, seed, seconds, fleet.n)
    setup_s = time.perf_counter() - T_PROCESS
    compiles.on = False
    log(f"set-up {setup_s:.3f} s: {fleet.n} vehicles, plane tick {fleet.sim.plane.t}, "
        f"{len(schedule)} requests scheduled over {seconds} s")
    log(f"set-up phases: imports {t[0] - T_PROCESS:.3f} s, build {t[1] - t[0]:.3f} s, "
        f"fill {t[2] - t[1]:.3f} s, warm {t[3] - t[2]:.3f} s; compilations {compiles}")
    compiles.reset()

    pre = cells.ring_copy(fleet.sim)
    probes = None
    if trace:
        import readings

        wants = readings.wants_program(m["name"] for m in cell.per_layer)
        probes = cells.Probes(cells.recorder() if wants else None)
        probes.install(fleet.sim, fleet.gw)
        shutil.rmtree(TRACE_DIR / workload, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR / workload), profiler_options=opts)
    before = cells.counters(fleet.sim, fleet.gw)
    compiles.on = pauses.on = True
    win = measure(fleet, schedule, seconds, probes, verdict.check_sample(seed, len(schedule)))
    compiles.on = pauses.on = False
    after = cells.counters(fleet.sim, fleet.gw)
    if trace:
        jax.profiler.stop_trace()
    log(f"compilations inside the window: {compiles}")
    log(f"garbage collection inside the window: {pauses}")
    late_ms = 1e3 * np.asarray(win["late"])
    lat_ms = 1e3 * np.asarray(win["latency_s"])
    log(f"window {win['t_end'] - win['t0']:.3f} s, {win['steps']} ticks; requests sent "
        f"late by mean {late_ms.mean():.1f} ms, max {late_ms.max():.1f} ms; "
        f"{len(lat_ms)} latencies, p50 {np.percentile(lat_ms, 50):.1f} ms, "
        f"p95 {np.percentile(lat_ms, 95):.1f} ms, max {lat_ms.max():.1f} ms")

    peak = memory_peak(devices)
    final = cells.ring_copy(fleet.sim)
    ev = verdict.Evidence(
        config=cell.config, seed=cells.FLEET_SEED, n_clients=fleet.n,
        rows=verdict.sample_rows(seed, fleet.n), copies=[pre, final],
        answers=[(r.kind, r.params, resp) for r, resp in win["kept"]],
        unanswered=win["unanswered"], failed=win["failed"],
    )
    counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    del fleet
    gc.collect()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    out = {"correct": None, "attempted": len(schedule), "failed": win["failed"]}
    if trace:
        import trace_reduce as tr

        program = probes.program()
        red = tr.load(tr.find_xplane(TRACE_DIR / workload), program)
        ctx = readings.Context(
            trace=red, spans=probes.spans, folds=probes.folds,
            stats_misses=probes.stats_misses, counters=counters,
            peaks=peaks_of(dev.device_kind), program=program)
        metrics = {}
        for m in cell.per_layer:
            v = readings.read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(red), window_s=red.window_s)
        out["breakdown"] = {"device_ops": tr.top_ops(red), "idle_gaps": tr.idle_by_span(red)}
    else:
        e2e = end_to_end(setup_s, win)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = device
    return out, ev


def run(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    out, ev = session(workload, seed, seconds, trace)
    t_check = time.perf_counter()
    numbers = verdict.compare(ev, seed)
    log(f"reference check took {time.perf_counter() - t_check:.1f} s; checked "
        + ", ".join(f"{k[1:]}={v}" for k, v in numbers.items() if k.startswith("_")))
    out["correct"] = verdict.verdict(numbers)
    checks = {k: {"value": _num(v), "limit": verdict.LIMITS[k]}
              for k, v in numbers.items() if k in verdict.LIMITS}
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} = {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
