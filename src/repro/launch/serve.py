"""Serving launcher: front a running fleet with the analyst gateway.

The default mode boots a `FleetSimulator`, opens `--sessions` concurrent
analyst sessions against it through `repro.serve.FleetGateway`, replays a
deterministic request mix (fleet gauges, windowed statistics, quantile
queries, federated rounds, analytics windows), and prints every response
plus the latency summary. Everything is a function of --seed and the
request trace: re-running prints byte-identical response bodies.

    PYTHONPATH=src python -m repro.launch.serve --clients 1024 --sessions 4

`--llm` switches to the original LLM serving path (continuous-batching
`ServeEngine` over a transformer checkpoint):

    PYTHONPATH=src python -m repro.launch.serve --llm --arch qwen3-4b
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--llm", action="store_true",
                    help="serve an LLM (ServeEngine) instead of the fleet")
    # -- fleet gateway mode -------------------------------------------- #
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--sessions", type=int, default=4,
                    help="concurrent analyst sessions")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", default="mixed")
    ap.add_argument("--plane", default="host",
                    help="signal plane backend: host | sharded")
    ap.add_argument("--signal", default="Vehicle.FuelRate")
    ap.add_argument("--warmup-ticks", type=int, default=16,
                    help="world ticks before the first request")
    ap.add_argument("--rounds", type=int, default=1,
                    help="federated rounds submitted per session")
    ap.add_argument("--windows", type=int, default=1,
                    help="analytics windows submitted per session")
    ap.add_argument("--admit-per-tick", type=int, default=None,
                    help="cap admissions per tick boundary (backpressure)")
    ap.add_argument("--leave", type=float, default=0.0,
                    help="per-tick ignition-off probability")
    ap.add_argument("--return", dest="p_return", type=float, default=0.0,
                    help="per-tick ignition-on probability")
    ap.add_argument("--stragglers", type=float, default=0.0,
                    help="fraction of slow clients")
    # -- LLM mode ------------------------------------------------------ #
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=4)
    return ap


def build_fleet(args: argparse.Namespace, *, mesh=None):
    """Boot the `FleetSimulator` the gateway fronts and run its warm-up
    ticks. `mesh` places the sharded plane (default: every device)."""
    from repro.fleet.simulator import Backends, FleetSimulator, SimConfig

    sim = FleetSimulator(
        SimConfig(
            n_clients=args.clients,
            seed=args.seed,
            scenario=args.scenario,
            p_leave=args.leave,
            p_return=args.p_return,
            straggler_fraction=args.stragglers,
            backends=Backends(plane=args.plane),
        ),
        mesh=mesh,
    )
    for _ in range(args.warmup_ticks):
        sim.tick()
    return sim


def replay_mix(sim, args: argparse.Namespace):
    """Open `--sessions` analyst sessions on `sim`, replay the request mix
    until every request is served, and return ``(gateway, responses in
    seq order, ticks used)``."""
    from repro.serve.gateway import FleetGateway

    gw = FleetGateway(sim, admit_per_tick=args.admit_per_tick)
    # the deterministic request mix every session replays: a dashboard
    # poll, fleet-level statistics, a percentile query, then the
    # submissions — all in flight concurrently across sessions
    for s in range(args.sessions):
        sess = gw.session(f"analyst-{s}")
        sess.gauges()
        sess.platform()
        sess.fleet_stats(args.signal)
        sess.quantile(args.signal, 0.9)
        for _ in range(args.rounds):
            sess.submit_round()
        for _ in range(args.windows):
            sess.submit_window(args.signal, sketch=True)
    ticks = gw.run_until_idle()
    responses = [r for s in gw._sessions.values() for r in s.inbox]
    responses.sort(key=lambda r: r.seq)
    return gw, responses, ticks


def _fleet_main(args: argparse.Namespace) -> int:
    """Serve the mix and print every response; exit status 1 if any
    request was answered with ``ok=False``."""
    sim = build_fleet(args)
    t0 = time.perf_counter()
    _, responses, ticks = replay_mix(sim, args)
    wall = time.perf_counter() - t0
    for r in responses:
        print(r.encode().decode())
    lat = np.asarray([r.ticks for r in responses], np.float64)
    failed = sum(not r.ok for r in responses)
    print(
        f"-- {len(responses)} responses ({failed} failed) over {ticks} ticks "
        f"({len(responses) / max(wall, 1e-9):.0f} resp/s wall); "
        f"response ticks p50={np.percentile(lat, 50):.0f} "
        f"p99={np.percentile(lat, 99):.0f}"
    )
    return 1 if failed else 0


def _llm_main(args: argparse.Namespace) -> None:
    import jax

    from repro.configs import get_config, get_tiny
    from repro.models import init_params
    from repro.serve.engine import Request, ServeEngine, serve_loop

    cfg = get_config(args.arch) if args.full else get_tiny(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, cache_len=args.cache_len)
    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(
            f"req-{i}",
            rng.integers(0, cfg.vocab_size, (int(rng.integers(8, 48)),)),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    results = serve_loop(engine, reqs, batch_size=args.batch_size)
    dt = time.perf_counter() - t0
    tok = sum(len(v) for v in results.values())
    print(f"{len(reqs)} requests -> {tok} tokens in {dt:.2f}s ({tok/dt:.1f} tok/s)")
    for rid in sorted(results):
        print(rid, results[rid])


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.llm:
        _llm_main(args)
        return 0
    return _fleet_main(args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
