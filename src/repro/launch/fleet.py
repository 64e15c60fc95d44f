"""Run the fleet simulator from the command line.

Federated learning (the default workload):

    PYTHONPATH=src python -m repro.launch.fleet --clients 1024 --rounds 5 \
        --drop 0.05 --duplicate 0.02 --delay 2 --stragglers 0.1

Streaming analytics (the paper's data-analytics case study — on-vehicle
Welford/histogram sketches over a drive-cycle signal, merged server-side
in one batched jit reduction per window):

    PYTHONPATH=src python -m repro.launch.fleet --workload analytics \
        --clients 256 --scenario mixed --signal Vehicle.FuelRate --rounds 6

Prints the per-round metrics table and the workload summary. Everything is
a deterministic function of --seed: re-running with identical flags gives
an identical final aggregate (printed as a checksum so drift is visible).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import numpy as np

from repro.fleet.analytics import AnalyticsConfig, AnalyticsDriver
from repro.fleet.checkpoint import FleetCheckpoint
from repro.fleet.federated import FedConfig
from repro.fleet.metrics import RoundMetrics
from repro.fleet.scenarios import PLANES, SCENARIOS
from repro.fleet.simulator import Backends, FleetSimulator, SimConfig
from repro.launch.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", choices=("federated", "analytics"),
                    default="federated")
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=5,
                    help="FedAvg rounds / analytics windows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", choices=SCENARIOS, default=None,
                    help="drive-cycle scenario for the signal plane "
                         "(default: road-grade for federated, mixed for "
                         "analytics)")
    ap.add_argument("--plane", choices=PLANES, default="host",
                    help="signal-plane implementation: one columnar host "
                         "array, or rows sharded across devices on a "
                         "`clients` mesh (run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 to "
                         "simulate devices on CPU); bit-for-bit identical")
    ap.add_argument("--dim", type=int, default=32, help="model dimension")
    ap.add_argument("--drop", type=float, default=0.0, help="QoS-0 drop prob")
    ap.add_argument("--duplicate", type=float, default=0.0, help="QoS-1 dup prob")
    ap.add_argument("--delay", type=int, default=0, help="max delivery delay (ticks)")
    ap.add_argument("--leave", type=float, default=0.0, help="per-tick ignition-off prob")
    ap.add_argument("--return", dest="p_return", type=float, default=0.0,
                    help="per-tick ignition-on prob")
    ap.add_argument("--stragglers", type=float, default=0.0,
                    help="fraction of slow clients")
    ap.add_argument("--service", choices=("scheduler", "calendar", "dense"),
                    default="scheduler",
                    help="fleet service: event-driven scheduler "
                         "(O(runnable)/tick), the calendar-queue variant "
                         "(same heap service with periodic refills moved "
                         "into numpy lanes — the 100k+ fast path), or the "
                         "dense poll-loop oracle (O(N)/tick, identical "
                         "interleaving)")
    ap.add_argument("--engine", choices=("event", "dense"), default="event",
                    help="tick orchestration: one unified time-ordered "
                         "event heap (churn toggles, service refills, "
                         "round deadlines — O(events)/tick) or the "
                         "legacy per-subsystem dense tick (the "
                         "bit-for-bit parity oracle)")
    ap.add_argument("--churn", choices=("event", "dense"), default="event",
                    help="churn schedule: seeded geometric event heap or "
                         "the O(N)-scan oracle (identical toggles)")
    ap.add_argument("--deadline", type=float, default=0.9,
                    help="fraction of clients awaited per round")
    ap.add_argument("--deadline-pumps", type=int, default=64,
                    help="hard per-round tick budget")
    # analytics knobs
    ap.add_argument("--signal", default="Vehicle.FuelRate",
                    help="signal the analytics workload sketches")
    ap.add_argument("--window", type=int, default=64,
                    help="on-vehicle samples per sketch")
    ap.add_argument("--bins", type=int, default=16,
                    help="fixed-bin histogram resolution")
    ap.add_argument("--quantile-k", type=int, default=32,
                    help="ranked values per vehicle quantile summary")
    ap.add_argument("--sketch", action="store_true",
                    help="fold windows on device via one fused fleet-wide "
                         "sketch kernel (autospada.get_signal_sketch) "
                         "instead of per-vehicle sandbox loops — same "
                         "result, bit for bit")
    ap.add_argument("--warmup-ticks", type=int, default=16,
                    help="world ticks before the first analytics window")
    # durable fleet state (repro.fleet.checkpoint)
    ap.add_argument("--checkpoint-to", metavar="DIR", default=None,
                    help="directory for durable checkpoints; one "
                         "subdirectory round-NNNN per saved round")
    ap.add_argument("--checkpoint-every", type=int, metavar="N", default=None,
                    help="save a checkpoint after every N completed "
                         "rounds/windows (requires --checkpoint-to)")
    ap.add_argument("--restore-from", metavar="DIR", default=None,
                    help="resume from a checkpoint directory: finishes any "
                         "in-flight round, then runs --rounds more "
                         "(workload/config come from the checkpoint)")
    ap.add_argument("--memory-report", action="store_true",
                    help="print the per-category bytes/client breakdown "
                         "(signal plane, columnar arena, documents, "
                         "queues, client objects) before the workload "
                         "runs")
    return ap


def _checkpoint_hook(ap: argparse.ArgumentParser, args, sim):
    """Returns the on_round/on_window hook saving durable checkpoints
    every N completed rounds, or None when checkpointing is off."""
    if args.checkpoint_every is not None and args.checkpoint_to is None:
        ap.error("--checkpoint-every requires --checkpoint-to")
    if args.checkpoint_to is None:
        return None
    every = args.checkpoint_every if args.checkpoint_every is not None else 1
    if every < 1:
        ap.error("--checkpoint-every must be >= 1")
    root = Path(args.checkpoint_to)
    last: list[Path | None] = [None]

    def hook(rnd: int, driver) -> None:
        if (rnd + 1) % every == 0:
            path = FleetCheckpoint.save(
                sim, root / f"round-{rnd:04d}", driver=driver,
                previous=last[0],  # hardlink unchanged arrays
            )
            last[0] = path
            print(f"checkpoint saved: {path}")

    return hook


def _resume(ap: argparse.ArgumentParser, args) -> None:
    """--restore-from: rebuild the world, finish any in-flight round, run
    --rounds more of whatever workload the checkpoint carries."""
    sim, driver, rif = FleetCheckpoint.restore(args.restore_from)
    if driver is None:
        ap.error(f"checkpoint {args.restore_from} has no workload driver; "
                 "nothing to resume")
    hook = _checkpoint_hook(ap, args, sim)
    if args.memory_report:
        print(FleetSimulator.format_memory_report(sim.memory_report()))
    analytics = isinstance(driver, AnalyticsDriver)
    if rif is not None:
        # finish the round that was mid-flight when the checkpoint was
        # taken, recording its metrics row like the campaign loop does
        online = len(sim.pool.online())
        t0, tick0 = time.perf_counter(), sim.t
        pub0, del0, drop0 = (
            sim.broker.published, sim.broker.delivered, sim.broker.dropped
        )
        if analytics:
            rec = driver.finish_window(rif)
            rnd, participants, canceled = (
                rif.window_id, rec.participants, rec.canceled
            )
            extra = {}
        else:
            rec = driver.finish_round(rif)
            rnd, participants, canceled = (
                rif.rnd, rec["participants"], rec["canceled"]
            )
            extra = {
                "mean_client_loss": rec["mean_client_loss"],
                "dist_to_optimum": rec["dist_to_optimum"],
            }
        sim.metrics.record(
            RoundMetrics(
                round=rnd,
                online_at_start=online,
                participants=participants,
                canceled=canceled,
                ticks=sim.t - tick0,
                published=sim.broker.published - pub0,
                delivered=sim.broker.delivered - del0,
                dropped=sim.broker.dropped - drop0,
                wall_s=time.perf_counter() - t0,
                **extra,
            )
        )
        if hook is not None:
            hook(rnd, driver)
    if analytics:
        driver = sim.run_analytics(
            driver.cfg, windows=args.rounds, driver=driver, on_window=hook
        )
        print(sim.metrics.format_table())
        print(driver.format_table())
    else:
        driver = sim.run_federated(
            driver.cfg, rounds=args.rounds, driver=driver, on_round=hook
        )
        print(sim.metrics.format_table())
        print(f"aggregate checksum: {float(np.sum(driver.w)):.6f}")


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    enable_compile_cache()
    if args.restore_from is not None:
        _resume(ap, args)
        return
    scenario = args.scenario or (
        "mixed" if args.workload == "analytics" else "road-grade"
    )
    sim = FleetSimulator(
        SimConfig(
            n_clients=args.clients,
            seed=args.seed,
            scenario=scenario,
            p_drop=args.drop,
            p_duplicate=args.duplicate,
            max_delay=args.delay,
            p_leave=args.leave,
            p_return=args.p_return,
            straggler_fraction=args.stragglers,
            # CLI strings coerce to the typed enums in Backends
            backends=Backends(
                plane=args.plane,
                service=args.service,
                churn=args.churn,
                engine=args.engine,
            ),
        )
    )
    hook = _checkpoint_hook(ap, args, sim)
    if args.memory_report:
        print(FleetSimulator.format_memory_report(sim.memory_report()))
    if args.workload == "analytics":
        driver = sim.run_analytics(
            AnalyticsConfig(
                signal=args.signal,
                window=args.window,
                bins=args.bins,
                quantile_k=args.quantile_k,
                sketch=args.sketch,
                deadline_fraction=args.deadline,
                deadline_pumps=args.deadline_pumps,
            ),
            windows=args.rounds,
            warmup_ticks=args.warmup_ticks,
            on_window=hook,
        )
        print(sim.metrics.format_table())
        print(driver.format_table())
        if driver.history:
            last = driver.history[-1]
            print(
                f"fleet {args.signal}: mean={last.mean:.4f} std={last.std:.4f} "
                f"p50={last.quantile(0.5):.4f} p90={last.quantile(0.9):.4f} "
                f"over {last.count} on-vehicle samples "
                f"(checksum {last.mean + last.var:.6f})"
            )
        return
    driver = sim.run_federated(
        FedConfig(
            local_steps=3,
            local_lr=0.2,
            deadline_fraction=args.deadline,
            deadline_pumps=args.deadline_pumps,
        ),
        dim=args.dim,
        rounds=args.rounds,
        n_samples=16,
        on_round=hook,
    )
    print(sim.metrics.format_table())
    print(f"aggregate checksum: {float(np.sum(driver.w)):.6f}")


if __name__ == "__main__":
    main()
