"""One sweep of offered rates on one fleet, to find a cell's knee: the
highest rate the system sustains without a growing backlog.

    python3 benchmarks/chip/sweep.py --workload fuel-100k.stats-skew \\
        --rates 5,10,20,40 --seconds 20 --seed 5

Builds, fills and warms the cell once, then runs one open-loop window per
rate, in the order given, and prints one JSON line per rate: requests
sent and answered, latency percentiles over the whole window and over its
first and last thirds (by send time), ticks and signal rows per second,
and the garbage collections inside the window. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import run  # noqa: E402
import traffic_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep-all", action="store_true",
                    help="hold every answer until the end, as a check of all of them would")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    devices = run.require_chip(cell.chips)[: cell.chips]
    run.enable_compile_cache()
    fleet = run.Fleet(cell, devices)
    for _ in range(cells.fill_ticks(cell.mix)):
        fleet.tick()
    run.warm(fleet, cell.mix)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = {**cell.mix, "rate_per_s": rate}
        sched = traffic_gen.generate(mix, args.seed, args.seconds, fleet.n)
        pauses = run.GcPauses()
        pauses.on = True
        keep = frozenset(range(len(sched))) if args.keep_all else frozenset()
        win = run.measure(fleet, sched, args.seconds, None, keep)
        pauses.on = False
        lat = 1e3 * np.asarray(win["latency_s"])
        by_due = [1e3 * lt for _, lt in sorted(zip(win["due_s"], win["latency_s"]))]
        third = max(1, len(by_due) // 3)
        span = win["t_end"] - win["t0"]
        print(json.dumps({
            "rate": rate, "sent": len(sched), "answered": len(win["due_s"]),
            "unanswered": win["unanswered"],
            "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
            "p50_first_third_ms": float(np.percentile(by_due[:third], 50)),
            "p50_last_third_ms": float(np.percentile(by_due[-third:], 50)),
            "ticks_per_s": win["steps"] / span, "rows_per_s": win["rows"] / span,
            "gc": str(pauses),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
