"""CPU rehearsal of the chip benchmark: one run of a cell at a tiny fleet,
on the CPU, with the look for a chip bypassed, and where asked with one of
the cell's faults (`faults.for_cell`) planted under the timed path.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --workload fuel-100k.stats-skew --seed 5 [--trace 1] [--fault half_fold]

Prints the run's result line, as ``run.py`` does. Its numbers are CPU
numbers and stand for no device. The tests beside it use the same
`patch` and `plant`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402

#: vehicles in the rehearsal's fleet, and seconds in its window
N, SECONDS = 1024, 1.0
#: the rehearsal's rate is the mix's own, held between these (req/s): at
#: least enough for a one-second window to send every kind's answers to
#: the check, at most what the CPU serves at N vehicles
RATE_MIN, RATE_MAX = 15.0, 60.0


def cpu_rate(mix: dict) -> float:
    return min(max(float(mix["rate_per_s"]), RATE_MIN), RATE_MAX)


def patch(setattr_) -> None:
    """Shrink every cell to N vehicles at the rehearsal's rate, and bypass
    the look for a chip, through ``setattr_`` (pytest's
    ``monkeypatch.setattr``, or the built-in ``setattr``)."""
    import jax

    load = cells.load_cell

    def small(name):
        c = load(name)
        c.config["sim"]["n_clients"] = N
        c.mix["rate_per_s"] = cpu_rate(c.mix)
        return c

    setattr_(cells, "load_cell", small)
    setattr_(run, "require_chip", lambda chips: jax.devices())
    setattr_(run, "peaks_of", lambda kind: {"hbm_bytes_per_s": 819e9})


def plant(setattr_, fault) -> None:
    """Build every fleet with ``fault(sim, gw)`` applied."""
    build = cells.build

    def broken(config, seed, devices):
        sim, gw = build(config, seed, devices)
        fault(sim, gw)
        return sim, gw

    setattr_(cells, "build", broken)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", help="name of one of the cell's faults")
    args = ap.parse_args(argv)
    patch(setattr)
    if args.fault:
        known = {f.__name__: f for f in faults.for_cell(cells.load_cell(args.workload))}
        if args.fault not in known:
            raise SystemExit(f"rehearse: {args.workload} has no fault {args.fault!r}; "
                             f"known: {sorted(known)}")
        plant(setattr, known[args.fault])
    out = run.run(args.workload, args.seed, SECONDS, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
