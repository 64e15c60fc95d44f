"""Readings for the limits of ``verdict.py``: for each seed, one short run
of a cell, then the compared numbers twice, once for the program and once
for the control (the reference in the program's place, computed one
precision step down: bfloat16 for the configuration's float32). All seeds
run in one process, one after another.

    python3 benchmarks/chip/control.py --workload fuel-100k.stats-skew \\
        --seeds 11,12,13 --seconds 10

Prints one JSON line per seed, then the largest program reading and the
smallest control reading of each number. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import verdict  # noqa: E402


def readings(workload: str, seeds: list[int], seconds: float) -> list[dict]:
    rows = []
    for seed in seeds:
        _, ev = run.session(workload, seed, seconds, False)
        prog = verdict.compare(ev, seed)
        ctrl = verdict.compare(ev, seed, lower=True)
        rows.append({"seed": seed, "correct": verdict.verdict(prog),
                     "control_correct": verdict.verdict(ctrl),
                     "program": prog, "control": ctrl})
        print(json.dumps(rows[-1], default=str), flush=True)
    return rows


def summary(rows: list[dict]) -> dict:
    out = {}
    for k in verdict.LIMITS:
        p = [r["program"][k] for r in rows if k in r["program"]]
        c = [r["control"][k] for r in rows if k in r["control"]]
        if p:
            out[k] = {"program_max": max(p), "control_min": min(c),
                      "limit": verdict.LIMITS[k], "seeds": len(p)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds)
    s = summary(rows)
    print(json.dumps({"summary": s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
