"""What decides ``correct``: the answers the measured window produced, and
the ring the plane wrote, each held against the plain reference.

The reference is the configuration's own: the module its file names under
``"reference"``, found beside this file, ``reference.py`` where it names
none. Every window a request read is rebuilt by the reference from the
seed and the configuration's churn (`reference.churn`,
`reference.observed`), so every answer of the run can be checked, at any
tick, and none trusts the program's own record of which vehicles were
online. The program's ring is compared with the same reference on sampled
vehicles, from host copies taken before and after the window.

Each compared number has a limit, set in PERF.md from two readings: the
largest that sound runs of the program gave over a dozen seeds or more,
and the smallest that the control gave. The control is the reference in
the program's place, computed one precision step below the
configuration's float32: values rounded to bfloat16, and the fold and
merge carried out in bfloat16. A count of exact mismatches has the
limit 0.

Here, in every cell:

* ``ring_gap``: sampled vehicles' ring entries against the reference
  signals, largest gap (relative above 1, absolute below);
* ``ring_nan_miss``: entries whose NaN (offline) state differs from the
  reference's online state at that tick;
* ``unanswered``, ``failed``: requests that never came back, or came back
  ``ok=False``, of all the run sent.

The answers are checked by request kind, each kind by one module under
``checks/`` (its numbers and limits are there): the `COMMON_CHECKS` for
every configuration, and those a configuration names under ``"checks"``
for its own answers only, so a deployment's check never reaches another
configuration's verdict. A kind that none of a configuration's modules
checks may be sent only where `OK_ONLY` names it.

The answers checked are those of every request of the run, or of
``CHECK_SAMPLE`` of them drawn from the seed where the run sends more.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import importlib
import pkgutil

import numpy as np

import checks

#: the reference of a configuration that names none
DEFAULT_REFERENCE = "reference"
#: the check modules under ``checks/`` that every configuration's answers go to
COMMON_CHECKS = ("gauges", "signal", "stats", "window")
#: kinds held to ``ok`` only where a configuration brings no check for
#: them: ``platform`` reads broker and store counts, which have no
#: independent reference
OK_ONLY = ("platform",)
#: vehicles whose whole ring is held against the reference signals
RING_ROWS = 256
#: answers a run keeps for the check, drawn from the seed (all, if fewer)
CHECK_SAMPLE = 20_000


def _check_modules() -> dict:
    return {m.name: importlib.import_module(f"checks.{m.name}")
            for m in pkgutil.iter_modules(checks.__path__) if not m.name.startswith("_")}


def _by_kind(mods) -> dict:
    out = {}
    for mod in mods:
        for kind in mod.KINDS:
            if kind in out:
                raise ValueError(f"kind {kind!r} is checked by both {out[kind].__name__} "
                                 f"and {mod.__name__}")
            out[kind] = mod
    return out


def _limits(mods) -> dict:
    out = {"ring_gap": 1e-3, "ring_nan_miss": 0}
    for mod in mods:
        for k, v in mod.LIMITS.items():
            if k in out:
                raise ValueError(f"{mod.__name__} names the number {k!r} again")
            out[k] = v
    return {**out, "unanswered": 0, "failed": 0}


#: every module under ``checks/``, by name
MODULES = _check_modules()
#: number -> limit, of every check. The readings each was set from are in
#: PERF.md.
LIMITS = _limits(MODULES.values())


def checks_for(config: dict) -> dict:
    """Request kind -> the module that checks its answers under ``config``:
    the `COMMON_CHECKS`, and the modules the configuration names under
    ``"checks"``."""
    names = list(dict.fromkeys([*COMMON_CHECKS, *config.get("checks", ())]))
    missing = [n for n in names if n not in MODULES]
    if missing:
        raise ValueError(f"no module {missing} under checks/")
    return _by_kind(MODULES[n] for n in names)


def unchecked(kinds, config: dict) -> list[str]:
    """The kinds of ``kinds`` that none of ``config``'s checks covers and
    `OK_ONLY` leaves out."""
    covered = checks_for(config)
    return sorted(k for k in set(kinds) if k not in covered and k not in OK_ONLY)


@dataclasses.dataclass
class Evidence:
    """What a run leaves for the check."""

    config: dict          # the cell's configuration, as its file states it
    seed: int             # the fleet's seed
    n_clients: int
    rows: np.ndarray      # sampled vehicle rows (RING_ROWS)
    copies: list          # (plane tick, host copy of the ring), the last one final
    answers: list         # (kind, params, response) of the sampled requests
    unanswered: int       # requests never answered
    failed: int           # requests answered ok=False
    masks: object = None  # the reference's online state, made by `compare`

    @functools.cached_property
    def ref(self):
        """The configuration's plain reference module."""
        return importlib.import_module(self.config.get("reference", DEFAULT_REFERENCE))

    @property
    def scenario(self) -> str:
        return self.config["sim"]["scenario"]

    @property
    def history(self) -> int:
        """Ring slots (the configuration's signal_history)."""
        return int(self.config["sim"]["signal_history"])

    @property
    def p_leave(self) -> float:
        return float(self.config["sim"]["p_leave"])

    @property
    def p_return(self) -> float:
        return float(self.config["sim"]["p_return"])


def check_sample(seed: int, n: int) -> frozenset:
    """Which of a run's ``n`` requests have their answers checked."""
    if n <= CHECK_SAMPLE:
        return frozenset(range(n))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    return frozenset(int(j) for j in rng.choice(n, size=CHECK_SAMPLE, replace=False))


def sample_rows(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5A3])
    return np.sort(rng.choice(n, size=min(RING_ROWS, n), replace=False))


def check_ring(ev: Evidence, lower: bool = False) -> dict:
    ref = ev.ref
    gap, miss = 0.0, 0
    for t, ring in ev.copies:
        ticks = np.arange(t - ring.shape[0] + 1, t + 1)
        want = ref.observed(ev.scenario, ev.seed, ev.masks, ev.rows, ticks)
        got = ref.to_bf16(want) if lower else ring[ticks % ring.shape[0]][:, ev.rows, :]
        nan_got, nan_want = np.isnan(got), np.isnan(want)
        miss += int(np.count_nonzero(nan_got != nan_want))
        both = ~nan_got & ~nan_want
        gap = max(gap, ref.gap(got[both], want[both]))
    return {"ring_gap": gap, "ring_nan_miss": miss}


def compare(ev: Evidence, seed: int, lower: bool = False) -> dict:
    """Every compared number of a run (``_``-prefixed entries are counts of
    what was checked, for the log, and have no limit). ``lower`` puts the
    control in the program's place."""
    if ev.masks is None:
        ev.masks = ev.ref.churn(ev.seed, ev.n_clients, ev.p_leave, ev.p_return,
                                max(t for t, _ in ev.copies))
    out = check_ring(ev, lower)
    by_kind = checks_for(ev.config)
    by_check = collections.defaultdict(list)
    for kind, p, r in ev.answers:
        if kind in by_kind and r.ok:
            by_check[by_kind[kind]].append((kind, p, r))
    for mod, items in by_check.items():
        out.update(mod.check(ev, items, seed, lower))
    out["unanswered"] = ev.unanswered
    out["failed"] = ev.failed
    return out


def verdict(numbers: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items() if k in LIMITS)
