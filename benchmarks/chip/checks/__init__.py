"""Checks by request kind, one module each, found by scanning this package.

A check module holds

* ``KINDS``: the request kinds whose answers it checks;
* ``LIMITS``: each number it returns that has a limit, with the limit;
* ``check(ev, items, seed, lower) -> dict``: its numbers over ``items``,
  the ``(kind, params, response)`` of the sampled answers of its kinds
  that came back ``ok``. It reads the reference that the evidence
  carries (``ev.ref``, the configuration's own), never one by name;
  ``lower`` puts the control in the program's place. Entries whose key
  starts with ``_`` are counts of what was checked, for the log.

`verdict.py` merges the modules' limits, so a number's name is one
module's. The modules of `verdict.COMMON_CHECKS` check every
configuration's answers; any other module checks only those of the
configurations that name it under ``"checks"``. A deployment that sends a
kind no common module checks adds a module here and names it in its
configuration; `run.py` refuses to run a mix that sends a kind that none
of its configuration's modules checks, unless `verdict.OK_ONLY` names it.
"""
from __future__ import annotations


def plane_tick(r) -> int:
    """Reads are served at the admission of tick ``served_tick``, against
    the state the previous tick left: plane tick ``served_tick - 1``."""
    return r.served_tick - 1


def column(ev, signal: str) -> int:
    """The ring column of ``signal`` in the evidence's reference."""
    return ev.ref.SIGNALS.index(signal)
