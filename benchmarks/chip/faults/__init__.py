"""Faults planted under the timed path, for the CPU rehearsal: each must
turn ``correct`` false.

Which faults a cell can have follows from its configuration and its mix
(`for_cell`), never from its name: a step that returns its state
unchanged and an answer altered where it is produced, in every cell; half
of the fleet left out of the plane step where the mix reads vehicles'
values or windows, and out of the fold where it reads fleet statistics;
churn on the wrong vehicles where the configuration has churn. No cell
has an exchange between chips to leave out (one chip each). A deployment
brings faults of its own as ``faults/<config>.py``, whose ``FAULTS``
lists functions ``fault(sim, gw)`` that break the program as built.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def stale_step(sim, gw):
    """The plane step returns its state unchanged."""
    p = sim.plane
    p._tick_fn = lambda t, hist, off: (p._dvalues, hist)


def half_step(sim, gw):
    """Half of the fleet left out of each plane step: its ring rows go
    unwritten (NaN), and every read is served from the rest."""
    import jax
    import jax.numpy as jnp

    p = sim.plane
    orig = p._tick_fn
    half = p.n_clients // 2
    blank = jax.jit(lambda h, s: h.at[s, half:, :].set(jnp.nan))

    def step(t, hist, off):
        vals, h = orig(t, hist, off)
        return vals, blank(h, t % h.shape[0])

    p._tick_fn = step


def half_fold(sim, gw):
    """Half of the fleet left out of each sketch fold: the statistics are
    taken over the rest."""
    p = sim.plane
    orig = p.compute_sketches

    def fold(name, spec, **kw):
        sk = orig(name, spec, **kw)
        half = sk.counts.shape[0] // 2
        sk.counts[half:] = 0
        sk.hists[half:] = 0
        sk.qvals[half:] = np.nan
        return sk

    p.compute_sketches = fold


def altered_answer(sim, gw):
    """One field of every answer altered where it is produced."""
    for name in ("_read_fleet_stats", "_read_quantile", "_read_window",
                 "_read_signal", "_read_gauges"):
        orig = getattr(gw, name)

        def alter(params, orig=orig):
            body = dict(orig(params))
            for k in ("mean", "value", "online"):
                if isinstance(body.get(k), (int, float)):
                    body[k] = body[k] + 1 if k == "online" else body[k] * (1 + 1e-3) + 1e-3
            if body.get("values"):
                body["values"] = body["values"][:-1] + [body["values"][-1] + 1e-3]
            return body

        setattr(gw, name, alter)


def wrong_churn(sim, gw):
    """Churn takes the wrong vehicles offline: each ignition toggle lands
    on the next vehicle in the fleet."""
    pool, toggle = sim.pool, sim.churn._toggle
    n = len(pool.vehicles)

    def next_one(cid):
        i = pool.vehicles[cid].metadata["index"]
        toggle(next(c for c, v in pool.vehicles.items()
                    if v.metadata["index"] == (i + 1) % n))

    sim.churn._toggle = next_one


def for_cell(cell) -> list:
    """The faults that ``cell`` (a `cells.Cell`) can have, in a fixed order:
    the general ones that its configuration and mix call for, then its
    configuration's own."""
    kinds = {k["kind"] for k in cell.mix["kinds"]}
    out = [stale_step]
    if kinds & {"signal", "window"}:
        out.append(half_step)
    if kinds & {"fleet_stats", "quantile"}:
        out.append(half_fold)
    out.append(altered_answer)
    if float(cell.config["sim"].get("p_leave", 0.0)) > 0.0:
        out.append(wrong_churn)
    own = HERE / f"{cell.config_name}.py"
    if own.exists():
        spec = importlib.util.spec_from_file_location(
            f"faults_{cell.config_name.replace('-', '_').replace('.', '_')}", own)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out += list(mod.FAULTS)
    return out
