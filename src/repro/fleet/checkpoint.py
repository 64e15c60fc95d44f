"""Durable fleet state: bit-for-bit checkpoint/restore (ROADMAP item 4).

`FleetCheckpoint.save` freezes an entire running fleet world — broker
queues and in-flight fault legs, statestore documents, per-vehicle
LocalDisk caches and client sync state, the event-engine heap, churn RNG
streams, the signal plane ring (host or device-sharded, gathered), fleet
metrics, and optionally a live workload driver plus its in-flight round —
into a versioned on-disk format: one deterministic JSON manifest plus
content-addressed arrays via `repro.train.checkpoint.BlobStore` (the same
npy-tree blobs training checkpoints use; nothing is duplicated).

`FleetCheckpoint.restore` rebuilds the world by constructing a fresh
`FleetSimulator` from the saved config and then surgically overwriting
every piece of state, so all object wiring (wake closures, plane views,
watchers) comes from ordinary construction and only *values* come from
disk. The restore is **elastic**: pass ``mesh=`` to reshard a sharded
signal plane onto a different device count — ring rows are re-padded to
the new capacity and device arrays are re-placed, with reads unchanged.

The contract, proven by `tests/test_checkpoint.py`: for any supported
config, ``run(a+b) == run(a) -> save -> restore -> run(b)`` bit-for-bit
on aggregates, broker counters, participants, pump counts, and plane
reads — including checkpoints taken mid-round with tasks in flight.
"""
from __future__ import annotations

import dataclasses
import heapq
import json
from collections import deque
from functools import partial
from pathlib import Path
from typing import Any

import numpy as np

from repro.core import documents as _documents
from repro.core.broker import Message, Subscription, _is_exact
from repro.core.client import _LocalTask
from repro.core.documents import (
    Assignment,
    Parameters,
    Payload,
    Result,
    Task,
    TaskStatus,
)
from repro.core.statestore import ClientRecord, ClientStateSnapshot, TaskSyncInfo
from repro.core.user import AssignmentDoc, ParametersDoc, PayloadDoc, TaskDoc
from repro.fleet.analytics import (
    AnalyticsConfig,
    AnalyticsDriver,
    WindowInFlight,
    WindowStats,
)
from repro.fleet.engine import CalendarService, Entry, EngineService
from repro.fleet.federated import FedConfig
from repro.fleet.metrics import RoundMetrics, RoundProgress
from repro.fleet.rounds import DeadlinePump, FederatedDriver, RoundInFlight
from repro.fleet.service import DensePollService, FleetServiceScheduler
from repro.train.checkpoint import BlobStore

#: on-disk manifest format tag
FORMAT = "fleet-checkpoint"
#: bump whenever the manifest schema changes incompatibly
SCHEMA_VERSION = 1


class CheckpointError(Exception):
    """A checkpoint could not be written or read back faithfully."""


# --------------------------------------------------------------------------- #
# value codec: platform dataclasses + containers + ndarrays <-> JSON
# --------------------------------------------------------------------------- #

#: dataclasses that may appear inside checkpointed state; encoded as
#: ``[tag, [field values in dataclass field order]]``
_TAGGED = (
    Payload,
    Parameters,
    Task,
    Assignment,
    Result,
    ClientRecord,
    TaskSyncInfo,
    ClientStateSnapshot,
    Message,
    _LocalTask,
    RoundMetrics,
    RoundProgress,
    WindowStats,
    AnalyticsConfig,
    FedConfig,
)
_TAG_BY_TYPE = {t: t.__name__.lstrip("_") for t in _TAGGED}
_TYPE_BY_TAG = {tag: t for t, tag in _TAG_BY_TYPE.items()}
#: encoded field order per tagged type. Dataclasses use their field
#: order; `ClientRecord` is a slotted arena-view class (not a
#: dataclass), so its order is pinned to the constructor signature.
_FIELD_NAMES = {
    t: (
        ("client_id", "logical_clock", "online", "metadata")
        if t is ClientRecord
        else tuple(f.name for f in dataclasses.fields(t))
    )
    for t in _TAGGED
}


class _Codec:
    """Encode platform state to JSON-safe values; ndarrays are swapped
    for ``["ndarray", i]`` references into ``self.arrays`` (stored via
    BlobStore, so the manifest stays pure JSON)."""

    def __init__(self, arrays: list[np.ndarray] | None = None):
        self.arrays: list[np.ndarray] = list(arrays) if arrays else []

    def enc(self, obj: Any) -> Any:
        t = type(obj)
        if t in (type(None), bool, int, float, str):
            return obj
        if isinstance(obj, TaskStatus):  # str subclass: before tag dispatch
            return ["TaskStatus", obj.value]
        tag = _TAG_BY_TYPE.get(t)
        if tag is not None:
            return [tag, [self.enc(getattr(obj, name))
                          for name in _FIELD_NAMES[t]]]
        if t is list:
            return ["list", [self.enc(v) for v in obj]]
        if t is tuple:
            return ["tuple", [self.enc(v) for v in obj]]
        if t is dict:
            return ["dict", [[self.enc(k), self.enc(v)]
                             for k, v in obj.items()]]
        if t in (set, frozenset):
            return ["set", [self.enc(v) for v in sorted(obj)]]
        if isinstance(obj, np.ndarray):
            self.arrays.append(obj)
            return ["ndarray", len(self.arrays) - 1]
        if isinstance(obj, np.integer):
            return int(obj)
        if isinstance(obj, np.floating):
            return float(obj)
        if isinstance(obj, np.bool_):
            return bool(obj)
        if hasattr(obj, "__array__"):  # jax arrays from on-device kernels
            arr = np.asarray(obj)
            if arr.ndim == 0:
                return self.enc(arr.item())
            self.arrays.append(arr)
            return ["ndarray", len(self.arrays) - 1]
        raise CheckpointError(
            f"cannot checkpoint value of type {t.__name__}: {obj!r}"
        )

    def dec(self, obj: Any) -> Any:
        if not isinstance(obj, list):
            return obj
        if len(obj) != 2:
            raise CheckpointError(f"malformed encoded value: {obj!r}")
        tag, payload = obj
        if tag == "list":
            return [self.dec(v) for v in payload]
        if tag == "tuple":
            return tuple(self.dec(v) for v in payload)
        if tag == "dict":
            return {self.dec(k): self.dec(v) for k, v in payload}
        if tag == "set":
            return set(self.dec(v) for v in payload)
        if tag == "ndarray":
            return np.asarray(self.arrays[payload])
        if tag == "TaskStatus":
            return TaskStatus(payload)
        cls = _TYPE_BY_TAG.get(tag)
        if cls is not None:
            return cls(*[self.dec(v) for v in payload])
        raise CheckpointError(f"unknown value tag {tag!r}")


# --------------------------------------------------------------------------- #
# config
# --------------------------------------------------------------------------- #

#: config fields that must match the checkpoint exactly on restore —
#: they shape the state being overwritten
_STRUCTURAL = (
    "plane", "service", "churn", "engine",
    "n_clients", "scenario", "signal_history",
)
#: SimConfig mirror knobs stored as their enum .value strings
_KNOBS = ("plane", "service", "churn", "engine")


def _snap_config(cfg) -> dict:
    from repro.fleet.simulator import SimConfig

    out = {}
    for f in dataclasses.fields(SimConfig):
        if f.name == "backends":
            continue
        v = getattr(cfg, f.name)
        if f.name in _KNOBS:
            v = v.value if v is not None else None
        out[f.name] = v
    return out


def _restore_config(saved: dict, overrides: dict | None, mpath: Path, mesh):
    from repro.fleet.simulator import SimConfig

    if mesh is not None and saved.get("plane") != "sharded":
        raise CheckpointError(
            f"checkpoint {mpath}: mesh= is only valid for a sharded-plane "
            f"checkpoint (saved plane is {saved.get('plane')!r})"
        )
    overrides = dict(overrides or {})
    for name in _STRUCTURAL:
        if name in overrides:
            v = overrides.pop(name)
            v = getattr(v, "value", v)
            if v != saved.get(name):
                hint = (
                    " (pass mesh= to restore onto a different device layout)"
                    if name == "plane" else ""
                )
                raise CheckpointError(
                    f"checkpoint {mpath}: config field {name!r} is structural"
                    f" and cannot be overridden: saved {saved.get(name)!r},"
                    f" requested {v!r}{hint}"
                )
    cfg = SimConfig(**saved)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


# --------------------------------------------------------------------------- #
# broker
# --------------------------------------------------------------------------- #

def _snap_broker(broker, codec: _Codec) -> dict:
    subs = []
    for lst in broker._exact.values():
        subs.extend(lst)
    subs.extend(broker._wild)
    subs.sort(key=lambda s: s.order)
    return {
        "now": broker.now,
        "published": broker.published,
        "delivered": broker.delivered,
        "dropped": broker.dropped,
        "next_msg_id": broker._ids.n,
        "next_sub_order": broker._sub_order.n,
        "next_delay_order": broker._delay_order.n,
        "subs": [
            {
                "pattern": s.pattern,
                "qos": s.qos,
                "order": s.order,
                "reliable": s.reliable,
                "queue": codec.enc(list(s._queue)),
            }
            for s in subs
        ],
        "delayed": [
            {"due": due, "order": order, "sub": sub.order,
             "msg": codec.enc(msg)}
            for due, order, sub, msg in sorted(broker._delayed)
        ],
    }


def _restore_broker(broker, s: dict, codec: _Codec, mpath: Path) -> dict:
    broker.now = s["now"]
    broker.published = s["published"]
    broker.delivered = s["delivered"]
    broker.dropped = s["dropped"]
    broker._ids.n = s["next_msg_id"]
    broker._sub_order.n = s["next_sub_order"]
    broker._delay_order.n = s["next_delay_order"]
    broker._exact = {}
    broker._wild = []
    sub_map: dict[int, Subscription] = {}
    for e in s["subs"]:
        sub = Subscription(
            e["pattern"], e["qos"], order=e["order"], reliable=e["reliable"]
        )
        sub._queue.extend(codec.dec(e["queue"]))
        if _is_exact(e["pattern"]):
            broker._exact.setdefault(e["pattern"], []).append(sub)
        else:
            broker._wild.append(sub)
        sub_map[e["order"]] = sub
    delayed = []
    for e in s["delayed"]:
        sub = sub_map.get(e["sub"])
        if sub is None:
            raise CheckpointError(
                f"checkpoint {mpath}: delayed message references unknown "
                f"subscription order {e['sub']}"
            )
        delayed.append((e["due"], e["order"], sub, codec.dec(e["msg"])))
    heapq.heapify(delayed)
    broker._delayed = delayed
    return sub_map


# --------------------------------------------------------------------------- #
# statestore / documents
# --------------------------------------------------------------------------- #

_STORE_DICTS = (
    "_payloads", "_parameters", "_tasks", "_active_by_client",
    "_assignments", "_results", "_clients",
)


def _snap_store(store, codec: _Codec) -> dict:
    return {name: codec.enc(getattr(store, name)) for name in _STORE_DICTS}


def _restore_store(store, s: dict, codec: _Codec) -> None:
    for name in _STORE_DICTS:
        setattr(store, name, codec.dec(s[name]))
    # _watchers untouched: the fresh server watcher wiring stands
    if store.columns is not None:
        # decoded ClientRecords are unbound (local scalars); rebind them
        # to the arena — the columns section overwrites the arena last,
        # so these writes only re-establish the view wiring
        store.attach_columns(store.columns)


# --------------------------------------------------------------------------- #
# vehicles (LocalDisk + EdgeClient volatile state)
# --------------------------------------------------------------------------- #

_DISK_FIELDS = (
    "payload_cache", "parameters_cache", "unacked",
    "next_seq", "terminal", "task_state", "done",
)


def _snap_vehicles(pool, codec: _Codec) -> dict:
    out = {}
    for cid, v in pool.vehicles.items():
        d = v.disk
        entry: dict[str, Any] = {
            "index": v.metadata["index"],
            "online": v.client is not None,
            "disk": {f: codec.enc(getattr(d, f)) for f in _DISK_FIELDS},
        }
        if v.client is not None:
            c = v.client
            for lt in c.local_tasks.values():
                if lt.container is not None:
                    raise CheckpointError(
                        f"client {cid} has a live container thread; "
                        "checkpoint requires inline containers"
                    )
            entry["client"] = {
                "ts": c.ts,
                "tasks": codec.enc(c.tasks),
                "local_tasks": codec.enc(c.local_tasks),
                "syncing_state": c.syncing_state,
                "dirty_state": c.dirty_state,
                "ops": codec.enc(list(c._ops)),
                "container_events": codec.enc(list(c._container_events)),
                "registered": bool(getattr(c, "_registered", True)),
                "rpc_failures": c.rpc_failures,
                "sub": c._sub.order if c._sub is not None else None,
            }
        out[cid] = entry
    return out


def _apply_power_state(sim, saved: dict) -> None:
    """Align the fresh fleet's power state with the checkpoint BEFORE
    any state is overwritten — power_off touches broker/store/plane/
    churn/service, and all those side effects get overwritten later."""
    for cid in sorted(saved):
        if not saved[cid]["online"]:
            sim.pool.power_off(cid)


def _restore_vehicles(sim, saved: dict, sub_map: dict, codec: _Codec,
                      mpath: Path) -> None:
    pool = sim.pool
    if set(saved) != set(pool.vehicles):
        raise CheckpointError(
            f"checkpoint {mpath}: vehicle ids do not match the fleet "
            f"(saved {len(saved)}, live {len(pool.vehicles)})"
        )
    for cid in sorted(saved):
        e = saved[cid]
        v = pool.vehicles[cid]
        d = v.disk
        for f in _DISK_FIELDS:
            setattr(d, f, codec.dec(e["disk"][f]))
        if not e["online"]:
            continue
        c = v.client
        ce = e["client"]
        c.ts = ce["ts"]
        c.tasks = codec.dec(ce["tasks"])
        c.local_tasks = codec.dec(ce["local_tasks"])
        c.syncing_state = ce["syncing_state"]
        c.dirty_state = ce["dirty_state"]
        c._ops = codec.dec(ce["ops"])
        c._container_events = deque(codec.dec(ce["container_events"]))
        c._registered = ce["registered"]
        c.rpc_failures = ce["rpc_failures"]
        if ce["sub"] is None:
            c._sub = None
        else:
            sub = sub_map.get(ce["sub"])
            if sub is None:
                raise CheckpointError(
                    f"checkpoint {mpath}: client {cid} references unknown "
                    f"subscription order {ce['sub']}"
                )
            c._sub = sub
            c._sub.wake = c._wake_cb


# --------------------------------------------------------------------------- #
# event engine
# --------------------------------------------------------------------------- #

def _snap_engine(engine) -> tuple[dict, dict[int, int]]:
    entries = []
    id_to_seq: dict[int, int] = {}
    for at, phase, key, seq, entry in sorted(engine._heap):
        if entry.canceled:
            continue
        fn = entry.fn
        if fn is None:
            kind, args = "timer", []
        elif isinstance(fn, partial):
            name = fn.func.__name__
            if name == "_fire":
                kind = "churn"
            elif name == "_fire_resync":
                kind = "resync"
            elif name == "_fire_release":
                kind = "release"
            else:
                raise CheckpointError(
                    f"cannot checkpoint engine callback {name!r}"
                )
            args = [a if isinstance(a, str) else int(a) for a in fn.args]
        else:
            raise CheckpointError(
                f"cannot checkpoint engine callback {fn!r}"
            )
        id_to_seq[id(entry)] = seq
        entries.append({
            "at": at, "phase": phase, "key": key, "seq": seq,
            "kind": kind, "args": args,
        })
    return {"now": engine.now, "next_seq": engine._seq.n,
            "entries": entries}, id_to_seq


def _restore_engine(sim, s: dict, mpath: Path) -> dict[int, Entry]:
    eng = sim.engine
    eng.now = s["now"]
    eng._seq.n = s["next_seq"]
    seq_map: dict[int, Entry] = {}
    heap = []
    for e in s["entries"]:
        kind, args = e["kind"], e["args"]
        if kind == "timer":
            fn = None
        elif kind == "churn":
            fn = partial(sim.churn._fire, args[0], int(args[1]))
        elif kind in ("resync", "release"):
            if not isinstance(sim.service, EngineService):
                raise CheckpointError(
                    f"checkpoint {mpath}: engine entry kind {kind!r} "
                    "requires the engine service backend"
                )
            target = (sim.service._fire_resync if kind == "resync"
                      else sim.service._fire_release)
            fn = partial(target, int(args[0]), int(args[1]))
        else:
            raise CheckpointError(
                f"checkpoint {mpath}: unknown engine entry kind {kind!r}"
            )
        entry = Entry(e["at"], e["phase"], e["key"], fn)
        seq_map[e["seq"]] = entry
        heap.append((e["at"], e["phase"], e["key"], e["seq"], entry))
    heapq.heapify(heap)
    eng._heap = heap
    return seq_map


# --------------------------------------------------------------------------- #
# churn
# --------------------------------------------------------------------------- #

def _snap_churn(churn) -> dict:
    return {
        "now": churn.now,
        "vehicles": {
            cid: {
                "index": churn._index[cid],
                "online": churn._online[cid],
                "next": churn._next.get(cid),
                "rng": churn._rng[cid].bit_generator.state,
            }
            for cid in sorted(churn._online)
        },
    }


def _restore_churn(sim, s: dict, mpath: Path) -> None:
    ch = sim.churn
    ch.now = s["now"]
    for cid, e in s["vehicles"].items():
        if cid not in ch._online:
            raise CheckpointError(
                f"checkpoint {mpath}: churn references unknown vehicle {cid}"
            )
        ch._index[cid] = e["index"]
        ch._online[cid] = e["online"]
        if e["next"] is None:
            ch._next.pop(cid, None)
        else:
            ch._next[cid] = e["next"]
        ch._rng[cid].bit_generator.state = e["rng"]
    if ch._engine is None and ch._use_heap:
        heap = [(t, ch._index[cid], cid) for cid, t in ch._next.items()
                if t is not None]
        heapq.heapify(heap)
        ch._heap = heap


# --------------------------------------------------------------------------- #
# service
# --------------------------------------------------------------------------- #

def _snap_service(svc, codec: _Codec) -> dict:
    if isinstance(svc, CalendarService):  # deepest subclass first
        # the refill schedule lives in lane membership bits, not the
        # heap: save them directly (resync membership also equals the
        # power state, but saving it keeps restore order-independent)
        n = svc._capacity
        return {
            "kind": "calendar",
            "runnable": [bool(b) for b in svc._runnable],
            "hot": [int(i) for i in svc._hot],
            "due": [int(i) for i in svc._due],
            "resync": [int(i) for i in
                       np.nonzero(svc._resync_lane._on[:n])[0]],
            "release": [int(i) for i in
                        np.nonzero(svc._release_lane._on[:n])[0]],
        }
    if isinstance(svc, EngineService):  # subclass check first
        return {
            "kind": "engine",
            "runnable": [bool(b) for b in svc._runnable],
            "hot": [int(i) for i in svc._hot],
            "due": [int(i) for i in svc._due],
            "resync_at": sorted([int(k), int(v)]
                                for k, v in svc._resync_at.items()),
            "release_at": sorted([int(k), int(v)]
                                 for k, v in svc._release_at.items()),
        }
    if isinstance(svc, DensePollService):
        return {"kind": "dense"}
    if isinstance(svc, FleetServiceScheduler):
        return {"kind": "scheduler",
                "runnable": [bool(b) for b in svc._runnable]}
    raise CheckpointError(
        f"cannot checkpoint service of type {type(svc).__name__}"
    )


def _restore_service(sim, s: dict, mpath: Path) -> None:
    svc = sim.service
    kind = s["kind"]
    if kind == "dense":
        if not isinstance(svc, DensePollService):
            raise CheckpointError(
                f"checkpoint {mpath}: service kind mismatch: saved 'dense', "
                f"live {type(svc).__name__}"
            )
        return
    runnable = np.asarray(s["runnable"], dtype=bool)
    if runnable.shape != svc._runnable.shape:
        raise CheckpointError(
            f"checkpoint {mpath}: field 'service.runnable' has shape "
            f"{runnable.shape}, live scheduler expects {svc._runnable.shape}"
        )
    svc._runnable[:] = runnable
    if kind == "calendar":
        if not isinstance(svc, CalendarService):
            raise CheckpointError(
                f"checkpoint {mpath}: service kind mismatch: saved "
                f"'calendar', live {type(svc).__name__}"
            )
        svc._hot = deque(int(i) for i in s["hot"])
        svc._due = [int(i) for i in s["due"]]
        for lane, key in ((svc._resync_lane, "resync"),
                          (svc._release_lane, "release")):
            lane._on[:] = False
            for i in s[key]:
                lane.set_member(int(i), True)
    elif kind == "engine":
        if not isinstance(svc, EngineService) or isinstance(
            svc, CalendarService
        ):
            raise CheckpointError(
                f"checkpoint {mpath}: service kind mismatch: saved 'engine', "
                f"live {type(svc).__name__}"
            )
        svc._hot = deque(int(i) for i in s["hot"])
        svc._due = [int(i) for i in s["due"]]
        svc._resync_at = {int(k): int(v) for k, v in s["resync_at"]}
        svc._release_at = {int(k): int(v) for k, v in s["release_at"]}


# --------------------------------------------------------------------------- #
# columnar per-client arena
# --------------------------------------------------------------------------- #

def _snap_columns(sim, codec: _Codec) -> dict | None:
    cols = getattr(sim, "columns", None)
    if cols is None:
        return None
    return {
        "ids": list(cols.client_ids()),
        "arrays": {name: codec.enc(arr)
                   for name, arr in cols.snapshot().items()},
    }


def _restore_columns(sim, s: dict | None, codec: _Codec, mpath: Path) -> None:
    """Overwrite the arena from its snapshot — applied LAST, so the
    column values (clocks, power, timestamps, gating bits) written
    through viewer properties during the earlier restore passes are
    superseded by the authoritative saved arrays."""
    cols = getattr(sim, "columns", None)
    if s is None or cols is None:
        return
    ids = list(s["ids"])
    live = list(cols.client_ids())
    if ids != live:
        raise CheckpointError(
            f"checkpoint {mpath}: columns row registry does not match the "
            f"fresh fleet (saved {len(ids)} rows, live {len(live)})"
        )
    cols.load({name: codec.dec(v) for name, v in s["arrays"].items()}, ids)


# --------------------------------------------------------------------------- #
# signal plane
# --------------------------------------------------------------------------- #

def _snap_plane(plane, codec: _Codec) -> dict:
    from repro.core.plane_sharded import ShardedSignalPlane

    n = plane.n_clients
    if isinstance(plane, ShardedSignalPlane):
        ring = np.asarray(plane._dhist)[:, :n, :]
        values = np.asarray(plane._dvalues)[:n]
        backend = "sharded"
    else:
        ring = plane._hist[:, :n, :].copy()
        values = plane._values[:n].copy()
        backend = "host"
    return {
        "backend": backend,
        "t": plane.t,
        "hist_len": plane._hist_len,
        "n_clients": n,
        "ring": codec.enc(np.ascontiguousarray(ring)),
        "values": codec.enc(np.ascontiguousarray(values)),
        "offline": codec.enc(np.array(plane._offline[:n])),
    }


def _restore_plane(sim, s: dict, codec: _Codec, mpath: Path) -> None:
    plane = sim.plane
    n = s["n_clients"]
    if plane.n_clients != n:
        raise CheckpointError(
            f"checkpoint {mpath}: field 'plane.n_clients' is {n}, live "
            f"plane has {plane.n_clients}"
        )
    ring = codec.dec(s["ring"])
    values = codec.dec(s["values"])
    offline = codec.dec(s["offline"])
    want = (plane._hist_cap, n, len(plane.names))
    if ring.shape != want:
        raise CheckpointError(
            f"checkpoint {mpath}: field 'plane.ring' has shape "
            f"{ring.shape}, expected {want}"
        )
    from repro.core.plane_sharded import ShardedSignalPlane

    if isinstance(plane, ShardedSignalPlane):
        import jax
        import jax.numpy as jnp

        from repro.sharding import fleet as fleet_sharding

        cap = plane._capacity
        full = np.full((plane._hist_cap, cap, len(plane.names)), np.nan,
                       dtype=np.float32)
        full[:, :n, :] = ring
        plane._dhist = jax.device_put(
            full, fleet_sharding.ring_sharding(plane.mesh)
        )
        plane.t = s["t"]
        plane._dvalues = plane._values_fn(jnp.int32(s["t"]))
        off = np.zeros(cap, dtype=bool)
        off[:n] = offline
        plane._offline = off
        plane._mask_dirty = True  # uploaded (as a copy) at the next step
        plane._hist_len = s["hist_len"]
        plane._values_dirty = True
        plane._hist_dirty = True
        plane._sketch_cache.clear()
    else:
        if s["backend"] == "sharded":
            raise CheckpointError(
                f"checkpoint {mpath}: field 'plane.backend' is 'sharded' "
                "but the live plane is host-resident; restore with the "
                "saved plane backend (optionally passing mesh=)"
            )
        plane._values[:n] = np.asarray(values, dtype=np.float32)
        plane._hist[:, :n, :] = np.asarray(ring, dtype=np.float32)
        plane._offline[:n] = offline
        plane.t = s["t"]
        plane._hist_len = s["hist_len"]
        plane._sketch_cache.clear()


# --------------------------------------------------------------------------- #
# workload driver + in-flight round
# --------------------------------------------------------------------------- #

def _snap_driver(driver, codec: _Codec) -> dict:
    if isinstance(driver, FederatedDriver):
        if driver.n_samples_fn is not None:
            raise CheckpointError(
                "FederatedDriver.n_samples_fn callables are not serializable"
            )
        return {
            "kind": "federated",
            "cfg": codec.enc(driver.cfg),
            "w": codec.enc(driver.w),
            "w_true": codec.enc(np.asarray(driver.w_true)),
            "bias_signal": driver.bias_signal,
            "n_samples": driver.n_samples,
            "payload_source": driver.payload_source,
            "status_oracle": driver.status_oracle,
            "has_metrics": driver.metrics is not None,
            "history": codec.enc(driver.history),
            "last_msgs": codec.enc(driver.last_msgs),
        }
    if isinstance(driver, AnalyticsDriver):
        return {
            "kind": "analytics",
            "cfg": codec.enc(driver.cfg),
            "status_oracle": driver.status_oracle,
            "has_metrics": driver.metrics is not None,
            "history": codec.enc(driver.history),
            "last_sketches": codec.enc(driver.last_sketches),
        }
    raise CheckpointError(
        f"cannot checkpoint driver of type {type(driver).__name__}"
    )


def _restore_driver(sim, d: dict, codec: _Codec):
    kind = d["kind"]
    if kind == "federated":
        w = codec.dec(d["w"])
        w_true = codec.dec(d["w_true"])
        drv = FederatedDriver(
            sim.user,
            codec.dec(d["cfg"]),
            dim=int(w.shape[0]),
            w_true=w_true,
            bias_signal=d["bias_signal"],
            n_samples=d["n_samples"],
            payload_source=d["payload_source"],
            engine=sim.engine,
            status_oracle=d["status_oracle"],
            metrics=sim.metrics if d["has_metrics"] else None,
        )
        drv.w = np.asarray(w, dtype=np.float32)
        drv.history = codec.dec(d["history"])
        drv.last_msgs = codec.dec(d["last_msgs"])
        return drv
    if kind == "analytics":
        drv = AnalyticsDriver(
            sim.user,
            codec.dec(d["cfg"]),
            engine=sim.engine,
            status_oracle=d["status_oracle"],
            metrics=sim.metrics if d["has_metrics"] else None,
        )
        drv.history = codec.dec(d["history"])
        drv.last_sketches = codec.dec(d["last_sketches"])
        return drv
    raise CheckpointError(f"unknown driver kind {kind!r}")


def _snap_rif(rif, id_to_seq: dict[int, int], codec: _Codec) -> dict:
    doc = rif.assign
    if doc.assignment_id is None:
        raise CheckpointError(
            "in-flight round's assignment is not committed; checkpoint "
            "after start_round/start_window"
        )
    p = rif.pump
    dl = p.deadline
    return {
        "round": getattr(rif, "rnd", None) if isinstance(rif, RoundInFlight)
                 else rif.window_id,
        "n_clients": rif.n_clients,
        "assign": {
            "name": doc.name,
            "assignment_id": doc.assignment_id,
            "tasks": [
                {
                    "client_id": t.client_id,
                    "payload_id": t.payload.payload_id,
                    "parameters_id": (t.parameters.parameters_id
                                      if t.parameters is not None else None),
                    "task_id": t.task_id,
                }
                for t in doc.tasks
            ],
            "terminal": codec.enc(doc._terminal),
            "n_finished": doc._n_finished,
            "n_error": doc._n_error,
            "n_canceled": doc._n_canceled,
            "task_ids": codec.enc(doc._task_ids),
            "results_sub": doc._results_sub.order,
            "status_sub": doc._status_sub.order,
        },
        "pump": {
            "need": p.need,
            "budget": p.budget,
            "pumps": p.pumps,
            "closed": p.closed,
            "has_on_counts": p.on_counts is not None,
            "deadline": None if dl is None else {
                "at": dl.at, "phase": dl.phase, "key": dl.key,
                "fired": dl.fired, "canceled": dl.canceled,
                "seq": id_to_seq.get(id(dl)),
            },
        },
    }


def _restore_rif(sim, driver, r: dict, sub_map: dict, seq_map: dict,
                 codec: _Codec, mpath: Path):
    a = r["assign"]
    doc = AssignmentDoc(sim.user, a["name"], tasks=[])
    doc.assignment_id = a["assignment_id"]
    for te in a["tasks"]:
        pd = PayloadDoc(sim.user, source="", name="",
                        payload_id=te["payload_id"])
        prm = (ParametersDoc(sim.user, value=None,
                             parameters_id=te["parameters_id"])
               if te["parameters_id"] is not None else None)
        doc.tasks.append(
            TaskDoc(sim.user, te["client_id"], pd, prm,
                    task_id=te["task_id"])
        )
    doc._terminal = codec.dec(a["terminal"])
    doc._n_finished = a["n_finished"]
    doc._n_error = a["n_error"]
    doc._n_canceled = a["n_canceled"]
    doc._task_ids = codec.dec(a["task_ids"])
    for attr, key in (("_results_sub", "results_sub"),
                      ("_status_sub", "status_sub")):
        sub = sub_map.get(a[key])
        if sub is None:
            raise CheckpointError(
                f"checkpoint {mpath}: in-flight assignment references "
                f"unknown subscription order {a[key]}"
            )
        setattr(doc, attr, sub)
    doc._status_sub.wake = doc._absorb_status_events
    doc._absorb_status_events()

    ps = r["pump"]
    p = DeadlinePump.__new__(DeadlinePump)
    p.assign = doc
    p.n_tasks = r["n_clients"]
    p.need = ps["need"]
    p.budget = ps["budget"]
    p.pump = sim.tick
    p.engine = sim.engine
    p.status_oracle = driver.status_oracle
    p.on_counts = (sim.metrics.update_progress
                   if ps["has_on_counts"] else None)
    p.hard = p.budget if p.budget is not None else 100_000
    p.pumps = ps["pumps"]
    p.closed = ps["closed"]
    dl = ps["deadline"]
    if dl is None:
        p.deadline = None
    elif dl["seq"] is not None and dl["seq"] in seq_map:
        p.deadline = seq_map[dl["seq"]]  # same Entry the heap holds
    else:
        entry = Entry(dl["at"], dl["phase"], dl["key"], None)
        entry.fired = dl["fired"]
        entry.canceled = dl["canceled"]
        p.deadline = entry

    if isinstance(driver, AnalyticsDriver):
        return WindowInFlight(window_id=r["round"],
                              n_clients=r["n_clients"], assign=doc, pump=p)
    return RoundInFlight(rnd=r["round"], n_clients=r["n_clients"],
                         assign=doc, pump=p)


# --------------------------------------------------------------------------- #
# the public facade
# --------------------------------------------------------------------------- #

class FleetCheckpoint:
    """Versioned whole-platform checkpoints on disk.

    Layout: ``{path}/manifest.json`` (deterministic JSON, sorted keys)
    plus ``{path}/arrays/`` — a `BlobStore` of content-addressed npy
    leaves holding every ndarray referenced from the manifest.
    """

    @staticmethod
    def save(sim, path: str | Path, *, driver=None, rif=None,
             previous: str | Path | None = None) -> Path:
        """Freeze the fleet at ``path``. With ``previous`` (the last
        checkpoint of the same run), unchanged content-addressed arrays
        are hardlinked from it instead of rewritten — periodic saves of
        a mostly-idle mega-fleet cost I/O proportional to what changed
        (the launch hook threads this automatically)."""
        if rif is not None and driver is None:
            raise CheckpointError(
                "cannot checkpoint an in-flight round without its driver"
            )
        if sim.plane is None:
            raise CheckpointError(
                "cannot checkpoint a simulator with an external signal_fn "
                "plane"
            )
        cfg = sim.cfg
        if len(sim.pool.vehicles) != cfg.n_clients:
            raise CheckpointError(
                f"fleet size {len(sim.pool.vehicles)} != configured "
                f"n_clients {cfg.n_clients}; grown fleets are unsupported"
            )
        codec = _Codec()
        if sim.engine is not None:
            engine_state, id_to_seq = _snap_engine(sim.engine)
        else:
            engine_state, id_to_seq = None, {}
        state = {
            "config": _snap_config(cfg),
            "t": sim.t,
            "documents_next_id": _documents._ids.n,
            "broker": _snap_broker(sim.broker, codec),
            "engine": engine_state,
            "churn": _snap_churn(sim.churn),
            "store": _snap_store(sim.store, codec),
            "vehicles": _snap_vehicles(sim.pool, codec),
            "plane": _snap_plane(sim.plane, codec),
            "service": _snap_service(sim.service, codec),
            "columns": _snap_columns(sim, codec),
            "metrics": {
                "rounds": codec.enc(sim.metrics.rounds),
                "progress": codec.enc(sim.metrics.progress),
            },
            "driver": _snap_driver(driver, codec) if driver is not None
                      else None,
            "rif": _snap_rif(rif, id_to_seq, codec) if rif is not None
                   else None,
        }
        path = Path(path)
        path.mkdir(parents=True, exist_ok=True)
        BlobStore(path / "arrays").put(
            "arrays", codec.arrays,
            link_from=None if previous is None else Path(previous) / "arrays",
        )
        manifest = {"format": FORMAT, "schema": SCHEMA_VERSION,
                    "state": state}
        (path / "manifest.json").write_text(
            json.dumps(manifest, sort_keys=True, separators=(",", ":"))
        )
        return path

    @staticmethod
    def restore(path: str | Path, *, config_overrides: dict | None = None,
                mesh=None):
        from repro.fleet.simulator import FleetSimulator

        path = Path(path)
        mpath = path / "manifest.json"
        if not mpath.exists():
            raise CheckpointError(f"checkpoint manifest missing: {mpath}")
        try:
            manifest = json.loads(mpath.read_text())
        except ValueError as e:
            raise CheckpointError(
                f"checkpoint manifest corrupt: {mpath}: {e}"
            ) from e
        fmt = manifest.get("format")
        if fmt != FORMAT:
            raise CheckpointError(
                f"checkpoint {mpath} has format {fmt!r}, expected {FORMAT!r}"
            )
        schema = manifest.get("schema")
        if schema != SCHEMA_VERSION:
            raise CheckpointError(
                f"checkpoint {mpath} has schema version {schema!r}; this "
                f"build reads {SCHEMA_VERSION}"
            )
        try:
            arrays = BlobStore(path / "arrays").get("arrays")
        except (FileNotFoundError, ValueError) as e:
            raise CheckpointError(
                f"checkpoint {mpath} arrays unreadable: {e}"
            ) from e
        codec = _Codec(arrays)
        state = manifest["state"]

        cfg = _restore_config(state["config"], config_overrides, mpath, mesh)
        sim = FleetSimulator(cfg, mesh=mesh)
        _apply_power_state(sim, state["vehicles"])
        sub_map = _restore_broker(sim.broker, state["broker"], codec, mpath)
        _restore_store(sim.store, state["store"], codec)
        _documents._ids.n = state["documents_next_id"]
        _restore_vehicles(sim, state["vehicles"], sub_map, codec, mpath)
        if state["engine"] is not None:
            if sim.engine is None:
                raise CheckpointError(
                    f"checkpoint {mpath}: saved engine state but the live "
                    "config has no event engine"
                )
            seq_map = _restore_engine(sim, state["engine"], mpath)
        else:
            seq_map = {}
        _restore_churn(sim, state["churn"], mpath)
        _restore_service(sim, state["service"], mpath)
        _restore_plane(sim, state["plane"], codec, mpath)
        sim.metrics.rounds = codec.dec(state["metrics"]["rounds"])
        sim.metrics.progress = codec.dec(state["metrics"]["progress"])
        _restore_columns(sim, state.get("columns"), codec, mpath)
        sim.t = state["t"]

        driver = None
        rif = None
        if state["driver"] is not None:
            driver = _restore_driver(sim, state["driver"], codec)
        if state["rif"] is not None:
            if driver is None:
                raise CheckpointError(
                    f"checkpoint {mpath}: in-flight round saved without a "
                    "driver"
                )
            rif = _restore_rif(sim, driver, state["rif"], sub_map, seq_map,
                               codec, mpath)
        return sim, driver, rif
