"""Device-sharded fleet signal plane.

`FleetSignalPlane` keeps the whole fleet's signals in one *host* array —
fine for thousands of vehicles, a single-host bottleneck at millions
(the ROADMAP's "sharded signal plane" item; OODIDA names central handling
of whole-fleet streams as the bottleneck AutoSPADA descends from).
`ShardedSignalPlane` lays the same structure of arrays out over a 1-D
``clients`` device mesh (`repro.sharding.fleet`):

* ``values``   `(capacity, n_signals)`      — rows split across devices;
* history ring `(history, capacity, n_signals)` — client axis split, the
  slot axis whole per device;
* offline mask `(capacity,)`                — aligned with the rows.

The per-tick step is jit'd ONCE with ``in_shardings``/``out_shardings``
pinning that layout, and fuses the drive-cycle evaluation with the ring
slot write (the ring buffer is donated, so the write is in place). Every
scenario op is elementwise per client row, so GSPMD partitions the step
with zero collectives: each device advances only its row shard. Because
the scenario step functions are pure and shared verbatim with the host
plane (`Scenario.step_fn`), the two planes are bit-for-bit identical —
the parity tests pin this down at N=1024 on 8 simulated devices.

Growth is shard-aware: capacity is always rounded up to a multiple of the
device count (`round_up_clients`), so a geometric double moves from one
evenly-divisible layout to another and never forces a resharding
collective on the tick path. Reads go through lazily synced host mirrors
(`values` / the ring are fetched device->host only when a payload
actually calls ``get_signal`` / ``get_signal_window``), which keeps the
hot tick loop free of blocking transfers; `PlaneSignalView`,
`SignalHandler`, NaN offline masking and the scenario generators all work
unchanged on top.
"""
from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import obs
from repro.core.signals import FleetSignalPlane
from repro.sharding import fleet as fleet_sharding


class ShardedSignalPlane(FleetSignalPlane):
    """`FleetSignalPlane` semantics over a client-sharded device layout.

    ``step_builder(capacity)`` must return the scenario's *pure* jax step
    (`t -> (capacity, n_signals)` float32) — `Scenario.step_fn` is the
    canonical source. Materialized traces stay host-only
    (`FleetSignalPlane.from_trace`); CSV playback works here too via
    `from_csv_fleet`, which streams one host row per tick into the
    sharded ring instead of materializing the trace.
    """

    def __init__(
        self,
        names: Sequence[str],
        n_clients: int,
        step_builder: Callable[[int], Callable[[jax.Array], jax.Array]],
        *,
        history: int = 256,
        growth: float = 2.0,
        mesh: Mesh | None = None,
    ):
        self.names = tuple(names)
        self._col = {n: j for j, n in enumerate(self.names)}
        self._growth = max(1.0, float(growth))
        self.mesh = mesh if mesh is not None else fleet_sharding.client_mesh()
        self._step_builder = step_builder
        #: host row source for CSV playback (`from_csv_fleet`); None for
        #: scenario planes, whose ticks are fully device-resident
        self._feed = None
        self._hist_cap = max(1, int(history))
        self.t = 0
        self.n_clients = int(n_clients)
        if self.n_clients < 0:
            raise ValueError("n_clients must be >= 0")
        # an empty fleet still allocates one device row per shard, so the
        # degenerate --clients 0 config works like the host plane's (0, S)
        self._capacity = fleet_sharding.round_up_clients(
            max(1, self.n_clients), self.mesh
        )
        self._compile(self._capacity)
        self._dvalues = self._values_fn(jnp.int32(0))
        if self._dvalues.shape != (self._capacity, len(self.names)):
            raise ValueError(
                f"step_builder must return (capacity, {len(self.names)}), "
                f"got {self._dvalues.shape}"
            )
        self._dhist = self._init_ring_fn(self._dvalues)
        self._offline = np.zeros(self._capacity, bool)
        self._mask_dirty = True
        self._sync_mask()
        self._hist_len = 1
        # lazily synced host mirrors — the read path is unchanged base code
        self._values = np.asarray(self._dvalues)
        self._hist = np.asarray(self._dhist)
        self._values_dirty = False
        self._hist_dirty = False
        self._sketch_cache: dict = {}
        self.sketch_hits = 0
        self.sketch_misses = 0
        #: device->host ring transfers so far — the sketch path must
        #: never bump it (asserted in the fleet/sketch_* benchmark)
        self.ring_syncs = 0
        #: bytes of every device->host copy the plane makes (ring and
        #: values mirrors, sketch blocks)
        self.d2h_bytes = 0

    @property
    def devices(self) -> int:
        return fleet_sharding.device_count(self.mesh)

    # -- compiled per-capacity machinery -------------------------------- #
    def _compile(self, cap: int) -> None:
        """Build and jit the per-tick advance for one capacity, with the
        client-sharded layout pinned on both sides. Called O(log N) times
        across N joins (geometric growth), like the host plane's series
        rebuilds."""
        step = self._step_builder(cap)
        rep = fleet_sharding.replicated(self.mesh)
        vsh = fleet_sharding.values_sharding(self.mesh)
        rsh = fleet_sharding.ring_sharding(self.mesh)
        msh = fleet_sharding.mask_sharding(self.mesh)
        hist_cap = self._hist_cap

        def tick(t, hist, offline):
            vals = step(t)
            row = jnp.where(offline[:, None], jnp.nan, vals)
            hist = jax.lax.dynamic_update_slice_in_dim(
                hist, row[None], t % hist_cap, axis=0
            )
            return vals, hist

        self._tick_fn = jax.jit(
            tick,
            in_shardings=(rep, rsh, msh),
            out_shardings=(vsh, rsh),
            donate_argnums=(1,),
        )
        self._values_fn = jax.jit(step, out_shardings=vsh)

        def feed_tick(t, vals, hist, offline):
            # host-fed variant of tick(): the row arrives device-placed
            # from the CSV stream instead of from the scenario step
            row = jnp.where(offline[:, None], jnp.nan, vals)
            hist = jax.lax.dynamic_update_slice_in_dim(
                hist, row[None], t % hist_cap, axis=0
            )
            return vals, hist

        self._feed_fn = jax.jit(
            feed_tick,
            in_shardings=(rep, vsh, rsh, msh),
            out_shardings=(vsh, rsh),
            donate_argnums=(2,),
        )

        def init_ring(vals):
            ring = jnp.full((hist_cap, cap, vals.shape[1]), jnp.nan, jnp.float32)
            return ring.at[0].set(vals)

        self._init_ring_fn = jax.jit(init_ring, out_shardings=rsh)

        def join(hist, vals, i, slot):
            # A joining row's ring history is NaN except the current
            # tick. Written as an elementwise masked select on broadcast
            # iotas rather than a dynamic_update_slice along the sharded
            # client axis: GSPMD partitions iota+where shard-locally
            # (each device rewrites only its own row shard of the
            # donated ring), where the slice update's halo analysis can
            # materialize more than the touched shard.
            cli = jax.lax.broadcasted_iota(jnp.int32, (1, hist.shape[1], 1), 1)
            slt = jax.lax.broadcasted_iota(jnp.int32, (hist_cap, 1, 1), 0)
            col = jnp.where(slt == slot, vals[None], jnp.nan)
            return jnp.where(cli == i, col, hist)

        self._join_fn = jax.jit(
            join,
            in_shardings=(rsh, vsh, rep, rep),
            out_shardings=rsh,
            donate_argnums=(0,),
        )

        def grow_ring(old, vals0):
            ring = jnp.full((hist_cap, cap, vals0.shape[1]), jnp.nan, jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(ring, old, 0, axis=1)

        # old ring arrives with the previous (smaller, also even) layout;
        # jit re-lays it out into the new capacity once per regrow
        self._grow_ring_fn = jax.jit(grow_ring, out_shardings=rsh)

    # -- host mirror sync ------------------------------------------------ #
    def _sync_values(self) -> None:
        if self._values_dirty:
            self._values = np.asarray(self._dvalues)
            self._values_dirty = False
            self.d2h_bytes += self._values.nbytes

    def _sync_hist(self) -> None:
        if self._hist_dirty:
            with obs.span("plane.ring_sync"):
                self._hist = np.asarray(self._dhist)
            self._hist_dirty = False
            self.ring_syncs += 1
            self.d2h_bytes += self._hist.nbytes

    def _sync_mask(self) -> None:
        """Upload the offline mask at most once per tick: K ignition
        toggles between steps cost one transfer, not K."""
        if self._mask_dirty:
            # upload a copy: `set_online` mutates `_offline` in place, and
            # device_put may read the host buffer after it returns (the
            # copy is asynchronous), so a later toggle could reach this
            # tick's mask
            self._doffline = jax.device_put(
                self._offline.copy(), fleet_sharding.mask_sharding(self.mesh)
            )
            self._mask_dirty = False

    @property
    def values(self) -> np.ndarray:
        self._sync_values()
        return self._values[: self.n_clients]

    # -- the hot path ----------------------------------------------------- #
    def step(self) -> None:
        """Advance every device's row shard: ONE sharded jit call fusing
        the scenario step with the in-place (donated) ring slot write. No
        host transfer happens here — mirrors sync lazily on read.

        CSV-fed planes (`from_csv_fleet`) pull the next streamed host
        row instead, pad it to capacity, and run the same donated ring
        write — one host->device transfer per tick, never a trace."""
        with obs.span("plane.step"):
            self.t += 1
            self._sync_mask()
            if self._feed is not None:
                row = self._feed.series(self.t)
                padded = np.full(
                    (self._capacity, len(self.names)), np.nan, np.float32
                )
                padded[: row.shape[0]] = row
                drow = jax.device_put(
                    padded, fleet_sharding.values_sharding(self.mesh)
                )
                self._dvalues, self._dhist = self._feed_fn(
                    jnp.int32(self.t), drow, self._dhist, self._doffline
                )
            else:
                self._dvalues, self._dhist = self._tick_fn(
                    jnp.int32(self.t), self._dhist, self._doffline
                )
            self._hist_len = min(self._hist_len + 1, self._hist_cap)
            self._values_dirty = True
            self._hist_dirty = True

    def device_ring(self) -> jax.Array:
        """The donated ring on the device, `(history, capacity, n_signals)`."""
        return self._dhist

    def block_until_ready(self) -> None:
        """Wait for in-flight device work (benchmark fairness hook)."""
        jax.block_until_ready((self._dvalues, self._dhist))

    # -- reads: base logic over lazily synced mirrors --------------------- #
    def read(self, row: int, name: str) -> float | None:
        self._sync_values()
        return super().read(row, name)

    def window(self, row: int, name: str, k: int) -> list[float]:
        self._sync_hist()
        return super().window(row, name, k)

    def compute_sketches(self, name: str, spec, *, backend: str | None = None):
        """Fold the *device-resident* ring shards into per-client
        sketches: one `kernels.sketch.sketch_ring` call partitioned over
        the client axis (jit sharding propagation on the XLA twin,
        shard_map on the Pallas kernel). Only the `(spec.dim, capacity)`
        sketch block crosses device->host — the ring itself never does,
        and the lazy host mirror stays cold (`_hist_dirty` untouched)."""
        from repro.kernels import sketch as _sk

        col = self._col.get(name)
        n = self.n_clients
        if col is None or n == 0:
            return _sk.empty_fleet_sketches(spec, n)
        with obs.span("plane.fold"):
            out = np.asarray(_sk.sketch_ring(
                self._dhist, self.t, self._hist_len, col, spec,
                backend=backend, mesh=self.mesh,
            ))
        self.d2h_bytes += out.nbytes
        return _sk.sketches_from_device(spec, out[:, :n])

    def set_online(self, row: int, online: bool) -> None:
        super().set_online(row, online)
        self._mask_dirty = True  # uploaded once at the next step

    # -- fleet growth ------------------------------------------------------ #
    def _ensure_capacity(self, n: int) -> None:
        """Geometric growth, rounded up to a device-count multiple: the
        doubled layout is evenly divisible again, so the recompiled tick
        keeps whole rows per device and never reshards mid-stream."""
        if n <= self._capacity:
            return
        cap = max(n, int(math.ceil(self._capacity * self._growth)))
        cap = fleet_sharding.round_up_clients(cap, self.mesh)
        old_hist = self._dhist
        self._compile(cap)
        # row-stable generators: rows < n_clients come back unchanged
        self._dvalues = self._values_fn(jnp.int32(self.t))
        self._dhist = self._grow_ring_fn(old_hist, self._dvalues)
        offline = np.zeros(cap, bool)
        offline[: self._capacity] = self._offline
        self._offline = offline
        self._mask_dirty = True
        self._capacity = cap
        self._values_dirty = True
        self._hist_dirty = True

    def add_client(self) -> int:
        """A new vehicle joins: amortized O(1) jitted ring-column init
        within spare capacity; past capacity the arrays double (rounded to
        the device count). Returns the new row index."""
        if self._feed is not None:
            # match the host CSV plane: a fixed trace defines the fleet
            raise ValueError(
                "this plane has a fixed fleet size (CSV playback); "
                "construct it via a scenario to support add_client"
            )
        i = self.n_clients
        self._ensure_capacity(i + 1)
        self.n_clients = i + 1
        self._dhist = self._join_fn(
            self._dhist,
            self._dvalues,
            jnp.int32(i),
            jnp.int32(self.t % self._hist_cap),
        )
        self._offline[i] = False
        self._mask_dirty = True
        self._hist_dirty = True
        return i

    # -- unsupported host-plane construction paths ------------------------- #
    @classmethod
    def from_trace(cls, *args, **kwargs):
        raise NotImplementedError(
            "sharded planes are scenario-backed; materialized traces stay "
            "on the host plane (FleetSignalPlane.from_trace)"
        )

    @classmethod
    def from_csv_fleet(
        cls,
        csv_texts: Sequence[str],
        *,
        history: int = 256,
        mesh: Mesh | None = None,
    ) -> "ShardedSignalPlane":
        """CSV playback on the sharded layout, through the same
        constant-memory `CsvFleetStream` the host plane uses: each tick
        streams ONE `(n_vehicles, n_signals)` host row, pads it to the
        device-rounded capacity, and feeds the donated ring write — the
        full trace is never materialized on host or device. Reads are
        bit-for-bit with `FleetSignalPlane.from_csv_fleet` (the parity
        test in `tests/test_signal_plane.py` pins it)."""
        from repro.core.signals import CsvFleetStream

        stream = CsvFleetStream(csv_texts)
        n = len(csv_texts)
        names = stream.names
        row0 = np.array(stream.series(0), np.float32, copy=True)

        def step_builder(cap):
            first = np.full((cap, len(names)), np.nan, np.float32)
            first[:n] = row0
            const = jnp.asarray(first)

            def step(t):
                # only evaluated at construction (t=0): every later tick
                # is host-fed through `step()`'s feed branch
                return const

            return step

        plane = cls(names, n, step_builder, history=history, mesh=mesh)
        plane._feed = stream
        return plane
