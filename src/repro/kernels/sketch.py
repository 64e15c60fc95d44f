"""Fused windowed-sketch kernels over the fleet signal ring.

The streaming-analytics workload folds each vehicle's last-`W`
observations into a compact sketch — Welford moments, a fixed-bin
histogram, and a mergeable KLL-style quantile summary. The legacy path
(`ANALYTICS_PAYLOAD`) does that per vehicle in a sandboxed Python loop
after `get_signal_window` has synced the whole history ring
device→host. This module folds the **entire fleet at once, in place on
the ring's device shards**: one `(3 + bins + K, capacity)` f32 result
leaves the device, the ring never does.

Bit-for-bit parity with the per-vehicle Python fold is load-bearing
(the payload path stays the oracle), which dictates three non-obvious
choices:

* The Welford scan carries the *pending* product ``d * (v - mean)`` as
  a separate element and adds it one step late. A plain
  ``m2 + d * (v - mean)`` lets XLA:CPU/LLVM contract the multiply-add
  into a single-rounding FMA, which diverges from the numpy scalar
  loop in the sandbox; routing the product through the scan carry (a
  phi node) blocks the contraction. Verified exact over masked and
  unmasked trials.
* Histogram binning compares samples against precomputed f32 interior
  edges (``x >= edge_j`` counts) instead of dividing by the bin width —
  comparisons are exact, division is not. The edge formula lives in
  `SketchSpec.edges` and is shared with the payload text.
* The quantile summary is pure selection: K order statistics at
  integer ranks of the f32-sorted window, no arithmetic on samples, so
  device and numpy agree bitwise. Rank error after merging is bounded
  by ``total / (2K)`` (see `merge_quantile_sketches` in kernels.ops).

Dispatch follows kernels/ops.py: TPU → the Pallas kernel, anything
else → the jit'd `lax.scan` twin (or the Pallas kernel in interpret
mode for kernel-parity tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Shape of a windowed sketch. Frozen + hashable so planes can key
    their per-tick fleet-sketch cache on it."""

    window: int = 64
    bins: int = 16
    lo: float = 0.0
    hi: float = 12.0
    quantile_k: int = 32

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.bins < 1:
            raise ValueError(f"bins must be >= 1, got {self.bins}")
        if self.quantile_k < 1:
            raise ValueError(f"quantile_k must be >= 1, got {self.quantile_k}")

    @property
    def dim(self) -> int:
        """Rows of the fused device output: count, mean, m2, hist, quantiles."""
        return 3 + self.bins + self.quantile_k

    def edges(self) -> np.ndarray:
        """Interior bin edges, f32. Samples are binned by counting
        ``x >= edge_j`` — exact comparisons, matching the clip semantics
        of the original division-based binning (x < lo → bin 0,
        x >= hi → last bin, x == edge_j → bin j)."""
        width = (self.hi - self.lo) / self.bins
        return (self.lo + width * np.arange(1, self.bins)).astype(np.float32)


def sketch_reference(xs: Iterable[float], spec: SketchSpec) -> dict:
    """Per-vehicle numpy oracle: the exact fold `ANALYTICS_PAYLOAD` runs
    in the sandbox (f32 Welford, edge-comparison binning, integer-rank
    quantile selection). `compute_sketches` must match it bit-for-bit."""
    x = np.asarray(list(xs), dtype=np.float32)
    count = int(x.shape[0])
    c = np.float32(0.0)
    one = np.float32(1.0)
    mean = np.float32(0.0)
    m2 = np.float32(0.0)
    for v in x:
        c = c + one
        d = v - mean
        mean = mean + d / c
        m2 = m2 + d * (v - mean)
    edges = spec.edges()
    if count:
        idx = (x[:, None] >= edges[None, :]).sum(axis=1)
        hist = np.bincount(idx, minlength=spec.bins)
        xs_sorted = np.sort(x)
        K = spec.quantile_k
        ranks = np.minimum((2 * np.arange(K) + 1) * count // (2 * K), count - 1)
        qsk = [float(v) for v in xs_sorted[ranks]]
    else:
        hist = np.zeros((spec.bins,), np.int64)
        qsk = []
    return {
        "count": count,
        "mean": float(mean),
        "m2": float(m2),
        "hist": [int(v) for v in hist],
        "qsk": qsk,
    }


@dataclasses.dataclass(frozen=True)
class FleetSketches:
    """Host-side container for one fleet-wide sketch call."""

    spec: SketchSpec
    counts: np.ndarray  # (n,) int64
    means: np.ndarray   # (n,) f32
    m2s: np.ndarray     # (n,) f32
    hists: np.ndarray   # (n, bins) int64
    qvals: np.ndarray   # (n, quantile_k) f32; NaN rows where count == 0

    @property
    def n_clients(self) -> int:
        return int(self.counts.shape[0])

    def row(self, i: int) -> dict:
        """Payload-shaped dict for vehicle `i` — bit-identical to
        `sketch_reference` over that vehicle's window."""
        c = int(self.counts[i])
        return {
            "count": c,
            "mean": float(self.means[i]),
            "m2": float(self.m2s[i]),
            "hist": [int(v) for v in self.hists[i]],
            "qsk": [] if c == 0 else [float(v) for v in self.qvals[i]],
        }


def sketches_from_device(spec: SketchSpec, out: np.ndarray) -> FleetSketches:
    """Split the fused `(3 + bins + K, n)` device result into typed
    host arrays (counts/hists exact as integers: both are bounded by
    the window length, far inside f32's 2^24 integer range)."""
    nb = spec.bins
    return FleetSketches(
        spec=spec,
        counts=out[0].astype(np.int64),
        means=out[1].copy(),
        m2s=out[2].copy(),
        hists=out[3 : 3 + nb].T.astype(np.int64),
        qvals=out[3 + nb :].T.copy(),
    )


def empty_fleet_sketches(spec: SketchSpec, n: int) -> FleetSketches:
    """Zero-sample sketches for `n` vehicles (unknown signal / empty
    fleet) — `row()` matches `sketch_reference([], spec)`."""
    return FleetSketches(
        spec=spec,
        counts=np.zeros((n,), np.int64),
        means=np.zeros((n,), np.float32),
        m2s=np.zeros((n,), np.float32),
        hists=np.zeros((n, spec.bins), np.int64),
        qvals=np.full((n, spec.quantile_k), np.nan, np.float32),
    )


# --------------------------------------------------------------------- #
# shared fold pieces (identical math in the XLA twin and the kernel)    #
# --------------------------------------------------------------------- #
def _welford_update(carry, v, ok):
    """One masked Welford step with the FMA-blocking pending product."""
    c, m, m2, pend = carry
    m2n = m2 + pend
    cn = c + 1.0
    d = v - m
    mn = m + d / cn
    pn = d * (v - mn)
    return (
        jnp.where(ok, cn, c),
        jnp.where(ok, mn, m),
        jnp.where(ok, m2n, m2),
        jnp.where(ok, pn, pend),
    )


def _edge_hist(x, valid, c, edges):
    """(bins, n) f32 counts from >=-edge comparisons. Exact: counts are
    bounded by the window length."""
    if edges.shape[0] == 0:
        return c[None]
    ge = jnp.where(
        valid[:, None, :] & (x[:, None, :] >= edges[None, :, None]), 1.0, 0.0
    )
    cum = jnp.sum(ge, axis=0)  # (bins-1, n) — count of samples >= each edge
    return jnp.concatenate([c[None] - cum[:1], cum[:-1] - cum[1:], cum[-1:]], axis=0)


def _quantile_ranks(kc, quantile_k):
    """(K, n) int32 ranks: midpoints of K equal-weight blocks, clipped."""
    j = jax.lax.broadcasted_iota(jnp.int32, (quantile_k, 1), 0)
    return jnp.clip(
        ((2 * j + 1) * kc[None, :]) // (2 * quantile_k),
        0,
        jnp.maximum(kc[None, :] - 1, 0),
    )


def _window_block(ring, t, hist_len, col, window):
    """Gather column `col`'s last-`window` ring slots, oldest first, as a
    (W, capacity) block. Positions older than the recorded history are
    NaN, exactly reproducing `FleetSignalPlane.window`'s
    ``k = min(k_requested, hist_len)`` truncation; offline ticks are
    already NaN in the ring itself."""
    hist_cap = ring.shape[0]
    W = min(int(window), hist_cap)
    i = jnp.arange(W, dtype=jnp.int32)
    slots = (t - W + 1 + i) % hist_cap  # jnp % is floor-mod: non-negative
    x = ring[slots, :, col]  # (W, capacity)
    k = jnp.minimum(W, hist_len)
    return jnp.where((i < W - k)[:, None], jnp.nan, x)


# --------------------------------------------------------------------- #
# XLA twin: jit'd lax.scan fold                                         #
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("quantile_k",))
def _fold_xla(x, edges, *, quantile_k):
    """(W, n) time-ordered window (NaN = not observed) -> (dim, n) f32."""
    n = x.shape[1]
    valid = jnp.logical_not(jnp.isnan(x))
    xz = jnp.where(valid, x, 0.0)

    def step(carry, vo):
        v, ok = vo
        return _welford_update(carry, v, ok), None

    zeros = jnp.zeros((n,), jnp.float32)
    (c, m, m2, pend), _ = jax.lax.scan(step, (zeros, zeros, zeros, zeros), (xz, valid))
    m2 = m2 + pend

    hist = _edge_hist(x, valid, c, edges)

    kc = c.astype(jnp.int32)
    idx = _quantile_ranks(kc, quantile_k)
    xs_sorted = jnp.sort(x, axis=0)  # NaNs sort last, matching numpy
    qv = jnp.take_along_axis(xs_sorted, idx, axis=0)
    qv = jnp.where(kc[None, :] > 0, qv, jnp.nan)
    return jnp.concatenate([c[None], m[None], m2[None], hist, qv], axis=0)


@functools.partial(jax.jit, static_argnames=("col", "window", "quantile_k"))
def _ring_sketch_xla(ring, t, hist_len, edges, *, col, window, quantile_k):
    """Fused gather + fold so the ring is consumed where it lives; on a
    sharded ring GSPMD propagates the client-axis sharding through every
    op (all are per-client elementwise/columnwise)."""
    x = _window_block(ring, t, hist_len, col, window)
    return _fold_xla(x, edges, quantile_k=quantile_k)


# --------------------------------------------------------------------- #
# Pallas kernel: one client block per grid step                         #
# --------------------------------------------------------------------- #
def _sketch_kernel(x_ref, xs_ref, e_ref, o_ref, *, quantile_k: int, n_bins: int):
    X = x_ref[...]   # (W, bn) time-ordered window block
    Xs = xs_ref[...]  # (W, bn) same block, sorted along the window axis
    W, bn = X.shape
    valid = jnp.logical_not(jnp.isnan(X))

    def body(s, carry):
        # Row s is read from the ref: Mosaic lowers a dynamic ref slice
        # but not a dynamic_slice of a loaded value.
        x = x_ref[pl.ds(s, 1), :][0]
        ok = jnp.logical_not(jnp.isnan(x))
        return _welford_update(carry, jnp.where(ok, x, 0.0), ok)

    zeros = jnp.zeros((bn,), jnp.float32)
    c, m, m2, pend = jax.lax.fori_loop(0, W, body, (zeros, zeros, zeros, zeros))
    m2 = m2 + pend

    edges = e_ref[0, : n_bins - 1] if n_bins > 1 else e_ref[0, :0]
    hist = _edge_hist(X, valid, c, edges)

    kc = c.astype(jnp.int32)
    idx = _quantile_ranks(kc, quantile_k)  # (K, bn)
    # One-hot selection of the ranked order statistics. `where` rather
    # than multiply: Xs holds NaN pad lanes and NaN * 0 = NaN.
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, W, 1), 1)
    sel = idx[:, None, :] == pos
    qv = jnp.sum(jnp.where(sel, Xs[None, :, :], 0.0), axis=1)
    qv = jnp.where(kc[None, :] > 0, qv, jnp.nan)
    o_ref[...] = jnp.concatenate([c[None], m[None], m2[None], hist, qv], axis=0)


@functools.partial(
    jax.jit, static_argnames=("quantile_k", "n_bins", "block_clients", "interpret")
)
def _fold_pallas(x, edges, *, quantile_k, n_bins, block_clients, interpret):
    """(W, n) window -> (dim, n) sketches via the Pallas kernel, one
    128-client block per grid step. Clients are padded to a block
    multiple with NaN columns (folded as count-0 rows, sliced off)."""
    W, n = x.shape
    bn = min(block_clients, max(n, 1))
    pad = (-n) % bn
    if pad:
        fill = jnp.full((W, pad), jnp.nan, x.dtype)
        x = jnp.concatenate([x, fill], axis=1)
    xs = jnp.sort(x, axis=0)
    # 2-D edges block (TPU tiles want >= 2-D refs); width-1 dummy when
    # there are no interior edges so the BlockSpec stays non-empty.
    ew = max(1, n_bins - 1)
    e2 = jnp.zeros((1, ew), jnp.float32)
    if n_bins > 1:
        e2 = e2.at[0, :].set(edges)
    dim = 3 + n_bins + quantile_k
    out = pl.pallas_call(
        functools.partial(_sketch_kernel, quantile_k=quantile_k, n_bins=n_bins),
        grid=((n + pad) // bn,),
        in_specs=[
            pl.BlockSpec((W, bn), lambda i: (0, i)),
            pl.BlockSpec((W, bn), lambda i: (0, i)),
            pl.BlockSpec((1, ew), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((dim, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((dim, n + pad), jnp.float32),
        interpret=interpret,
    )(x, xs, e2)
    return out[:, :n]


#: Programs built for the Pallas ring fold: incremented by
#: `_ring_sketch_pallas`'s Python body, which runs only while jit traces
#: it, so it counts builds (one per static key and shape), not calls.
fold_traces = 0


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "col", "window", "quantile_k", "n_bins", "interpret"),
)
def _ring_sketch_pallas(
    ring, t, hist_len, edges, *, mesh, col, window, quantile_k, n_bins, interpret
):
    """Fused gather + Pallas fold, one program per static key. With a
    mesh the fold is shard_mapped over the client axis: each device
    folds only its own client columns."""
    global fold_traces
    fold_traces += 1
    x = _window_block(ring, t, hist_len, col, window)
    fold = functools.partial(
        _fold_pallas,
        quantile_k=quantile_k,
        n_bins=n_bins,
        block_clients=128,
        interpret=interpret,
    )
    if mesh is not None:
        axis = mesh.axis_names[0]
        fold = jax.shard_map(
            fold,
            mesh=mesh,
            in_specs=(P(None, axis), P(None)),
            out_specs=P(None, axis),
            check_vma=False,  # no varying-axes rule for pallas_call
        )
    return fold(x, edges)


# --------------------------------------------------------------------- #
# dispatch                                                              #
# --------------------------------------------------------------------- #
def fold_window(x, spec: SketchSpec, *, backend: str | None = None):
    """Fold a (W, n) time-ordered window matrix (NaN = not observed)
    into `(spec.dim, n)` f32 sketches. Kernel-level entry used by the
    parity tests and benchmarks; the planes go through `sketch_ring`."""
    if backend is None:
        backend = "pallas" if _on_tpu() else "xla"
    x = jnp.asarray(x, jnp.float32)
    edges = jnp.asarray(spec.edges())
    if backend == "xla":
        return _fold_xla(x, edges, quantile_k=spec.quantile_k)
    if backend != "pallas":
        raise ValueError(f"unknown sketch backend {backend!r}")
    return _fold_pallas(
        x,
        edges,
        quantile_k=spec.quantile_k,
        n_bins=spec.bins,
        block_clients=128,
        interpret=not _on_tpu(),
    )


def sketch_ring(
    ring,
    t: int,
    hist_len: int,
    col: int,
    spec: SketchSpec,
    *,
    backend: str | None = None,
    mesh=None,
):
    """Fold column `col`'s last-`spec.window` ring slots into per-client
    sketches, in place where the ring lives. Returns the fused
    `(spec.dim, capacity)` f32 device array — the only thing that
    crosses device→host on the analytics path."""
    if backend is None:
        backend = "pallas" if _on_tpu() else "xla"
    edges = jnp.asarray(spec.edges())
    t = jnp.int32(t)
    hist_len = jnp.int32(hist_len)
    if backend == "xla":
        return _ring_sketch_xla(
            ring, t, hist_len, edges,
            col=col, window=spec.window, quantile_k=spec.quantile_k,
        )
    if backend != "pallas":
        raise ValueError(f"unknown sketch backend {backend!r}")
    return _ring_sketch_pallas(
        ring, t, hist_len, edges,
        mesh=mesh, col=col, window=spec.window, quantile_k=spec.quantile_k,
        n_bins=spec.bins, interpret=not _on_tpu(),
    )
