"""Result/gradient compression for the slow edge (paper §3.4 network
budget, adapted to distributed learning — see DESIGN.md §2).

* int8 symmetric quantization with per-row scales (Pallas kernel on TPU,
  interpret/jnp elsewhere) — 4x over f32, ~2x over bf16;
* top-k sparsification — transmit the k largest-magnitude entries;
* error feedback (Seide et al. / Karimireddy et al.): the compression
  residual is accumulated locally and added before the next compression,
  which keeps SGD convergent under aggressive compression.

Everything operates on flat f32 vectors; `flatten_pytree`/`unflatten`
adapt parameter pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import dequantize_int8_ref, quantize_int8_ref


# --------------------------------------------------------------------- #
# pytree <-> flat vector                                                 #
# --------------------------------------------------------------------- #
def flatten_pytree(tree: Any) -> tuple[jax.Array, Any, list]:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [(l.shape, l.dtype) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat, treedef, shapes


def unflatten_pytree(flat: jax.Array, treedef: Any, shapes: list) -> Any:
    leaves = []
    off = 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, leaves)


# --------------------------------------------------------------------- #
# codecs                                                                 #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Int8Codec:
    """Per-chunk-of-`row` int8 quantization."""

    row: int = 4096

    def encode(self, flat: jax.Array) -> dict[str, Any]:
        n = flat.shape[0]
        pad = (-n) % self.row
        x = jnp.pad(flat, (0, pad)).reshape(-1, self.row)
        q, s = quantize_int8_ref(x)
        return {"kind": "int8", "q": q, "s": s[:, 0], "n": n}

    def decode(self, msg: dict[str, Any]) -> jax.Array:
        x = dequantize_int8_ref(msg["q"], msg["s"][:, None])
        return x.reshape(-1)[: msg["n"]]

    def nbytes(self, msg: dict[str, Any]) -> int:
        return int(msg["q"].size + msg["s"].size * 4)


@dataclasses.dataclass(frozen=True)
class TopKCodec:
    """Keep the k largest-magnitude entries (indices + values)."""

    fraction: float = 0.01

    def encode(self, flat: jax.Array) -> dict[str, Any]:
        n = flat.shape[0]
        k = max(1, int(n * self.fraction))
        vals, idx = jax.lax.top_k(jnp.abs(flat), k)
        return {
            "kind": "topk",
            "idx": idx.astype(jnp.int32),
            "val": flat[idx],
            "n": n,
        }

    def decode(self, msg: dict[str, Any]) -> jax.Array:
        out = jnp.zeros((msg["n"],), jnp.float32)
        return out.at[msg["idx"]].set(msg["val"])

    def nbytes(self, msg: dict[str, Any]) -> int:
        return int(msg["idx"].size * 4 + msg["val"].size * 4)


@dataclasses.dataclass(frozen=True)
class NullCodec:
    def encode(self, flat: jax.Array) -> dict[str, Any]:
        return {"kind": "raw", "val": flat}

    def decode(self, msg: dict[str, Any]) -> jax.Array:
        return msg["val"]

    def nbytes(self, msg: dict[str, Any]) -> int:
        return int(msg["val"].size * 4)


def make_codec(name: str, **kw) -> Any:
    return {"int8": Int8Codec, "topk": TopKCodec, "none": NullCodec}[name](**kw)


# --------------------------------------------------------------------- #
# batched aggregation (the fleet-scale hot path)                         #
# --------------------------------------------------------------------- #
#: full f32 contraction: at default precision the TPU rounds the f32
#: per-row scales to bf16 before the matmul, which misses the per-client
#: reference (`rounds.aggregate_reference`) by far more than 1e-6
_F32 = jax.lax.Precision.HIGHEST


@jax.jit
def _dequant_weighted_sum(
    q: jax.Array,  # (N, R, row) int8 — all clients' packed deltas, stacked
    s: jax.Array,  # (N, R) f32 per-row scales
    w: jax.Array,  # (N,) f32 normalized aggregation weights
) -> jax.Array:
    """One fused dequantize + weighted-sum over the client axis.

    Algebraically this is `vmap(dequantize_int8_ref)` over clients followed
    by a weighted sum, but folding the aggregation weight into each
    client's dequant scales first (`w_n * s_{nr}`) turns the whole FedAvg
    server step into a single einsum contraction over the client axis —
    XLA fuses the int8->f32 cast straight into the reduction and never
    materializes the (N, R, row) f32 dequantized tensor."""
    ws = w[:, None] * s
    return jnp.einsum(
        "nr,nrc->rc", ws, q.astype(jnp.float32), precision=_F32
    )


@jax.jit
def _dequant_mean_uniform(q: jax.Array, s: jax.Array) -> jax.Array:
    """Unweighted FedAvg mean: the 1/N weight is a compile-time scalar, so
    no weight vector is built or transferred per round."""
    out = jnp.einsum("nr,nrc->rc", s, q.astype(jnp.float32), precision=_F32)
    return out / q.shape[0]


def batched_dequant_mean(
    q: np.ndarray | jax.Array,
    s: np.ndarray | jax.Array,
    weights: np.ndarray | None = None,
) -> np.ndarray:
    """Weighted mean of N packed int8 deltas, computed in one batched op.

    `q` is (N, R, row) int8, `s` is (N, R) f32. Returns the (R, row) f32
    mean delta. Replaces the per-client unpack-then-accumulate Python loop
    (see `repro.fleet.rounds.aggregate_reference` for the reference)."""
    if weights is None:
        out = _dequant_mean_uniform(q, s)
    else:
        w = np.asarray(weights, np.float32)
        out = _dequant_weighted_sum(q, s, w / w.sum())
    return np.asarray(jax.block_until_ready(out))


# --------------------------------------------------------------------- #
# error feedback                                                         #
# --------------------------------------------------------------------- #
class ErrorFeedback:
    """Stateful compressor: residual accumulation per client."""

    def __init__(self, codec: Any):
        self.codec = codec
        self._residual: jax.Array | None = None
        self.bytes_sent = 0
        self.bytes_raw = 0

    def compress(self, flat: jax.Array) -> dict[str, Any]:
        if self._residual is not None:
            flat = flat + self._residual
        msg = self.codec.encode(flat)
        decoded = self.codec.decode(msg)
        self._residual = flat - decoded
        self.bytes_sent += self.codec.nbytes(msg)
        self.bytes_raw += int(flat.size * 4)
        return msg

    @property
    def compression_ratio(self) -> float:
        return self.bytes_raw / max(1, self.bytes_sent)
