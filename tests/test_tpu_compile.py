"""Ahead-of-time compiles of the fleet path's device programs for a
described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts (unsupported Mosaic
primitives, unaligned slices, programs that do not fit device memory),
so these compiles guard the sketch kernel and Linear Road's toll step at
their serving widths on every test run. The topology is described inside a module-scoped fixture, never
at import: only one process at a time may load the TPU library, and the
tests of this file must be the same on every pytest-xdist worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.fleet import linear_road as lr
from repro.kernels import sketch as sk
from repro.sharding.fleet import CLIENT_AXIS, replicated, ring_sharding

SPEC = sk.SketchSpec()  # window 64, 16 bins, K=32: the gateway default
#: the serving ring: 256 history slots x 100k vehicles (rounded up to a
#: 128-client block multiple) x the mixed scenario's 4 signals
HISTORY, CLIENTS, SIGNALS = 256, 102_400, 4
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-device compiles are written to a persistent cache but can
    # never be read back without a chip: keep the cache out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(topo.devices[:4], (CLIENT_AXIS,))


def _edges(sharding):
    return jax.ShapeDtypeStruct((SPEC.bins - 1,), jnp.float32, sharding=sharding)


@pytest.mark.parametrize("window,clients", [(64, 4096), (256, CLIENTS)])
def test_pallas_fold_compiles_for_one_chip(one_chip, window, clients):
    x = jax.ShapeDtypeStruct((window, clients), jnp.float32, sharding=one_chip)
    compiled = sk._fold_pallas.lower(
        x, _edges(one_chip),
        quantile_k=SPEC.quantile_k, n_bins=SPEC.bins,
        block_clients=128, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_pallas_fold_compiles_for_four_chips(four_chips):
    # the program `sketch_ring` runs for a plane on this mesh: the ring
    # gather and the shard_mapped kernel, fused into one jit
    ring = jax.ShapeDtypeStruct(
        (HISTORY, CLIENTS, SIGNALS), jnp.float32,
        sharding=ring_sharding(four_chips),
    )
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated(four_chips))
    edges = _edges(replicated(four_chips))
    text = sk._ring_sketch_pallas.lower(
        ring, scalar, scalar, edges,
        mesh=four_chips, col=0, window=SPEC.window,
        quantile_k=SPEC.quantile_k, n_bins=SPEC.bins, interpret=False,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    # each chip folds its own client columns: nothing crosses chips
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert op not in text


def test_ring_sketch_twin_fits_one_chip(one_chip):
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ring = jax.ShapeDtypeStruct(
        (HISTORY, CLIENTS, SIGNALS), jnp.float32, sharding=one_chip
    )
    compiled = sk._ring_sketch_xla.lower(
        ring, scalar, scalar, _edges(one_chip),
        col=0, window=SPEC.window, quantile_k=SPEC.quantile_k,
    ).compile()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert mem.argument_size_in_bytes >= HISTORY * CLIENTS * SIGNALS * 4
    assert used < V5E_HBM_BYTES


#: Linear Road at L = 2: 102,000 vehicles (a multiple of 4 chips), the
#: rules' 12 ring slots, the scenario's 6 columns
LR_CLIENTS, LR_XWAYS = 102_000, 2


@pytest.mark.parametrize("chips", [1, 4])
def test_toll_step_compiles(topo, chips):
    mesh = Mesh(topo.devices[:chips], (CLIENT_AXIS,))
    ring = jax.ShapeDtypeStruct((lr.HISTORY, LR_CLIENTS, len(lr.COLUMNS)), jnp.float32,
                                sharding=ring_sharding(mesh))
    sh = lr.state_shardings(mesh)
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
             for k, v in lr.initial_state(LR_CLIENTS).items()}
    tick = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated(mesh))
    compiled = jax.jit(
        lr.toll_step, static_argnames="expressways", out_shardings=sh, donate_argnums=(0,),
    ).lower(state, ring, tick, expressways=LR_XWAYS).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= LR_CLIENTS * (lr.HISTORY * len(lr.COLUMNS) + 9) * 4 // chips
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES // 16
    # on a mesh the grouped sums are reduced across the client shards
    assert ("all-reduce" in compiled.as_text()) == (chips > 1)
