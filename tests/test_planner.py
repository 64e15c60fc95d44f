"""Sharding planner rules on the production mesh shape (AbstractMesh:
no devices needed — specs are pure metadata)."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.models.model import cache_spec, init_params
from repro.sharding import planner

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def shapes_of(arch):
    cfg = get_config(arch)
    return cfg, jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
    )


def spec_map(tree):
    return planner.describe(tree)


def test_divisible_heads_shard_on_model():
    cfg, shapes = shapes_of("granite-8b")
    sh = planner.param_shardings(cfg, shapes, MESH, fsdp=False)
    m = spec_map(sh)
    assert "'model'" in m["groups/0/0/mixer/wq"].replace('"', "'")
    # kv heads = 8 < 16 -> replicated
    assert "model" not in m["groups/0/0/mixer/wk"]
    assert "data" not in m["groups/0/0/mixer/wk"]


def test_gemma3_four_heads_fall_back_to_replicated():
    cfg, shapes = shapes_of("gemma3-1b")
    sh = planner.param_shardings(cfg, shapes, MESH, fsdp=False)
    m = spec_map(sh)
    assert "model" not in m["groups/0/0/mixer/wq"]
    # but the 262k vocab shards
    assert "model" in m["embed"]
    # and the MLP shards
    assert "model" in m["groups/0/0/ffn/w_gate"]


def test_mixtral_8_experts_use_tp_within_expert():
    cfg, shapes = shapes_of("mixtral-8x22b")
    sh = planner.param_shardings(cfg, shapes, MESH, fsdp=False)
    m = spec_map(sh)
    # 8 % 16 != 0: expert dim unsharded, f sharded on model
    assert m["groups/0/0/ffn/w_gate"] == "PartitionSpec(None, None, None, 'model')"


def test_jamba_16_experts_use_expert_parallelism():
    cfg, shapes = shapes_of("jamba-1.5-large-398b")
    sh = planner.param_shardings(cfg, shapes, MESH, fsdp=False)
    m = spec_map(sh)
    assert m["groups/0/1/ffn/w_gate"].startswith(
        "PartitionSpec(None, 'model'"
    )


def test_fsdp_adds_data_axis_for_big_models():
    cfg, shapes = shapes_of("mixtral-8x22b")
    sh = planner.param_shardings(cfg, shapes, MESH)  # auto => fsdp on (141B)
    m = spec_map(sh)
    assert "data" in m["groups/0/0/ffn/w_gate"]


def test_every_spec_divides_its_dimension():
    """No spec may assign an axis that does not divide the dim — for every
    arch, every param, every mesh."""
    for arch in ("jamba-1.5-large-398b", "gemma3-1b", "mixtral-8x22b",
                 "granite-moe-1b-a400m", "xlstm-1.3b", "musicgen-large",
                 "h2o-danube-3-4b"):
        cfg, shapes = shapes_of(arch)
        for mesh in (MESH, MESH_MP):
            for serve in (False, True):
                sh = planner.param_shardings(
                    cfg, shapes, mesh, serve=serve
                )
                _assert_divisible(shapes, sh, mesh, arch)


def _assert_divisible(shapes, shardings, mesh, tag):
    for leaf, s in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(shardings, is_leaf=lambda x: hasattr(x, "spec")),
    ):
        for dim, axes in enumerate(s.spec):
            if axes is None:
                continue
            n = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                n *= mesh.shape[a]
            assert leaf.shape[dim] % n == 0, (tag, leaf.shape, s.spec)


def test_cache_specs_divide_and_cover():
    for arch, shape_seq, B in (
        ("granite-8b", 32768, 128),
        ("jamba-1.5-large-398b", 524288, 1),
        ("xlstm-1.3b", 524288, 1),
        ("mixtral-8x22b", 32768, 128),
    ):
        cfg = get_config(arch)
        cs = cache_spec(cfg, B, shape_seq)
        sh = planner.cache_shardings(cfg, cs, MESH)
        _assert_divisible(cs, sh, MESH, arch)


def test_batch_sharding_uses_pod_and_data():
    b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    sh = planner.batch_shardings(b, MESH_MP)
    assert sh["tokens"].spec == P(("pod", "data"), None)
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    sh1 = planner.batch_shardings(b1, MESH_MP)
    assert sh1["tokens"].spec == P(None, None)
