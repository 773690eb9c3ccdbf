"""Sharding rules: rank consistency + production-mesh divisibility for every
full-size config (pure spec math, no 512 devices needed)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_config, get_smoke_config
from repro.models import init, init_cache

FULL_ARCHS = [a for a in ARCHITECTURES if a != "gpt2-paper"]


class FakeMesh:
    """Just enough Mesh interface for spec derivation (axis_names/shape)."""

    def __init__(self, shape_by_axis):
        self.axis_names = tuple(shape_by_axis)
        self.shape = dict(shape_by_axis)


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for e in entry:
            n *= mesh.shape[e]
        return n
    return mesh.shape[entry]


@pytest.mark.parametrize("arch", FULL_ARCHS)
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single_pod", "multi_pod"])
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    specs = sh.param_specs(shapes, mesh)

    def check(path, leaf, spec):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (path, leaf.shape, spec)
        for dim, entry in zip(leaf.shape, tuple(spec)):
            size = _axis_size(mesh, entry)
            assert dim % size == 0, (
                f"{arch}: {jax.tree_util.keystr(path)} dim {dim} not divisible "
                f"by {size} ({entry})"
            )

    jax.tree_util.tree_map_with_path(check, shapes, specs)


@pytest.mark.parametrize("arch", ["command-r-35b", "jamba-1.5-large-398b", "mamba2-130m"])
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_specs_divisible(arch, shape_name):
    from repro.launch.policy import window_for

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    w = window_for(cfg, shape)
    cache_shape = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len, window=w)
    )
    shardable = shape.global_batch % 16 == 0
    specs = sh.cache_specs(cache_shape, SINGLE, batch_shardable=shardable)

    def check(path, leaf, spec):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            size = _axis_size(SINGLE, entry)
            assert dim % size == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, cache_shape, specs)


def test_batch_axes_by_mesh():
    assert sh.batch_axes(SINGLE) == ("data",)
    assert sh.batch_axes(MULTI) == ("pod", "data")


def test_cohort_mesh_covers_all_devices():
    """The federated engines' cohort placement: a 1-D mesh over every
    addressable device under the shared COHORT_AXIS name (the axis contract
    of the fused/fused-e2e shard_map placements)."""
    mesh = sh.cohort_mesh()
    assert mesh.axis_names == (sh.COHORT_AXIS,)
    assert mesh.shape[sh.COHORT_AXIS] == jax.device_count()
    from repro.launch.mesh import make_client_mesh

    assert make_client_mesh().shape == mesh.shape


def test_embed_is_vocab_sharded():
    cfg = get_config("command-r-35b")
    shapes = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    specs = sh.param_specs(shapes, SINGLE)
    assert tuple(specs["embed"])[0] == "model"  # 256k vocab split 16 ways


def test_moe_experts_sharded():
    cfg = get_config("moonlight-16b-a3b")
    shapes = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    specs = sh.param_specs(shapes, SINGLE)
    up_spec = specs["stack"]["pos0"]["mlp"]["up"]
    assert tuple(up_spec)[:2] == (None, "model")  # (layer-stack, experts, ...)


@pytest.mark.parametrize("arch", FULL_ARCHS)
def test_smoke_configs_are_reduced(arch):
    smoke = get_smoke_config(arch)
    assert smoke.num_layers <= 4
    assert smoke.d_model <= 512
    if smoke.moe:
        assert smoke.moe.num_experts <= 8
