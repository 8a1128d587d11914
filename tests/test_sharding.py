"""Partition rules: specs must be valid (divisible), big weights must be
sharded, small/norm leaves replicated, caches laid out sanely."""
import jax
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ASSIGNED, get_config
from repro.launch import specs as S
from repro.sharding import partition


def _mesh(shape=(4, 4), axes=("data", "model")):
    # an abstract stand-in is enough for spec derivation; use real devices=1
    devs = np.array(jax.devices() * (np.prod(shape) // len(jax.devices())
                                     + 1))[: np.prod(shape)]
    return jax.sharding.Mesh(devs.reshape(shape), axes)


MESH = _mesh()


def _check_divisible(tree, specs, mesh):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    sflat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )[0]
    bad = []
    for (kp, leaf), (_, spec) in zip(flat, sflat):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            size = partition.mesh_axis_size(mesh, ax)
            if dim % size:
                bad.append((jax.tree_util.keystr(kp), leaf.shape, spec))
    assert not bad, bad


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    shapes = S.params_specs(cfg)
    specs = partition.param_pspecs(shapes, MESH)
    _check_divisible(shapes, specs, MESH)


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "dbrx-132b",
                                  "zamba2-2.7b", "rwkv6-1.6b"])
def test_big_weights_are_sharded(arch):
    """No multi-MB weight may end up fully replicated (the w_up bug class)."""
    cfg = get_config(arch)
    shapes = S.params_specs(cfg)
    specs = partition.param_pspecs(shapes, MESH)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    sflat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )[0]
    offenders = []
    for (kp, leaf), (_, spec) in zip(flat, sflat):
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if nbytes > 64 * 2**20 and all(a is None for a in tuple(spec)):
            offenders.append((jax.tree_util.keystr(kp), leaf.shape))
    assert not offenders, offenders


def test_moe_experts_on_model_axis():
    cfg = get_config("dbrx-132b")
    shapes = S.params_specs(cfg)
    specs = partition.param_pspecs(shapes, MESH)
    moe = specs["layers"]["moe"]
    assert tuple(moe["w_gate"])[1] == "model"   # (L, E, D, F): EP
    assert tuple(moe["w_down"])[1] == "model"


def test_row_parallel_projections():
    cfg = get_config("granite-3-2b")
    shapes = S.params_specs(cfg)
    specs = partition.param_pspecs(shapes, MESH)
    assert tuple(specs["layers"]["mlp"]["w_down"])[1] == "model"
    assert tuple(specs["layers"]["attn"]["wo"])[1] == "model"
    # column-parallel counterparts
    assert tuple(specs["layers"]["mlp"]["w_up"])[-1] == "model"
    assert tuple(specs["layers"]["attn"]["wq"])[-1] == "model"


def test_norms_replicated():
    cfg = get_config("granite-3-2b")
    shapes = S.params_specs(cfg)
    specs = partition.param_pspecs(shapes, MESH)
    assert all(a is None for a in tuple(specs["final_norm"]["w"]))
    assert all(a is None for a in tuple(specs["layers"]["ln1"]["w"]))


def test_batch_specs_and_fallback():
    cfg = get_config("granite-3-2b")
    b = S.train_batch_specs(cfg, SHAPES["train_4k"])
    specs = partition.batch_pspecs(b, MESH)
    assert tuple(specs["tokens"])[0] in ("data", ("data",))  # P() normalizes
    # batch=1 long_500k: replicate instead of crashing
    b1 = S.decode_batch_specs(cfg, SHAPES["long_500k"])
    specs1 = partition.batch_pspecs(b1, MESH)
    assert tuple(specs1["tokens"])[0] is None


@pytest.mark.parametrize("arch", ["granite-3-2b", "zamba2-2.7b",
                                  "whisper-small", "rwkv6-1.6b"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    shape = SHAPES["decode_32k"]
    cache = S.cache_specs(cfg, shape)
    specs = partition.cache_pspecs(cache, MESH)
    _check_divisible(cache, specs, MESH)


def test_pod_axis_composes():
    mesh3 = _mesh((2, 2, 4), ("pod", "data", "model"))
    cfg = get_config("granite-3-2b")
    b = S.train_batch_specs(cfg, SHAPES["train_4k"])
    specs = partition.batch_pspecs(b, mesh3)
    assert tuple(specs["tokens"])[0] == ("pod", "data")
    assert partition.mesh_axis_size(mesh3, ("pod", "data")) == 4


def test_sparse_pack_pspecs_shard_packed_rows():
    """Pack-group device arrays: packed-row dim -> 'model' when divisible
    (devices as banks), perms replicated, layer/chunk dims never split."""
    from repro.core.sparse_model import sparsify_model
    from repro.models import factory

    cfg = get_config("llama7b-espim", reduced=True)
    params = factory.init_params(cfg, jax.random.PRNGKey(0))
    sparse = sparsify_model(cfg, params, 0.9, projections="all",
                            row_tile=32)
    specs = partition.sparse_pack_pspecs(sparse, MESH)
    assert set(specs) == set(sparse["groups"])
    for name, g in sparse["groups"].items():
        gs = specs[name]
        assert gs["perm"] == jax.sharding.PartitionSpec(None, None)
        assert len(gs["buckets"]) == len(g["buckets"])
        for b, bs in zip(g["buckets"], gs["buckets"]):
            for key, spec in bs.items():
                arr = b[key]
                assert len(spec) == arr.ndim
                assert spec[0] is None          # layer-stack dim: the scan
                row_ax = spec[-1]               # packed rows: the lane dim
                assert row_ax in (None, "model")
                if row_ax == "model":
                    assert arr.shape[-1] % partition.mesh_axis_size(
                        MESH, "model") == 0
                assert all(a is None for a in spec[1:-1])  # chunk/slot dims
    # quantized packs: srow scales shard with their rows
    sq = sparsify_model(cfg, params, 0.9, projections="mlp", row_tile=32,
                        quant="int8")
    qspecs = partition.sparse_pack_pspecs(sq, MESH)
    for name, g in sq["groups"].items():
        for b, bs in zip(g["buckets"], qspecs[name]["buckets"]):
            assert set(bs) == {"q", "cols", "srow"}
