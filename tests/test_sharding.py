"""Sharding policy: every spec divides its dimension on the production mesh.

Uses AbstractMesh — no devices needed, so this runs in the normal 1-device
test process (the real 512-device lowering is the dry-run's job).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES
from repro.launch.sharding import batch_specs, cache_specs, param_specs
from repro.launch.steps import abstract_cache, abstract_params, input_specs
from repro.models import Model

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shapes, specs, mesh, where):
    flat_sh = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_sh) == len(flat_sp)
    for (path, leaf), spec in zip(flat_sh, flat_sp):
        dims = tuple(leaf.shape)
        parts = tuple(spec) + (None,) * (len(dims) - len(spec))
        for dim, part in zip(dims, parts):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            ways = 1
            for a in axes:
                ways *= mesh.shape[a]
            assert dim % ways == 0, (where, path, dims, spec)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["pod1", "pod2"])
def test_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    model = Model(cfg)
    p_shape = jax.eval_shape(model.init, jax.random.key(0))
    specs = param_specs(cfg, p_shape, mesh)
    _check_divisible(p_shape, specs, mesh, arch)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_batch_specs_divide(arch, shape_name):
    from repro.configs.shapes import shape_applicable

    if not shape_applicable(arch, shape_name)[0]:
        pytest.skip("shape not applicable")
    cfg = get_config(arch)
    b = input_specs(cfg, SHAPES[shape_name])
    specs = batch_specs(cfg, b, MESH1)
    _check_divisible(b, specs, MESH1, (arch, shape_name))


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "jamba-v0.1-52b", "xlstm-125m"])
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    model = Model(cfg)
    c_shape = jax.eval_shape(lambda: model.init_cache(128, 1024))
    specs = cache_specs(cfg, c_shape, MESH1)
    _check_divisible(c_shape, specs, MESH1, arch)


def test_attention_params_tp_sharded():
    cfg = get_config("llama3.2-3b")
    model = Model(cfg)
    p_shape = jax.eval_shape(model.init, jax.random.key(0))
    specs = param_specs(cfg, p_shape, MESH1)
    body = specs["decoder"]["body"][0]
    # column-parallel QKV (stacked: leading None for the repeats dim)
    assert body["mixer"]["q"]["w"] == P(None, None, "model")
    assert body["mixer"]["o"]["w"] == P(None, "model", None)
    assert body["ffn"]["gate"]["w"] == P(None, None, "model")
    assert body["ffn"]["down"]["w"] == P(None, "model", None)
    assert body["norm1"]["w"] == P(None, None)


def test_moe_expert_parallel():
    cfg = get_config("deepseek-moe-16b")
    model = Model(cfg)
    p_shape = jax.eval_shape(model.init, jax.random.key(0))
    specs = param_specs(cfg, p_shape, MESH1)
    body = specs["decoder"]["body"][0]
    assert body["ffn"]["gate"] == P(None, "model", None, None)  # EP
    assert body["ffn"]["router"]["w"] == P(None, None, None)  # replicated


def test_long_context_cache_seq_sharded():
    """long_500k (batch=1): KV sequence axis shards over data(+model) (SP)."""
    cfg = get_config("jamba-v0.1-52b")
    model = Model(cfg)
    c_shape = jax.eval_shape(lambda: model.init_cache(1, 4096))
    body = cache_specs(cfg, c_shape, MESH1, optimized=True)["decoder"]["body"]
    # the attention position (index 4 of the 8-layer pattern); seq axis is
    # index 2 (after the stacked repeats dim)
    assert body[4]["k"][2] == ("data", "model")
    # baseline variant shards seq over data only
    body_b = cache_specs(cfg, c_shape, MESH1, optimized=False)["decoder"]["body"]
    assert body_b[4]["k"][2] == "data"


def test_decode_cache_seq_sharded_h3():
    """H3: batched decode shards the cache sequence over model."""
    cfg = get_config("granite-moe-1b-a400m")
    model = Model(cfg)
    c_shape = jax.eval_shape(lambda: model.init_cache(128, 1024))
    body = cache_specs(cfg, c_shape, MESH1, optimized=True)["decoder"]["body"]
    spec = body[0]["k"]
    assert spec[1] == "data" and spec[2] == "model"
