"""Distribution hints (H1 attention / H2 MoE): numerically identical to the
baseline paths on a degenerate 1×1 mesh (the 512-device behaviour is
exercised by the dry-run)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import Model, ModelConfig, MoECfg
from repro.models.hints import ShardHints, get_hints, set_hints
from repro.models.layers import sdpa


@pytest.fixture
def unit_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    set_hints(ShardHints(mesh=mesh, dp_axes=("data",)))
    yield mesh
    set_hints(None)


def test_stride_chunks_match_contiguous():
    key = jax.random.key(0)
    q = jax.random.normal(key, (2, 64, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 16))
    a = sdpa(q, k, v, causal=True, q_chunk=16, stride_chunks=False)
    b = sdpa(q, k, v, causal=True, q_chunk=16, stride_chunks=True)
    c = sdpa(q, k, v, causal=True, q_chunk=64)  # single chunk reference
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=1e-5)


def test_hinted_model_matches_baseline(unit_mesh):
    cfg = ModelConfig(
        name="hinted", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=0, vocab=128, dtype="float32", remat=False,
        pattern=(("attn", "moe"),),
        moe=MoECfg(n_experts=4, top_k=2, d_expert=16, n_shared=1,
                   capacity_factor=4.0),
    )
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}

    assert get_hints() is not None
    with unit_mesh:
        loss_h, metrics_h = jax.jit(m.loss)(params, batch)
    set_hints(None)
    loss_b, metrics_b = jax.jit(m.loss)(params, batch)

    assert float(jnp.abs(loss_h - loss_b)) < 1e-5
    assert float(jnp.abs(metrics_h["aux"] - metrics_b["aux"])) < 1e-5


def test_hinted_grads_match_baseline(unit_mesh):
    cfg = ModelConfig(
        name="hinted-g", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=0, vocab=128, dtype="float32", remat=False,
        pattern=(("attn", "moe"),),
        moe=MoECfg(n_experts=2, top_k=1, d_expert=16, capacity_factor=4.0),
    )
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}

    with unit_mesh:
        g_h = jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    set_hints(None)
    g_b = jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_h, g_b
    )
    assert max(jax.tree.leaves(diffs)) < 1e-4
