"""Per-architecture smoke tests: reduced config, one train + decode step on
CPU, asserting shapes and no NaNs.  Full configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.models import Model, padded_vocab


def _batch(cfg, b, l, key=1):
    tokens = jax.random.randint(jax.random.key(key), (b, l), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encoder_decoder:
        batch["src_embeds"] = (
            jax.random.normal(
                jax.random.key(key + 1), (b, cfg.frontend_len, cfg.d_model)
            ) * 0.1
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.frontend == "vision":
        batch["patch_embeds"] = (
            jax.random.normal(
                jax.random.key(key + 2), (b, cfg.frontend_len, cfg.d_model)
            ) * 0.1
        ).astype(jnp.dtype(cfg.dtype))
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    b, l = 2, 16
    batch = _batch(cfg, b, l)

    logits, aux = model.forward(
        params, batch["tokens"],
        src_embeds=batch.get("src_embeds"),
        patch_embeds=batch.get("patch_embeds"),
    )
    exp_len = l + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    assert logits.shape == (b, exp_len, padded_vocab(cfg))
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch
    )
    assert jnp.isfinite(loss), arch
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_decode(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    b = 2
    batch = _batch(cfg, b, 8)
    cache = model.init_cache(b, 32)
    logits, cache = model.prefill(
        params, batch["tokens"], cache,
        src_embeds=batch.get("src_embeds"),
        patch_embeds=batch.get("patch_embeds"),
    )
    assert logits.shape[0] == b
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for _ in range(3):
        logits, cache = model.decode_step(params, tok, cache)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), arch
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_full_config_exactness(arch):
    """The registered full config matches the assignment row."""
    cfg = get_config(arch)
    assignment = {
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "deepseek-moe-16b": (28, 2048, 16, 16, 10944, 102400),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 7168, 98304),
    }[arch]
    assert cfg.n_layers == assignment[0]
    assert cfg.d_model == assignment[1]
    assert cfg.n_heads == assignment[2]
    assert cfg.n_kv_heads == assignment[3]
    assert cfg.d_ff == assignment[4]
    assert cfg.vocab == assignment[5]


def test_moe_configs():
    g = get_config("granite-moe-1b-a400m").moe
    assert (g.n_experts, g.top_k, g.d_expert) == (32, 8, 512)
    d = get_config("deepseek-moe-16b").moe
    assert (d.n_experts, d.top_k, d.n_shared, d.d_expert) == (64, 6, 2, 1408)
    j = get_config("jamba-v0.1-52b").moe
    assert (j.n_experts, j.top_k) == (16, 2)


def test_jamba_interleave():
    cfg = get_config("jamba-v0.1-52b")
    seq = cfg.layer_seq()
    assert len(seq) == 32
    assert sum(1 for m, _ in seq if m == "attn") == 4  # 1:7 attn:mamba
    assert sum(1 for _, f in seq if f == "moe") == 16  # every other layer
