"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm


def _qkv(key, b, hq, hkv, lq, lk, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, lq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, lk, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, lk, d)).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,hq,hkv,lq,lk,d",
        [
            (1, 2, 2, 128, 128, 64),  # MHA
            (2, 4, 2, 128, 128, 64),  # GQA 2:1
            (1, 8, 1, 128, 256, 128),  # MQA, rectangular
            (1, 3, 1, 192, 192, 192),  # odd heads, xLSTM-ish head_dim
        ],
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, b, hq, hkv, lq, lk, d, dtype):
        q, k, v = _qkv(jax.random.key(0), b, hq, hkv, lq, lk, d, dtype)
        out = flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        )
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype],
        )

    @pytest.mark.parametrize("window", [32, 64, 100])
    def test_sliding_window(self, window):
        q, k, v = _qkv(jax.random.key(1), 1, 2, 2, 128, 128, 64, jnp.float32)
        out = flash_attention(
            q, k, v, causal=True, window=window,
            block_q=32, block_k=32, interpret=True,
        )
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("softcap", [20.0, 50.0])
    def test_softcap(self, softcap):
        q, k, v = _qkv(jax.random.key(2), 1, 2, 2, 64, 64, 64, jnp.float32)
        out = flash_attention(
            q, k, v, causal=True, softcap=softcap,
            block_q=32, block_k=32, interpret=True,
        )
        want = ref.flash_attention_ref(q, k, v, causal=True, softcap=softcap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_non_causal(self):
        q, k, v = _qkv(jax.random.key(3), 2, 2, 2, 64, 128, 64, jnp.float32)
        out = flash_attention(
            q, k, v, causal=False, block_q=32, block_k=64, interpret=True
        )
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_block_shape_invariance(self):
        q, k, v = _qkv(jax.random.key(4), 1, 2, 1, 256, 256, 64, jnp.float32)
        outs = [
            flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(outs[0]), atol=2e-5
            )


class TestDecodeAttention:
    @pytest.mark.parametrize(
        "b,hq,hkv,lk,d,kv_len",
        [
            (2, 4, 2, 256, 64, 200),
            (1, 8, 8, 512, 128, 512),
            (4, 2, 1, 128, 64, 1),
            (1, 14, 2, 256, 64, 100),  # internvl2-style GQA 7:1
        ],
    )
    def test_sweep(self, b, hq, hkv, lk, d, kv_len):
        key = jax.random.key(5)
        q = jax.random.normal(key, (b, hq, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, lk, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, lk, d))
        out = decode_attention(q, k, v, kv_len, block_k=64, interpret=True)
        want = ref.decode_attention_ref(q, k, v, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_garbage_beyond_kv_len_ignored(self):
        key = jax.random.key(6)
        b, hq, hkv, lk, d = 1, 2, 2, 128, 64
        q = jax.random.normal(key, (b, hq, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, lk, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, lk, d))
        k2 = k.at[:, :, 64:].set(1e9)  # poison the invalid region
        v2 = v.at[:, :, 64:].set(1e9)
        out = decode_attention(q, k2, v2, 64, block_k=32, interpret=True)
        want = decode_attention(q, k, v, 64, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


class TestRMSNorm:
    @pytest.mark.parametrize(
        "shape,d", [((7, 64), 64), ((2, 33, 128), 128), ((256, 512), 512)]
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, d, dtype):
        key = jax.random.key(7)
        x = jax.random.normal(key, shape).astype(dtype)
        w = (jax.random.normal(jax.random.fold_in(key, 1), (d,)) * 0.1).astype(
            dtype
        )
        out = rmsnorm(x, w, block_rows=32, interpret=True)
        want = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype],
        )

    def test_row_padding_path(self):
        # rows not a multiple of block_rows exercises the pad/slice path
        x = jax.random.normal(jax.random.key(8), (5, 64))
        w = jnp.zeros((64,))
        out = rmsnorm(x, w, block_rows=4, interpret=True)
        want = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_ops_wrappers_model_layout():
    """ops.py wrappers accept the model's [B, L, H, D] layout."""
    from repro.kernels import ops

    key = jax.random.key(9)
    q = jax.random.normal(key, (2, 64, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 64))
    out = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    assert out.shape == q.shape
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize(
    "platform,interpret", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_ops_interpret_follows_platform(monkeypatch, platform, interpret):
    """Interpret mode only on the CPU; no other device falls back to it."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="cannot run on platform"):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is interpret
