"""Shared neural layers: norms, RoPE, MLPs, and GQA attention.

Attention is implemented "flash-style" in pure JAX: the query axis is
processed in Python-unrolled chunks so the ``[Lq, Lk]`` score tensor never
exceeds ``q_chunk × Lk`` (in training, ``LIVE_BLOCK`` × the block's live
keys, causal and windowed) — this bounds live memory at 32k prefill and keeps
every FLOP visible to ``cost_analysis`` (no while-loops hiding work).  The
Pallas TPU kernel (:mod:`repro.kernels`) is a drop-in replacement selected
with ``attn_impl="pallas"`` on real TPUs.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = dict[str, Any]

# "xla" (default, compiles everywhere) or "pallas" (TPU kernels).
_ATTN_IMPL = "xla"


def set_attn_impl(impl: str) -> None:
    global _ATTN_IMPL
    assert impl in ("xla", "pallas"), impl
    _ATTN_IMPL = impl


def get_attn_impl() -> str:
    return _ATTN_IMPL


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


def init_norm(cfg: ModelConfig, dtype) -> Params:
    if cfg.norm == "rmsnorm":
        return {"w": jnp.zeros((cfg.d_model,), dtype)}
    return {"w": jnp.ones((cfg.d_model,), dtype), "b": jnp.zeros((cfg.d_model,), dtype)}


# ---------------------------------------------------------------------------
# Linear / init helpers
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, dtype, *, bias: bool = False) -> Params:
    scale = 1.0 / math.sqrt(d_in)
    p: Params = {
        "w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)
    }
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_freqs(
    head_dim: int, theta: float, *, factor: float, original_len: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's inverse frequencies [D/2] (float32, as published).

    Dimensions that turn fewer than ``beta_slow`` times over the original
    length are interpolated (divided by ``factor``), those that turn more
    than ``beta_fast`` times are kept, and a linear ramp between the two
    correction dimensions (floored and ceiled) blends them.
    """

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original_len / (rotations * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float32) - low) / (high - low), 0, 1
    )
    extrapolate = (1 - ramp).astype(np.float32)
    inv = (1.0 / (factor * pos)) * (1 - extrapolate) + (1.0 / pos) * extrapolate
    return inv.astype(np.float32)


def layer_rope(cfg: ModelConfig, *, local: bool) -> tuple[Any, float]:
    """(inverse frequencies, cos/sin scale) of an attention layer's RoPE:
    ``rope_local_theta`` on windowed layers, YaRN on the others if set."""
    if local:
        return rope_freqs(cfg.head_dim, cfg.rope_local_theta or cfg.rope_theta), 1.0
    if cfg.yarn_factor <= 0.0:
        return rope_freqs(cfg.head_dim, cfg.rope_theta), 1.0
    inv = yarn_freqs(
        cfg.head_dim, cfg.rope_theta, factor=cfg.yarn_factor,
        original_len=cfg.yarn_original_len, beta_fast=cfg.yarn_beta_fast,
        beta_slow=cfg.yarn_beta_slow,
    )
    return jnp.asarray(inv), cfg.yarn_attention_factor


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10_000.0, *,
    freqs: jax.Array | None = None, scale: float = 1.0,
) -> jax.Array:
    """x: [B, L, H, D]; positions: [B, L] or [L].  ``freqs`` replaces the
    plain frequencies of ``theta``; ``scale`` multiplies cos and sin."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)  # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    if cfg.activation.endswith("_glu"):
        return {
            "gate": init_linear(ks[0], cfg.d_model, d_ff, dtype),
            "up": init_linear(ks[1], cfg.d_model, d_ff, dtype),
            "down": init_linear(ks[2], d_ff, cfg.d_model, dtype),
        }
    return {
        "up": init_linear(ks[0], cfg.d_model, d_ff, dtype),
        "down": init_linear(ks[1], d_ff, cfg.d_model, dtype),
    }


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.activation == "silu_glu":
        h = jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x)
    elif cfg.activation == "gelu_glu":
        h = jax.nn.gelu(linear(p["gate"], x), approximate=True) * linear(p["up"], x)
    elif cfg.activation == "relu_sq":
        h = jnp.square(jax.nn.relu(linear(p["up"], x)))
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(linear(p["up"], x), approximate=True)
    else:
        raise ValueError(cfg.activation)
    return linear(p["down"], h)


# ---------------------------------------------------------------------------
# Attention (GQA; causal / bidirectional / sliding window / cross; softcap)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "q": init_linear(ks[0], cfg.d_model, cfg.q_dim, dtype, bias=cfg.qkv_bias),
        "k": init_linear(ks[1], cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "v": init_linear(ks[2], cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "o": init_linear(ks[3], cfg.q_dim, cfg.d_model, dtype),
    }


def _sdpa_chunk(
    q: jax.Array,  # [B, c, Hkv, G, D] fp32-scaled queries
    k: jax.Array,  # [B, Lk, Hkv, D]
    v: jax.Array,  # [B, Lk, Hkv, D]
    q_pos: jax.Array,  # [c] (or [B, c]) absolute positions of the q rows
    k_pos: jax.Array,  # [Lk]
    kv_valid: Optional[jax.Array],  # [] or [B] — #valid cache rows, or None
    *,
    causal: bool,
    window: int,
    softcap: float,
) -> jax.Array:
    scores = jnp.einsum(
        "bchgd,bkhd->bchgk", q, k, preferred_element_type=jnp.float32
    )
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]  # [B?, c]
    kp = k_pos[None, None, :]  # [1, 1, Lk]
    mask = jnp.ones((qp.shape[0], qp.shape[1], k_pos.shape[0]), bool)
    if causal:
        mask &= qp[:, :, None] >= kp
    if window > 0:
        mask &= qp[:, :, None] - kp < window
    if kv_valid is not None:
        kv = jnp.asarray(kv_valid)
        kv = kv[:, None, None] if kv.ndim == 1 else kv[None, None, None]
        mask &= kp < kv
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bchgk,bkhd->bchgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


# Queries per block of the training path (sequences longer than sdpa's
# ``q_chunk``): each block reads only its live keys.
LIVE_BLOCK = 512
# Keys a training chunk's softmax reduces over in one piece.  XLA:TPU
# lowers a longer reduction (scores laid out queries-minor) to fusions
# whose cost grows with the square of the keys.
KEY_BLOCK = 2048


def _live_chunk(
    q: jax.Array,  # [B, c, Hkv, G, D] queries in the model's dtype
    k: jax.Array,  # [B, n, Hkv, D] the chunk's live keys
    v: jax.Array,  # [B, n, Hkv, D]
    q_pos: jax.Array,  # [c]
    k_pos: jax.Array,  # [n]
    *,
    causal: bool,
    window: int,
    softcap: float,
) -> jax.Array:
    """One training chunk over its live keys; returns [B, c, Hkv, G, D].

    Scores, ``[B, Hkv, G, c, keys]``, are the product of the unscaled
    operands in float32, then scaled.  The softmax is exact and split over
    blocks of at most ``KEY_BLOCK`` keys: their maxima, then the shared
    maximum, the exponentials' sums and the value products per block.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    blocks = []
    for lo in range(0, k.shape[1], KEY_BLOCK):
        kp = k_pos[lo : lo + KEY_BLOCK]
        s = jnp.einsum(
            "bchgd,bkhd->bhgck", q, k[:, lo : lo + KEY_BLOCK],
            preferred_element_type=jnp.float32,
        ) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.ones((q_pos.shape[0], kp.shape[0]), bool)
        if causal:
            mask &= q_pos[:, None] >= kp[None, :]
        if window > 0:
            mask &= q_pos[:, None] - kp[None, :] < window
        blocks.append(jnp.where(mask, s, -1e30))
    m = blocks[0].max(axis=-1, keepdims=True)
    for s in blocks[1:]:
        m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    m = jax.lax.stop_gradient(m)
    total, out = 0.0, 0.0
    for i, s in enumerate(blocks):
        e = jnp.exp(s - m)
        total = total + e.sum(axis=-1, keepdims=True)
        out = out + jnp.einsum(
            "bhgck,bkhd->bhgcd", e.astype(v.dtype), v[:, i * KEY_BLOCK : (i + 1) * KEY_BLOCK],
            preferred_element_type=jnp.float32,
        )
    out = out / total  # [B, Hkv, G, c, D]
    return jnp.moveaxis(out, 3, 1).astype(v.dtype)


def sdpa(
    q: jax.Array,  # [B, Lq, Hq, D]
    k: jax.Array,  # [B, Lk, Hkv, D]
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: jax.Array | int = 0,
    kv_valid: Optional[jax.Array] = None,
    q_chunk: int = 2048,
    stride_chunks: bool = False,
) -> jax.Array:
    """Chunked-query GQA attention; returns [B, Lq, Hq, D].

    Self-attention over a sequence longer than ``q_chunk`` (training: no
    cache, no query offset) reads, for each block of ``LIVE_BLOCK``
    queries (``q_chunk`` if fewer), only its live keys:
    ``[start - window + 1, stop)`` windowed (the start floored to a
    block), ``[0, stop)`` causal.  The masked softmax over that range is
    the masked softmax over all keys, minus the dead keys' zeros.  Each
    chunk is rematerialised on its own, so the backward pass holds one
    chunk's scores at a time and memory stays linear in the length.

    ``stride_chunks``: chunk the query axis by STRIDE instead of contiguous
    ranges — used when Lq is sequence-sharded over the TP axis, so every
    chunk keeps rows on every shard (a contiguous chunk would collapse onto
    one shard and serialise the mesh).  Masks stay exact because positions
    are explicit.
    """
    if _ATTN_IMPL == "pallas" and kv_valid is None and window == 0 and causal:
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, causal=True, softcap=softcap)

    b, lq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qs = (q.astype(jnp.float32) / math.sqrt(d)).reshape(b, lq, hkv, g, d)
    k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    offs = jnp.asarray(q_offset, jnp.int32)

    def chunk_out(rows: jax.Array, q_pos: jax.Array, size: int) -> jax.Array:
        o = _sdpa_chunk(
            rows, k, v, q_pos, k_pos, kv_valid,
            causal=causal, window=window, softcap=softcap,
        )
        return o.reshape(b, size, hq, d)

    if lq <= q_chunk:
        qp = offs + jnp.arange(lq, dtype=jnp.int32)
        return chunk_out(qs, qp, lq)
    live = (
        isinstance(q_offset, int) and q_offset == 0 and kv_valid is None
        and k.shape[1] == lq and not stride_chunks
    )
    if live:
        size = min(LIVE_BLOCK, q_chunk)
        assert lq % size == 0, (lq, size)
        block = jax.checkpoint(
            partial(_live_chunk, causal=causal, window=window, softcap=softcap)
        )
        qb = q.reshape(b, lq, hkv, g, d)
        outs = []
        for start in range(0, lq, size):
            stop = start + size
            hi = stop if causal else lq
            lo = max(0, start - window + 1) // size * size if window > 0 else 0
            o = block(qb[:, start:stop], k[:, lo:hi], v[:, lo:hi], k_pos[start:stop], k_pos[lo:hi])
            outs.append(o.reshape(b, size, hq, d))
        return jnp.concatenate(outs, axis=1)
    assert lq % q_chunk == 0, (lq, q_chunk)
    n = lq // q_chunk
    if stride_chunks:
        outs = []
        for c in range(n):
            qp = offs + jnp.arange(c, lq, n, dtype=jnp.int32)
            outs.append(chunk_out(qs[:, c::n], qp, q_chunk))
        # row i·n + c of the output is row i of chunk c
        return (
            jnp.stack(outs, axis=2)  # [B, lq/n, n, H, D]
            .reshape(b, lq, hq, d)
        )
    outs = []
    for start in range(0, lq, q_chunk):
        qp = offs + jnp.arange(start, start + q_chunk, dtype=jnp.int32)
        outs.append(chunk_out(qs[:, start : start + q_chunk], qp, q_chunk))
    return jnp.concatenate(outs, axis=1)


def attention_block(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,  # [B, L, d]
    *,
    positions: jax.Array,  # [L] absolute positions
    causal: bool,
    window: int = 0,
    cache: Optional[Params] = None,  # {"k","v","len"} — decode/prefill cache
    cross_kv: Optional[tuple[jax.Array, jax.Array]] = None,
    use_rope: bool = True,
) -> tuple[jax.Array, Optional[Params]]:
    """Full attention sub-block: projections + RoPE + SDPA (+ cache update)."""
    b, l, _ = x.shape
    q = linear(p["q"], x).reshape(b, l, cfg.n_heads, cfg.head_dim)
    if cross_kv is not None:
        k, v = cross_kv
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        out = sdpa(q, k, v, causal=False, softcap=cfg.attn_logit_softcap)
        return linear(p["o"], out.reshape(b, l, cfg.q_dim)), cache

    k = linear(p["k"], x).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["v"], x).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    if use_rope:
        freqs, scale = layer_rope(cfg, local=window > 0)
        q = apply_rope(q, positions, freqs=freqs, scale=scale)
        k = apply_rope(k, positions, freqs=freqs, scale=scale)

    def attend(q, k, v, **kw):
        # Named for the device trace: windowed and full layers apart.
        with jax.named_scope("attn.window" if window > 0 else "attn.full"):
            return sdpa(
                q, k, v, causal=causal, window=window,
                softcap=cfg.attn_logit_softcap, **kw,
            )

    if cache is None:
        # H1 (hints): explicit attention sharding.  GSPMD's default for GQA
        # with Hkv ∤ TP partially shards heads and ALL-REDUCES the score
        # tensor (~10 GB/layer on llama3.2-3b).  Two regimes:
        #   · Hkv | tp  → head-parallel: everything local per KV head;
        #   · Hkv ∤ tp  → sequence-sharded queries + replicated K/V: one
        #     K/V all-gather per layer instead of score all-reduces.
        from .hints import constrain, get_hints

        h = get_hints()
        head_parallel = (
            h is not None
            and h.head_shard_attention
            and cfg.n_kv_heads % h.tp_size == 0
            and b % h.dp_size == 0
        )
        seq_parallel = (
            h is not None
            and not head_parallel
            and h.seq_shard_attention
            and l % h.tp_size == 0
            and b % h.dp_size == 0
        )
        if head_parallel:
            dp = h.dp_spec()
            q = constrain(q, dp, None, h.tp_axis, None)
            k = constrain(k, dp, None, h.tp_axis, None)
            v = constrain(v, dp, None, h.tp_axis, None)
            out = attend(q, k, v)
            out = constrain(out, dp, None, h.tp_axis, None)
        elif seq_parallel:
            dp = h.dp_spec()
            q = constrain(q, dp, h.tp_axis, None, None)
            k = constrain(k, dp, None, None, None)
            v = constrain(v, dp, None, None, None)
            # ≤4k: the TP split already bounds per-device score memory —
            # skip chunking (strided chunks lower to gather/scatter whose
            # backward re-introduces full-residual collectives, §Perf it.3).
            out = attend(
                q, k, v, q_chunk=l if l <= 4096 else 2048, stride_chunks=True,
            )
            out = constrain(out, dp, h.tp_axis, None, None)
        else:
            out = attend(q, k, v)
        new_cache = None
    else:
        # Write new K/V rows at cache["len"], then attend over valid rows.
        idx = cache["len"]  # scalar int32
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
        valid = idx + l
        out = attend(q, ck, cv, q_offset=idx, kv_valid=valid)
        new_cache = {"k": ck, "v": cv, "len": valid}
    return linear(p["o"], out.reshape(b, l, cfg.q_dim)), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((), jnp.int32),
    }
