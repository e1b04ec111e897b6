"""Decode attention (flash-decode) — Pallas TPU kernel.

Single-token decode is *memory-bound*: the entire KV cache is streamed once
per step.  The kernel splits the KV sequence into blocks (split-K) and
accumulates the online-softmax partials in VMEM scratch, so the only HBM
traffic is the one mandatory KV read — the roofline optimum.

Queries for all ``G = Hq/Hkv`` heads of one KV group are processed together
as a ``[G, d]`` tile: the score matmul ``[G, d] × [d, bk]`` feeds the MXU a
tall-thin-but-batched operand instead of ``G`` rank-1 products, and the KV
block is read once per *group* rather than once per query head (the GQA
bandwidth saving is the whole point of GQA at decode time).

The valid-length mask makes rows beyond ``kv_len`` contribute zero, so a
static-shape ring cache can be over-allocated (serving pads to the shape
bucket and the kernel reads only what is valid — rounded up to the block).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    kvlen_ref,  # scalar prefetch: [1] int32 — valid KV rows
    q_ref,  # [1, 1, G, d]
    k_ref,  # [1, 1, bk, d]
    v_ref,  # [1, 1, bk, d]
    o_ref,  # [1, 1, G, d]
    m_scr,  # [G, 1]
    l_scr,  # [G, 1]
    acc_scr,  # [G, d]
    *,
    sm_scale: float,
    bk: int,
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = kvlen_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ik * bk

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, bk]
        s_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(s_idx < kv_len, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, Hq, d] — one new token per sequence
    k: jax.Array,  # [B, Hkv, Lk, d] — cache (possibly over-allocated)
    v: jax.Array,  # [B, Hkv, Lk, d]
    kv_len: jax.Array | int,  # valid rows, dynamic scalar
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    _, hkv, lk, _ = k.shape
    g = hq // hkv
    bk = min(block_k, lk)
    assert lk % bk == 0, (lk, bk)

    qg = q.reshape(b, hkv, g, d)
    kv_len_arr = jnp.asarray([kv_len], jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, lk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ik, *_: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ik, *_: (b_, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=1.0 / math.sqrt(d), bk=bk
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="swirl_decode_attention",
    )(kv_len_arr, qg, k, v)
    return out.reshape(b, hq, d)
