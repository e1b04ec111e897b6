"""Flash attention (prefill/train) — Pallas TPU kernel.

Online-softmax attention with explicit VMEM tiling:

* grid ``(B, Hq, Lq/bq, Lk/bk)`` — the last axis is ``arbitrary`` (sequential)
  so the running max ``m``, denominator ``l`` and accumulator ``acc`` live in
  VMEM scratch across KV blocks;
* Q blocks ``[bq, d]`` and KV blocks ``[bk, d]`` are staged HBM→VMEM by the
  BlockSpec pipeline; the two matmuls per block hit the MXU with
  ``d = head_dim`` padded to the 128-lane register width by construction
  (all assigned archs use head_dim ∈ {64, 128, 192});
* GQA is folded into the index map: query head ``h`` reads KV head
  ``h // (Hq/Hkv)`` — no KV replication in HBM;
* causal masking skips fully-masked KV blocks via ``pl.when`` (no FLOPs,
  no VMEM traffic beyond the prefetch);
* optional sliding-window and tanh soft-capping (Gemma-2) are fused.

Validated against :mod:`repro.kernels.ref` in ``interpret=True`` mode (this
container has no TPU); selected on real TPUs via ``set_attn_impl("pallas")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, bq, d] VMEM
    k_ref,  # [1, 1, bk, d]
    v_ref,  # [1, 1, bk, d]
    o_ref,  # [1, 1, bq, d]
    m_scr,  # [bq, 1] fp32 scratch
    l_scr,  # [bq, 1] fp32 scratch
    acc_scr,  # [bq, d] fp32 scratch
    *,
    sm_scale: float,
    causal: bool,
    window: int,
    softcap: float,
    bq: int,
    bk: int,
    kv_len: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    # Causal / window block-level skip: block is live iff some (t, s) pair
    # with t ≥ s (causal) and t − s < window (if windowed) exists.
    live = True
    if causal:
        live = q_start + bq - 1 >= k_start
    if window > 0:
        live = jnp.logical_and(live, q_start - (k_start + bk - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap

        t_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = s_idx < kv_len
        if causal:
            mask &= t_idx >= s_idx
        if window > 0:
            mask &= t_idx - s_idx < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Hq, Lq, d]
    k: jax.Array,  # [B, Hkv, Lk, d]
    v: jax.Array,  # [B, Hkv, Lk, d]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    kv_len: int | None = None,  # valid KV rows (≤ Lk), static
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    assert lq % bq == 0 and lk % bk == 0, (lq, bq, lk, bk)
    kv_len = lk if kv_len is None else kv_len

    grid = (b, hq, lq // bq, lk // bk)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=1.0 / math.sqrt(d),
        causal=causal,
        window=window,
        softcap=softcap,
        bq=bq,
        bk=bk,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="swirl_flash_attention",
    )(q, k, v)
