"""Fused RMSNorm — Pallas TPU kernel.

A bandwidth-bound elementwise+reduction op: fusing the mean-square
reduction, rsqrt and scale into one kernel reads/writes each row exactly
once (XLA sometimes splits the fp32 upcast path into two HBM round-trips).
Rows are processed in ``[br, d]`` VMEM tiles; the feature dim stays whole so
the row reduction never crosses tiles (all assigned d_model ≤ 8192 ⇒ a
``[256, 8192]`` fp32 tile is 8 MiB — comfortably inside the 16 MiB/core
VMEM budget together with the weight row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [br, d]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm(
    x: jax.Array,  # [..., d]
    w: jax.Array,  # [d]
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    # Pad rows up to a block multiple (masked rows are normalised garbage
    # that is sliced away — no correctness impact).
    pad = (-rows) % br
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, d), x2.dtype)], axis=0)
    grid = (x2.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="swirl_rmsnorm",
    )(x2, w)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
