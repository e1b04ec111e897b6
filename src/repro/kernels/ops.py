"""Jitted public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic.  On the CPU platform they run in
Pallas interpret mode, which the CPU tests also request explicitly with
``interpret=True``.  Any other platform is an error: a kernel never falls
back to the interpreter where a device was expected.
"""

from __future__ import annotations

from functools import partial

import jax

from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .rmsnorm import rmsnorm as _rmsnorm


def _interpret_default() -> bool:
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on platform {platform!r}; "
        "pass interpret=True to run them in the Pallas interpreter"
    )


@partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal=True, window=0, softcap=0.0,
    block_q=128, block_k=128, interpret=None,
):
    """q: [B, Lq, Hq, d]; k/v: [B, Lk, Hkv, d] (model layout) → [B, Lq, Hq, d]."""
    interp = _interpret_default() if interpret is None else interpret
    out = _flash(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interp,
    )
    return out.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, kv_len, *, block_k=512, interpret=None):
    """q: [B, 1, Hq, d]; k/v cache: [B, M, Hkv, d] → [B, 1, Hq, d]."""
    interp = _interpret_default() if interpret is None else interpret
    out = _decode(
        q[:, 0],
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        kv_len,
        block_k=block_k, interpret=interp,
    )
    return out[:, None]


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps=1e-6, block_rows=256, interpret=None):
    interp = _interpret_default() if interpret is None else interpret
    return _rmsnorm(x, w, eps=eps, block_rows=block_rows, interpret=interp)
