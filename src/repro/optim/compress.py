"""Int8 gradient compression with error feedback (cross-pod sync).

Cross-pod links are the scarcest bandwidth in a multi-pod job (data-centre
network vs. intra-pod ICI), so the SWIRL ``gradsync`` step compresses the
pod-level gradient before its send/recv exchange:

* per-row (last-axis) absmax scaling to int8 — 4× fewer bytes than bf16·2;
* *error feedback* (Seide et al., 1-bit SGD lineage): the quantisation
  residual is added back to the next step's gradient, so the compression
  bias telescopes and SGD-style convergence is preserved.

``compress``, ``decompress``, ``allreduce_mean`` and ``decompress_mean``
are jitted programs over pytrees: each call is one dispatch, and XLA
fuses a leaf's error correction, absmax, rounding and new residual into
about one pass over it.  They are traced once per tree layout (shapes and
dtypes) and reused on every later call; :data:`TRACES` counts the traces.
``compress`` writes the new error over the old one's buffers, and
``decompress_mean`` never writes the pods' float32 trees out, so a caller
that runs ahead of the device holds no more of them than it must.  The
workflow-level trainer (`launch/train.py`) calls them between its
``fwdbwd`` and ``update`` steps; they are unit-tested against the eager
formula and for the telescoping property.
"""

from __future__ import annotations

import collections
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any

TRACES: collections.Counter[str] = collections.Counter()
"""How many times each jitted body here has been traced, by function name.
A count that stays put over iterations shows every call reused its
compiled program."""


class Compressed(NamedTuple):
    q: PyTree  # int8 leaves
    scale: PyTree  # fp32 per-row scales


def _quant_leaf(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    g32 = g.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(g32), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_leaf(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


@partial(jax.jit, donate_argnums=1)
def _compress(grads: PyTree, error: PyTree) -> tuple[Compressed, PyTree]:
    TRACES["compress"] += 1
    corrected = jax.tree.map(
        lambda g, e: g.astype(jnp.float32) + e, grads, error
    )
    # Two maps, as parameter trees hold tuples; CSE merges the duplicate work.
    q = jax.tree.map(lambda c: _quant_leaf(c)[0], corrected)
    s = jax.tree.map(lambda c: _quant_leaf(c)[1], corrected)
    deq = jax.tree.map(_dequant_leaf, q, s)
    new_error = jax.tree.map(lambda c, d: c - d, corrected, deq)
    return Compressed(q=q, scale=s), new_error


def compress(grads: PyTree, error: PyTree | None = None) -> tuple[Compressed, PyTree]:
    """Quantise ``grads + error``; returns (compressed, new error feedback).

    ``error`` is donated: the new error takes its buffers, and it may not
    be read after the call."""
    if error is None:
        error = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
    return _compress(grads, error)


@jax.jit
def decompress(c: Compressed) -> PyTree:
    TRACES["decompress"] += 1
    return jax.tree.map(_dequant_leaf, c.q, c.scale)


@jax.jit
def allreduce_mean(parts: list[PyTree]) -> PyTree:
    """Mean of decompressed pod gradients (gradsync step body)."""
    TRACES["allreduce_mean"] += 1
    n = float(len(parts))
    out = parts[0]
    for p in parts[1:]:
        out = jax.tree.map(lambda a, b: a + b, out, p)
    return jax.tree.map(lambda a: a / n, out)


@partial(jax.jit, static_argnums=0)
def decompress_mean(
    mean: Callable[[list[PyTree]], PyTree], parts: list[Compressed]
) -> PyTree:
    """``mean`` of the decompressed ``parts``, as one program (gradsync
    step body).  ``mean`` is static: a module-level function such as
    :func:`allreduce_mean` is traced once per tree layout."""
    TRACES["decompress_mean"] += 1
    return mean([decompress(c) for c in parts])


def compressed_bytes(c: Compressed) -> int:
    qb = sum(l.size for l in jax.tree.leaves(c.q))
    sb = sum(l.size * 4 for l in jax.tree.leaves(c.scale))
    return qb + sb
