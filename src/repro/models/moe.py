"""Mixture-of-Experts FFN: token-choice top-k routing with capacity.

Dispatch is scatter-based (TPU-friendly, EP-shardable):

1. router logits → top-k (expert, weight) choices per token;
2. each choice gets a *slot* inside its expert's capacity buffer, computed
   with a running count (cumsum over the flattened choice list) — choices
   beyond capacity ``C = ceil(T·k/E · capacity_factor)`` are dropped (their
   tokens fall through the residual, standard Switch behaviour);
3. ``x`` rows are scattered into the ``[E, C, d]`` buffer, experts run as one
   batched gated-MLP einsum (sharded on the expert axis = EP), and results
   are gathered back and combined with the routing weights.

The auxiliary load-balance loss (Switch §2.2 form) is returned so the train
step can add ``router_aux_weight ×`` it.

:func:`apply_moe_held` is the dropless layer of expert parallelism: the
router scores all ``router_width`` experts, and the layer computes the part
of the result its held experts give, every choice that lands on them, as
grouped products over the choices sorted by expert.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import init_linear, linear

Params = dict[str, Any]


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": init_linear(ks[0], d, m.router_width, jnp.float32),
        "gate": (jax.random.normal(ks[1], (m.n_experts, d, m.d_expert)) * scale).astype(dtype),
        "up": (jax.random.normal(ks[2], (m.n_experts, d, m.d_expert)) * scale).astype(dtype),
        "down": (
            jax.random.normal(ks[3], (m.n_experts, m.d_expert, d))
            * (1.0 / math.sqrt(m.d_expert))
        ).astype(dtype),
    }
    if m.n_shared:
        d_sh = m.n_shared * m.d_expert
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": init_linear(kss[0], d, d_sh, dtype),
            "up": init_linear(kss[1], d, d_sh, dtype),
            "down": init_linear(kss[2], d_sh, d, dtype),
        }
    return p


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def apply_moe(
    cfg: ModelConfig, p: Params, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """x: [B, L, d] → (y, aux_loss)."""
    m = cfg.moe
    b, l, d = x.shape
    t = b * l
    k = m.top_k
    xf = x.reshape(t, d)

    logits = linear(p["router"], xf.astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)  # [T, k]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # Load-balance auxiliary loss (Switch): E · Σ_e f_e · P_e
    density = jnp.mean(
        jnp.sum(jax.nn.one_hot(experts, m.n_experts), axis=1), axis=0
    )  # fraction of tokens whose choice set includes e (×k)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(density / k * mean_prob)

    cap = moe_capacity(cfg, t)

    # Slot assignment: choice (t, j) takes the next free slot of its expert.
    flat_e = experts.reshape(t * k)  # [T·k]
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)  # [T·k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot  # prior same-expert choices
    slot = jnp.sum(pos * onehot, axis=-1)  # [T·k]
    keep = slot < cap
    slot_c = jnp.where(keep, slot, cap - 1)

    # Scatter tokens into [E, C, d] (dropped rows contribute zero).
    xk = jnp.repeat(xf, k, axis=0)  # [T·k, d] (choice-major: token t rows t·k..)
    contrib = jnp.where(keep[:, None], xk, 0).astype(x.dtype)
    buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
    buf = buf.at[flat_e, slot_c].add(contrib, mode="drop")

    # Batched expert gated-MLP (EP: leading expert axis shards on "model").
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["up"]
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["down"])  # [E, C, d]

    # Gather back and combine.
    fetched = out_buf[flat_e, slot_c]  # [T·k, d]
    fetched = jnp.where(keep[:, None], fetched, 0)
    yk = fetched.reshape(t, k, d) * weights[..., None].astype(x.dtype)
    y = jnp.sum(yk, axis=1)

    if "shared" in p:
        sh = p["shared"]
        y = y + linear(
            sh["down"],
            jax.nn.silu(linear(sh["gate"], xf)) * linear(sh["up"], xf),
        )
    return y.reshape(b, l, d), aux


def apply_moe_held(
    cfg: ModelConfig, p: Params, x: jax.Array
) -> tuple[jax.Array, jax.Array, dict[str, jax.Array]]:
    """x: [B, L, d] → (y, aux_loss, counters): the held experts' share.

    Routes over ``router_width`` experts (top-k, renormalised weights, the
    aux loss over all of them), keeps the choices that land on experts
    ``[first_expert, first_expert + n_experts)``, and computes them with
    no capacity: sorted by expert, one ``ragged_dot`` per projection, the
    weighted rows added back to their tokens.  What the other experts
    would add is left out: that is another chip's share.  The counters are
    ``moe_held_share`` (held choices ÷ all choices) and ``moe_load_max``
    (the busiest held expert's choices ÷ the held mean).
    """
    m = cfg.moe
    b, l, d = x.shape
    t, k, held = b * l, m.top_k, m.n_experts
    xf = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        logits = linear(p["router"], xf.astype(jnp.float32))  # [T, E_routed]
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        density = jnp.mean(
            jnp.sum(jax.nn.one_hot(experts, m.router_width), axis=1), axis=0
        )
        aux = m.router_width * jnp.sum(density / k * jnp.mean(probs, axis=0))

        # Held choices first, sorted by expert; the others (key ``held``)
        # follow and are computed by no group.
        local = experts.reshape(t * k) - m.first_expert
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        token = order // k
        valid = mine[order]
        # Rows past the groups are no product's, and a grouped product
        # leaves them undefined: select (never multiply) them away, here
        # and in the combine, so that neither pass reads them.
        rows = jnp.where(valid[:, None], xf[token], 0)
    with jax.named_scope("moe.held"):
        h = jax.nn.silu(jax.lax.ragged_dot(rows, p["gate"], sizes)) * (
            jax.lax.ragged_dot(rows, p["up"], sizes)
        )
        out = jax.lax.ragged_dot(h, p["down"], sizes)  # [T·k, d]
        out = jnp.where(valid[:, None], out, 0)
    with jax.named_scope("moe.route"):
        w = weights.reshape(t * k)[order]
        y = jnp.zeros((t, d), jnp.float32).at[token].add(
            out.astype(jnp.float32) * w[:, None]
        )
        n_held = jnp.sum(sizes).astype(jnp.float32)
        counters = {
            "moe_held_share": n_held / (t * k),
            "moe_load_max": jnp.max(sizes) * held / jnp.maximum(n_held, 1.0),
        }
    return y.astype(x.dtype).reshape(b, l, d), aux, counters


# ---------------------------------------------------------------------------
# H2 (hints): expert-local dispatch under shard_map
# ---------------------------------------------------------------------------
#
# Under pure GSPMD the capacity buffer is a GLOBAL [E, C_glob, d] tensor and
# the token→slot cumsum runs across the data-sharded token axis; XLA lowers
# the scatter/gather through whole-buffer all-reduces (~75 GB/layer on
# deepseek-moe-16b × train_4k).  But with TP-replicated activations no
# cross-shard dispatch is needed at all: each (dp, tp) device routes its
# LOCAL tokens, keeps only the choices owned by its LOCAL experts, runs a
# purely local scatter→expert-matmul→gather, and the partial outputs are
# summed with one psum over the TP axis.  Link traffic per layer drops from
# ~75 GB to one [B_loc, L, d] all-reduce.


def _local_moe_body(
    cfg: ModelConfig, tp_axis: str, tp_size: int, dp_axes, *, scatter_out: bool
):
    m = cfg.moe
    e_local = m.n_experts // tp_size

    def body(x_l, router, gate, up, down, shared):
        # x_l: [B_loc, L, d] (replicated over tp); gate/up/down: local experts
        b, l, d = x_l.shape
        t = b * l
        k = m.top_k
        xf = x_l.reshape(t, d)

        logits = xf.astype(jnp.float32) @ router  # router replicated [d, E]
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        # aux loss from globally-reduced router statistics
        density_l = jnp.mean(
            jnp.sum(jax.nn.one_hot(experts, m.n_experts), axis=1), axis=0
        )
        mean_prob_l = jnp.mean(probs, axis=0)
        # tokens are sharded over dp only; tp shards see identical stats.
        density = jax.lax.pmean(density_l, dp_axes)
        mean_prob = jax.lax.pmean(mean_prob_l, dp_axes)
        aux = m.n_experts * jnp.sum(density / k * mean_prob)

        # my expert range on this tp shard
        tp_idx = jax.lax.axis_index(tp_axis)
        e_start = tp_idx * e_local

        cap = moe_capacity(cfg, t)
        flat_e = experts.reshape(t * k)
        local_e = flat_e - e_start  # [T·k] in [0, e_local) if mine
        mine = (local_e >= 0) & (local_e < e_local)
        local_e_c = jnp.where(mine, local_e, 0)

        onehot = jax.nn.one_hot(local_e_c, e_local, dtype=jnp.int32)
        onehot = onehot * mine[:, None].astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.sum(pos * onehot, axis=-1)
        keep = mine & (slot < cap)
        slot_c = jnp.where(keep, slot, cap - 1)

        xk = jnp.repeat(xf, k, axis=0)
        contrib = jnp.where(keep[:, None], xk, 0).astype(x_l.dtype)
        buf = jnp.zeros((e_local, cap, d), x_l.dtype)
        buf = buf.at[local_e_c, slot_c].add(contrib, mode="drop")

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate)) * jnp.einsum(
            "ecd,edf->ecf", buf, up
        )
        out_buf = jnp.einsum("ecf,efd->ecd", h, down)

        fetched = out_buf[local_e_c, slot_c]
        fetched = jnp.where(keep[:, None], fetched, 0)
        yk = fetched.reshape(t, k, d) * weights[..., None].astype(x_l.dtype)
        y = jnp.sum(yk, axis=1)

        if shared is not None:
            # shared experts: column-parallel gate/up, row-parallel down —
            # their partial sum rides the same psum as the routed experts.
            sh_gate, sh_up, sh_down = shared
            hs = jax.nn.silu(xf @ sh_gate) * (xf @ sh_up)
            y = y + hs @ sh_down

        # One collective over TP for the whole MoE layer.  With an
        # SP residual stream the output is consumed sequence-sharded, so a
        # reduce-scatter over the token axis halves the traffic vs psum
        # (§Perf deepseek iter. 3).
        if scatter_out:
            y = jax.lax.psum_scatter(
                y.reshape(b, l, d), tp_axis, scatter_dimension=1, tiled=True
            )
            return y, aux
        y = jax.lax.psum(y, tp_axis)
        return y.reshape(b, l, d), aux

    return body


def apply_moe_sharded(
    cfg: ModelConfig, p: Params, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Expert-local MoE dispatch (requires installed ShardHints)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .hints import get_hints

    h = get_hints()
    m = cfg.moe
    assert h is not None
    mesh = h.mesh
    tp, dp = h.tp_axis, h.dp_spec()
    none2 = P(None, None)

    shared = None
    shared_specs = (
        (P(None, tp), P(None, tp), P(tp, None)) if "shared" in p else None
    )
    if "shared" in p:
        shared = (
            p["shared"]["gate"]["w"],
            p["shared"]["up"]["w"],
            p["shared"]["down"]["w"],
        )

    dp_axes = h.dp_axes if len(h.dp_axes) > 1 else h.dp_axes[0]
    scatter_out = (
        h.seq_parallel_residual and x.shape[1] % (h.tp_size * h.tp_size) == 0
    )
    body = _local_moe_body(
        cfg, tp, h.tp_size, dp_axes, scatter_out=scatter_out
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),  # x
            none2,  # router (replicated)
            P(tp, None, None),  # gate [E, d, de] expert-sharded
            P(tp, None, None),  # up
            P(tp, None, None),  # down
            shared_specs,  # shared expert weights (column/row parallel)
        ),
        out_specs=(
            P(dp, tp if scatter_out else None, None),
            P(),
        ),
        check_vma=False,
    )
    return fn(
        x, p["router"]["w"], p["gate"], p["up"], p["down"], shared
    )
