"""Model FLOPs of a decoder-only language model, counted from its sizes.

Training counts the forward pass's matrix products three times (forward,
and the two products of the backward pass), with no recomputation: a
rematerialised block is the program's choice, not work the model needs.
Experts count as the ``top_k`` routed to, with no capacity padding;
attention counts its causal length (a query at position ``i`` attends to
``i + 1`` keys); the LM head counts the logical vocabulary.
"""

from __future__ import annotations


def lm_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["d_model"]
    q_dim = cfg["n_heads"] * cfg["head_dim"]
    kv_dim = cfg["n_kv_heads"] * cfg["head_dim"]
    mean_ctx = (seq_len + 1) / 2
    total = 0.0
    layers = [tuple(k) for k in cfg["pattern"]] * (cfg["n_layers"] // len(cfg["pattern"]))
    for mixer, ffn in layers:
        if mixer != "attn":
            raise ValueError(f"no FLOP count for mixer {mixer!r}")
        total += 2 * d * (q_dim + 2 * kv_dim + q_dim)  # q, k, v, o
        total += 2 * 2 * q_dim * mean_ctx  # scores and values
        if ffn == "moe":
            m = cfg["moe"]
            total += 2 * d * m["n_experts"]  # router
            total += m["top_k"] * 3 * 2 * d * m["d_expert"]  # gated MLPs
        elif ffn == "mlp":
            total += 3 * 2 * d * cfg["d_ff"]
        else:
            raise ValueError(f"no FLOP count for ffn {ffn!r}")
    total += 2 * d * cfg["vocab"]  # LM head
    return total


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * lm_forward_flops_per_token(cfg, seq_len)
