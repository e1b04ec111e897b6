"""FLOPs and bytes of a decoder-only language model with windowed and full
attention and a held share of dropless experts, counted from its sizes.

Two kinds of count:

* :func:`model_flops_per_token`, for ``train.mfu``: the matrix products a
  token needs, forward and backward (3 × the forward), with no
  recomputation.  Attention counts the keys a query reads: its causal
  length on a full layer, at most the window on a windowed one.  Experts
  count the ``top_k · n_experts / n_routed`` choices a token sends to the
  experts held here, on average; the router counts all routed experts and
  the LM head the vocabulary slice.
* :func:`attention_work` and :func:`expert_work`, for the roofline shares:
  the FLOPs the program's attention and held-expert products execute in
  one step, recomputation included, and the least bytes they must move.

The program's passes over a layer, as its compiled grad step runs them
(``tests/bench`` checks the counts against the traced grad step):

* attention, per block of queries (:func:`query_block`): the forward's two
  products (scores, values), both again when the rematerialised layer is
  recomputed, both once more inside the block's own remat, and four in
  the backward pass — 10 products over the block's live keys (split into
  pieces of at most 2,048 keys, which changes their number, not their
  work); a single block over all keys (no block remat) runs 8;
* held experts: three products forward, recomputed, and six backward — 12
  products over the rows routed here.

A block's live keys are ``[start - window + 1, stop)`` with the start
floored to a block (windowed) or ``[0, stop)`` (full), as the program
slices them.
"""

from __future__ import annotations

BF16 = 2
# The program's tiling of a training sequence's queries (``sdpa`` in
# ``models/layers.py``): one block up to its ``q_chunk`` default, blocks of
# its ``LIVE_BLOCK`` beyond.
WHOLE = 2048
LIVE_BLOCK = 512


def layers(cfg: dict) -> list[tuple[str, str]]:
    pattern = [tuple(k) for k in cfg["pattern"]]
    return pattern * (cfg["n_layers"] // len(pattern))


def mean_keys(seq_len: int, window: int) -> float:
    """Keys a query reads, averaged over positions 0..L-1."""
    if window <= 0 or window >= seq_len:
        return (seq_len + 1) / 2
    ramp = window * (window + 1) / 2  # positions 0..w-1 read 1..w keys
    return (ramp + (seq_len - window) * window) / seq_len


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["d_model"]
    q_dim = cfg["n_heads"] * cfg["head_dim"]
    kv_dim = cfg["n_kv_heads"] * cfg["head_dim"]
    m = cfg["moe"]
    total = 0.0
    for mixer, ffn in layers(cfg):
        if mixer not in ("attn", "attn_local") or ffn != "moe":
            raise ValueError(f"no count for layer {mixer}+{ffn}")
        window = cfg["sliding_window"] if mixer == "attn_local" else 0
        total += 2 * d * (q_dim + 2 * kv_dim + q_dim)  # q, k, v, o
        total += 2 * 2 * q_dim * mean_keys(seq_len, window)  # scores and values
        total += 2 * d * m["n_routed"]  # router
        choices = m["top_k"] * m["n_experts"] / m["n_routed"]
        total += choices * 3 * 2 * d * m["d_expert"]  # gated MLPs
    total += 2 * d * cfg["vocab"]  # LM head over the slice
    return 3.0 * total


def query_block(seq_len: int) -> int:
    """Queries per attention block of a training sequence."""
    return seq_len if seq_len <= WHOLE else LIVE_BLOCK


def live_blocks(seq_len: int, block: int, window: int) -> list[tuple[int, int]]:
    """(queries, live keys) of each block of the program's attention."""
    if seq_len <= block:
        return [(seq_len, seq_len)]
    out = []
    for start in range(0, seq_len, block):
        stop = start + block
        lo = max(0, start - window + 1) // block * block if window > 0 else 0
        out.append((block, stop - lo))
    return out


def attention_work(
    cfg: dict, batch: int, seq_len: int, kind: str, *, block: int | None = None,
) -> tuple[float, float]:
    """(FLOPs executed, least bytes) of one step's ``kind`` layers
    (``attn_local`` or ``attn``), summed over those layers, in blocks of
    ``block`` queries (default: the program's :func:`query_block`)."""
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "attn_local" else 0
    blocks = live_blocks(seq_len, block or query_block(seq_len), window)
    chunked = len(blocks) > 1
    # forward, the layer's recompute and (chunked) the block's own
    fwd = (2 if cfg["remat"] else 1) + (1 if chunked else 0)
    products = 2 * fwd + 4
    per_layer = products * sum(2 * batch * hq * hd * q * k for q, k in blocks)
    q_bytes = batch * seq_len * hq * hd * BF16  # q, o, do, dq alike
    kv_bytes = batch * seq_len * hkv * hd * BF16  # k, v, dk, dv alike
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes  # read q, k, v; write o
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes  # read q, k, v, o, do; write dq, dk, dv
    bytes_ = fwd * fwd_bytes + bwd_bytes
    n = sum(1 for mixer, _ in layers(cfg) if mixer == kind)
    return n * per_layer, n * bytes_


def expert_work(cfg: dict, rows: float) -> tuple[float, float]:
    """(FLOPs executed, least bytes) of one step's held-expert products,
    over ``rows`` routed choices that landed on held experts (all layers)."""
    d, de = cfg["d_model"], cfg["moe"]["d_expert"]
    held = cfg["moe"]["n_experts"]
    fwd = 2 if cfg["remat"] else 1
    products = 3 * fwd + 6
    flops = products * 2 * d * de * rows
    n_layers = len(layers(cfg))
    # Each product reads its rows and its experts' weights and writes its
    # result: [rows, d] x [held, d, de] -> [rows, de] or back.
    per_product = (rows * (d + de)) * BF16 + n_layers * held * d * de * BF16
    return flops, products * per_product
