"""Plain reference for ``granite-moe-1b-a400m.4l``: weights, data, and three
training steps in float32.

Imports nothing of the program.  It states, from the configuration's sizes:

* :func:`param_layout` — the parameter tree the program consumes (the
  harness checks it against the program's own abstract parameters);
* :func:`init_params` — the seeded weights, made on the device in one call;
* :func:`synthetic_batch` — a copy of the token stream the trainer reads
  (a splitmix hash of ``(seed, document, position)`` with a Zipf skew);
* :func:`reference_readings` — forward and backward of the model, int8
  error-feedback compression of each pod's gradient, the mean over pods
  and AdamW, for the first three steps.  Every value is float32 and every
  matrix product runs at ``HIGHEST`` precision; parameters are stored
  after each update in the type the configuration gives them (bfloat16,
  router float32), which is part of the model's stated arithmetic.

``compute_dtype`` rounds the operands of every matrix product to a lower
precision first (float8 for the control), accumulation staying float32.
The rounding passes gradients through unchanged (straight through): the
backward pass multiplies the float32 cotangents by the rounded operands,
as a lower-precision matrix unit with float32 gradients would, instead of
rounding the cotangents too, which would flush them to zero.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_PAD = 256
NORM_EPS = 1e-6
MASK = -1e30


def padded_vocab(m: dict) -> int:
    return -(-m["vocab"] // VOCAB_PAD) * VOCAB_PAD


def param_layout(m: dict) -> dict:
    """The program's parameter tree for ``m``, as ``ShapeDtypeStruct`` leaves."""
    dt = jnp.dtype(m["dtype"])
    d, r = m["d_model"], m["n_layers"] // len(m["pattern"])
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    e, de = m["moe"]["n_experts"], m["moe"]["d_expert"]
    s = jax.ShapeDtypeStruct
    if [list(k) for k in m["pattern"]] != [["attn", "moe"]] or not m["tied_embeddings"]:
        raise ValueError("this reference states one attn+moe layer pattern with tied embeddings")
    block = {
        "norm1": {"w": s((r, d), dt)},
        "mixer": {
            "q": {"w": s((r, d, qd), dt)},
            "k": {"w": s((r, d, kvd), dt)},
            "v": {"w": s((r, d, kvd), dt)},
            "o": {"w": s((r, qd, d), dt)},
        },
        "norm2": {"w": s((r, d), dt)},
        "ffn": {
            "router": {"w": s((r, d, e), jnp.float32)},
            "gate": s((r, e, d, de), dt),
            "up": s((r, e, d, de), dt),
            "down": s((r, e, de, d), dt),
        },
    }
    return {
        "embed": s((padded_vocab(m), d), dt),
        "final_norm": {"w": s((d,), dt)},
        "decoder": {"prefix": [], "body": (block,)},
    }


def seed_key_data(seed: int) -> np.ndarray:
    """Threefry key data for a seed of up to 64 bits."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def init_params(m: dict, key_data: jax.Array) -> dict:
    """Seeded weights in the program's layout (jit this: one device call)."""
    key = jax.random.wrap_key_data(key_data)
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_layout(m))
    out = []
    for i, (path, sd) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            out.append(jnp.zeros(sd.shape, sd.dtype))
            continue
        fan_in = sd.shape[-1] if name == "['embed']" else sd.shape[-2]
        w = jax.random.normal(jax.random.fold_in(key, i), sd.shape, jnp.float32)
        out.append((w / math.sqrt(fan_in)).astype(sd.dtype))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Token stream
# ---------------------------------------------------------------------------


def _mix(seed: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    x = (a.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) ^ (
        b.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
    )
    x ^= np.uint64(seed) * np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def synthetic_batch(
    *, vocab: int, seq_len: int, global_batch: int, seed: int,
    step: int, shard: int, n_shards: int, zipf_s: float = 1.1,
) -> dict:
    rows = global_batch // n_shards
    row_ids = shard + np.arange(rows, dtype=np.uint64) * n_shards
    doc = np.uint64(step) * np.uint64(global_batch) + row_ids
    t = np.arange(seq_len + 1, dtype=np.uint64)
    with np.errstate(over="ignore"):
        h = _mix(seed, doc[:, None], t[None, :])
    u = (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    tok = np.floor(vocab * np.power(u, zipf_s)).astype(np.int32)
    tok = np.clip(tok, 0, vocab - 1)
    return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _q(x, cd):
    if cd is None:
        return x
    return x + jax.lax.stop_gradient(x.astype(cd).astype(jnp.float32) - x)


def _mm(eq, a, b, cd):
    return jnp.einsum(eq, _q(a, cd), _q(b, cd), precision=HIGHEST)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * (1.0 + w)


def _rope(x, theta):
    """x: [B, L, H, D]; rotate the two halves of each head by position."""
    l, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs  # [L, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(m, p, x, cd):
    b, l, _ = x.shape
    h, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm("bld,de->ble", x, p["q"]["w"], cd).reshape(b, l, h, hd)
    k = _mm("bld,de->ble", x, p["k"]["w"], cd).reshape(b, l, hkv, hd)
    v = _mm("bld,de->ble", x, p["v"]["w"], cd).reshape(b, l, hkv, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(b, l, hkv, h // hkv, hd) / math.sqrt(hd)  # head = kv * g + j
    s = _mm("bqhgd,bkhd->bhgqk", q, k, cd)
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, s, MASK), axis=-1)
    o = _mm("bhgqk,bkhd->bqhgd", probs, v, cd).reshape(b, l, h * hd)
    return _mm("ble,ed->bld", o, p["o"]["w"], cd)


def capacity(moe: dict, n_tokens: int) -> int:
    c = math.ceil(n_tokens * moe["top_k"] / moe["n_experts"] * moe["capacity_factor"])
    return max(4, -(-c // 4) * 4)


def _moe(m, p, x, cd):
    """Top-k routing with per-expert capacity; every expert computed densely."""
    moe = m["moe"]
    e, k = moe["n_experts"], moe["top_k"]
    b, l, d = x.shape
    t = b * l
    xf = x.reshape(t, d)
    probs = jax.nn.softmax(_mm("td,de->te", xf, p["router"]["w"], cd), axis=-1)
    w, ex = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    density = jnp.mean(jnp.sum(jax.nn.one_hot(ex, e), axis=1), axis=0)
    aux = e * jnp.sum(density / k * jnp.mean(probs, axis=0))
    # A choice keeps its place if fewer than `capacity` earlier choices
    # (in token order, then choice order) went to the same expert.
    flat = ex.reshape(t * k)
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
    earlier = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    kept = jnp.where(earlier < capacity(moe, t), w.reshape(t * k), 0.0)
    comb = jnp.zeros((t, e), jnp.float32).at[jnp.arange(t * k) // k, flat].add(kept)
    g = _mm("td,edf->etf", xf, p["gate"], cd)
    u = _mm("td,edf->etf", xf, p["up"], cd)
    y = _mm("etf,efd->etd", jax.nn.silu(g) * u, p["down"], cd)
    return jnp.einsum("te,etd->td", comb, y, precision=HIGHEST).reshape(b, l, d), aux


def loss(m: dict, params: dict, batch: dict, cd=None):
    """Next-token cross entropy plus the router's load-balance term."""
    x = params["embed"][batch["tokens"]]
    body = params["decoder"]["body"][0]
    aux_total = jnp.zeros((), jnp.float32)

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(m, p["mixer"], _rms(x, p["norm1"]["w"]), cd)
        y, aux = _moe(m, p["ffn"], _rms(x, p["norm2"]["w"]), cd)
        return x + y, aux

    for r in range(m["n_layers"]):
        x, aux = layer(x, jax.tree.map(lambda a: a[r], body))
        aux_total = aux_total + aux
    x = _rms(x, params["final_norm"]["w"])
    logits = _mm("bld,vd->blv", x, params["embed"][: m["vocab"]], cd)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1))
    return ce + m["moe"]["router_aux_weight"] * aux_total


# ---------------------------------------------------------------------------
# Gradient exchange and optimizer
# ---------------------------------------------------------------------------


def compress(g: dict, err: dict) -> tuple[dict, dict]:
    """Per-row absmax int8 with error feedback: (dequantised, new error)."""

    def deq(g, e):
        c = g + e
        scale = jnp.maximum(jnp.max(jnp.abs(c), axis=-1, keepdims=True) / 127.0, 1e-12)
        return jnp.clip(jnp.round(c / scale), -127, 127) * scale

    d = jax.tree.map(deq, g, err)
    return d, jax.tree.map(lambda g_, e_, d_: g_ + e_ - d_, g, err, d)


def lr_at(opt: dict, step: int) -> float:
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(1.0, opt["warmup_steps"])
    t = (step - opt["warmup_steps"]) / max(1.0, opt["total_steps"] - opt["warmup_steps"])
    t = min(max(t, 0.0), 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * t))
    return opt["lr"] * cos


def adamw(opt: dict, step: int, g: dict, mo: dict, vo: dict, p: dict, dtypes: dict):
    """One AdamW step; returns (params, m, v, the clipped gradient)."""
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12)), g)
    b1, b2 = opt["b1"], opt["b2"]
    mo = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, mo, g)
    vo = jax.tree.map(lambda v_, x: b2 * v_ + (1 - b2) * x * x, vo, g)
    lr = lr_at(opt, step)
    b1c, b2c = 1 - b1**step, 1 - b2**step

    def upd(p_, m_, v_, dt):
        delta = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"]) + opt["weight_decay"] * p_
        return (p_ - lr * delta).astype(dt).astype(jnp.float32)

    return jax.tree.map(upd, p, mo, vo, dtypes), mo, vo, g


def leaf_norms(tree: dict) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        jax.tree_util.keystr(path): float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
        for path, x in flat
    }


def reference_readings(
    m: dict, job: dict, seed: int, *, steps: int = 3, compute_dtype=None,
    fault: str | None = None,
) -> dict:
    """Losses of ``steps`` steps, the first clipped gradient's leaf norms, and
    the leaf norms of the parameters' change after ``steps`` steps.

    ``fault`` plants one of the faults the check must catch, to read it at
    the cell's size: ``"half_batch"`` (each pod's loss and gradient over
    half its rows) or ``"no_exchange"`` (every pod updates with pod 0's
    gradient alone).
    """
    n_pods, opt = job["n_pods"], job["optimizer"]
    p0 = jax.jit(partial(init_params, m))(seed_key_data(seed))
    dtypes = jax.tree.map(lambda a: a.dtype, p0)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p0)
    zeros = lambda: jax.tree.map(jnp.zeros_like, p)  # noqa: E731
    err = [zeros() for _ in range(n_pods)]
    mo, vo = zeros(), zeros()
    grad = jax.jit(jax.value_and_grad(partial(loss, m, cd=compute_dtype)))
    comp = jax.jit(compress)
    step_fn = jax.jit(
        lambda step, g, mo, vo, p: adamw(opt, step, g, mo, vo, p, dtypes),
        static_argnums=0,
    )
    losses, first_grad = [], None
    for step in range(steps):
        total, pod_losses = None, []
        for pod in range(n_pods):
            batch = synthetic_batch(
                vocab=m["vocab"], seq_len=job["seq_len"], global_batch=job["global_batch"],
                seed=seed, step=step, shard=pod, n_shards=n_pods,
            )
            if fault == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            value, g = grad(p, batch)
            deq, err[pod] = comp(g, err[pod])
            pod_losses.append(float(value))
            if fault == "no_exchange":
                deq = deq if total is None else total
            total = deq if total is None else jax.tree.map(jnp.add, total, deq)
            del g, deq
        mean = jax.tree.map(lambda a: a / n_pods, total)
        del total
        losses.append(sum(pod_losses) / n_pods)
        p, mo, vo, clipped = step_fn(step + 1, mean, mo, vo, p)
        if step == 0:
            first_grad = leaf_norms(clipped)
        del mean, clipped
    change = leaf_norms(jax.tree.map(lambda a, b: a - b.astype(jnp.float32), p, p0))
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}
