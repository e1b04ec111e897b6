"""Model assembly: blocks → scanned layer groups → LM / enc-dec forward.

Layers are grouped by the config's repeating ``pattern``; params of each
pattern position are stacked over ``repeats`` and the stack is traversed
with ``jax.lax.scan`` so an 80-layer model lowers to a compact HLO.  The
same block code serves training (no cache), prefill (cache write) and
decode (cache append) — recurrent mixers thread their states through the
identical path, which is what makes ``long_500k`` O(1)-state decode work.

Vocab padding: embedding/LM-head rows are padded up to a multiple of 256 so
vocab shards evenly on the ``model`` mesh axis (the config's logical vocab is
unchanged; padded logits are masked to −∞).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    apply_mlp,
    apply_norm,
    attention_block,
    init_attention,
    init_kv_cache,
    init_mlp,
    init_norm,
    linear,
    init_linear,
)
from .moe import apply_moe, apply_moe_held, init_moe
from .recurrent import (
    MambaState,
    MLSTMState,
    SLSTMState,
    init_mamba,
    init_mamba_state,
    init_mlstm,
    init_mlstm_state,
    init_slstm,
    init_slstm_state,
    mamba_mix,
    mlstm_mix,
    slstm_mix,
)

Params = dict[str, Any]
PyTree = Any

VOCAB_PAD = 256


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: tuple[str, str], *, cross: bool, dtype) -> Params:
    mixer, ffn = kind
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": init_norm(cfg, dtype)}
    if mixer in ("attn", "attn_local"):
        p["mixer"] = init_attention(ks[0], cfg, dtype)
    elif mixer == "mamba":
        p["mixer"] = init_mamba(ks[0], cfg, dtype)
    elif mixer == "mlstm":
        p["mixer"] = init_mlstm(ks[0], cfg, dtype)
    elif mixer == "slstm":
        p["mixer"] = init_slstm(ks[0], cfg, dtype)
    else:
        raise ValueError(mixer)
    if cfg.post_block_norm:
        p["post_norm1"] = init_norm(cfg, dtype)
    if cross:
        p["norm_cross"] = init_norm(cfg, dtype)
        p["cross"] = init_attention(ks[1], cfg, dtype)
    if ffn != "none":
        p["norm2"] = init_norm(cfg, dtype)
        if ffn == "mlp":
            p["ffn"] = init_mlp(ks[2], cfg, cfg.d_ff, dtype)
        elif ffn == "moe":
            p["ffn"] = init_moe(ks[2], cfg, dtype)
        elif ffn == "dense0":
            p["ffn"] = init_mlp(ks[2], cfg, cfg.d_ff, dtype)
        else:
            raise ValueError(ffn)
        if cfg.post_block_norm:
            p["post_norm2"] = init_norm(cfg, dtype)
    return p


def init_block_cache(
    cfg: ModelConfig, kind: tuple[str, str], batch: int, max_len: int, dtype
) -> Params | None:
    """Cache entry for one layer (no 'len' — it is shared model-wide)."""
    mixer, _ = kind
    if mixer in ("attn", "attn_local"):
        kv = init_kv_cache(cfg, batch, max_len, dtype)
        return {"k": kv["k"], "v": kv["v"]}
    if mixer == "mamba":
        return {"state": init_mamba_state(cfg, batch, dtype)}
    if mixer == "mlstm":
        return {"state": init_mlstm_state(cfg, batch)}
    if mixer == "slstm":
        return {"state": init_slstm_state(cfg, batch)}
    raise ValueError(mixer)


def apply_block(
    cfg: ModelConfig,
    kind: tuple[str, str],
    p: Params,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool,
    cache: Params | None,  # per-layer entry (no "len")
    cache_len: jax.Array | None,  # shared scalar, None when training
    enc_out: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, dict[str, jax.Array]]:
    """Returns (x', new cache entry, stats): ``stats["aux"]`` is the aux
    loss, beside the held-experts layer's counters."""
    mixer, ffn = kind
    stats = {"aux": jnp.zeros((), jnp.float32)}
    h = apply_norm(cfg, p["norm1"], x)

    new_cache: Params | None = None
    if mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if mixer == "attn_local" else 0
        kv_cache = None
        if cache is not None:
            kv_cache = {"k": cache["k"], "v": cache["v"], "len": cache_len}
        out, upd = attention_block(
            cfg, p["mixer"], h,
            positions=positions, causal=causal, window=window, cache=kv_cache,
            use_rope=cfg.use_rope,
        )
        if upd is not None:
            new_cache = {"k": upd["k"], "v": upd["v"]}
    elif mixer == "mamba":
        out, st = mamba_mix(cfg, p["mixer"], h, cache["state"] if cache else None)
        new_cache = {"state": st}
    elif mixer == "mlstm":
        out, st = mlstm_mix(cfg, p["mixer"], h, cache["state"] if cache else None)
        new_cache = {"state": st}
    elif mixer == "slstm":
        out, st = slstm_mix(cfg, p["mixer"], h, cache["state"] if cache else None)
        new_cache = {"state": st}
    else:
        raise ValueError(mixer)

    if cfg.post_block_norm:
        out = apply_norm(cfg, p["post_norm1"], out)
    x = x + out

    if "cross" in p:
        h = apply_norm(cfg, p["norm_cross"], x)
        b, s, _ = enc_out.shape
        ck = linear(p["cross"]["k"], enc_out).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        cv = linear(p["cross"]["v"], enc_out).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        out, _ = attention_block(
            cfg, p["cross"], h,
            positions=positions, causal=False, cross_kv=(ck, cv), use_rope=False,
        )
        x = x + out

    if ffn != "none":
        h = apply_norm(cfg, p["norm2"], x)
        if ffn == "moe":
            from .hints import get_hints
            from .moe import apply_moe_sharded

            hints = get_hints()
            if cfg.moe.dropless:
                out, aux, counters = apply_moe_held(cfg, p["ffn"], h)
                stats = {"aux": aux, **counters}
            elif (
                hints is not None
                and hints.local_moe_dispatch
                and cfg.moe.n_experts % hints.tp_size == 0
                and (x.shape[0] * x.shape[1]) % hints.dp_size == 0
                and x.shape[0] % hints.dp_size == 0
            ):
                out, stats["aux"] = apply_moe_sharded(cfg, p["ffn"], h)
            else:
                out, stats["aux"] = apply_moe(cfg, p["ffn"], h)
        else:
            out = apply_mlp(cfg, p["ffn"], h)
        if cfg.post_block_norm:
            out = apply_norm(cfg, p["post_norm2"], out)
        x = x + out
    if cache is not None and new_cache is None:
        new_cache = cache
    return x, new_cache, stats


def _merge_stats(acc: dict, stats: dict) -> dict:
    """Sum the aux loss and held shares over layers; the busiest layer's
    load is the stack's."""
    out = dict(acc)
    for k, v in stats.items():
        out[k] = jnp.maximum(acc[k], v) if k == "moe_load_max" else acc[k] + v
    return out


# ---------------------------------------------------------------------------
# Layer stacks (prefix unrolled, body scanned over repeats)
# ---------------------------------------------------------------------------


def _init_stack(key, cfg: ModelConfig, *, cross: bool, dtype) -> Params:
    """params = {"prefix": [block...], "body": tuple_j stacked-block}."""
    kp, kb = jax.random.split(key)
    prefix = []
    for i, kind in enumerate(cfg.prefix_pattern):
        prefix.append(
            init_block(jax.random.fold_in(kp, i), cfg, kind, cross=cross, dtype=dtype)
        )
    body = []
    for j, kind in enumerate(cfg.pattern):
        per_repeat = [
            init_block(
                jax.random.fold_in(kb, j * cfg.repeats + r),
                cfg, kind, cross=cross, dtype=dtype,
            )
            for r in range(cfg.repeats)
        ]
        body.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))
    return {"prefix": prefix, "body": tuple(body)}


def _init_stack_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype
) -> Params:
    prefix = [
        init_block_cache(cfg, kind, batch, max_len, dtype)
        for kind in cfg.prefix_pattern
    ]
    body = []
    for kind in cfg.pattern:
        per_repeat = [
            init_block_cache(cfg, kind, batch, max_len, dtype)
            for _ in range(cfg.repeats)
        ]
        body.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))
    return {"prefix": prefix, "body": tuple(body)}


def _apply_stack(
    cfg: ModelConfig,
    params: Params,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool,
    cache: Params | None,
    cache_len: jax.Array | None,
    enc_out: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, dict[str, jax.Array]]:
    """Returns (x, new cache, stats): the aux loss summed over layers and,
    for held experts, the counters of :func:`~.moe.apply_moe_held` (share
    averaged over the MoE layers, load the busiest layer's)."""
    # H4 (hints): Megatron-SP residual stream — between blocks the
    # [B, L, d] activations live sequence-sharded over the TP axis, so the
    # backward activation-grad exchange lowers to reduce-scatter/all-gather
    # pairs instead of full all-reduces (≈2× less residual traffic) and
    # norms compute on 1/tp of the rows.
    from .hints import constrain, get_hints

    h = get_hints()
    sp_resid = (
        h is not None
        and h.seq_parallel_residual
        and cache is None  # decode keeps L=1
        and x.shape[1] % h.tp_size == 0
        and x.shape[0] % h.dp_size == 0
    )

    def sp(z):
        return constrain(z, h.dp_spec(), h.tp_axis, None) if sp_resid else z

    x = sp(x)
    n_moe = sum(f == "moe" for _, f in cfg.layer_seq())
    keys = ("aux",)
    if cfg.moe.dropless and n_moe:
        keys += ("moe_held_share", "moe_load_max")
    stats = {k: jnp.zeros((), jnp.float32) for k in keys}
    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    new_prefix = []
    for i, kind in enumerate(cfg.prefix_pattern):
        c = cache["prefix"][i] if cache is not None else None
        block_fn = partial(
            apply_block, cfg, kind,
            positions=positions, causal=causal, cache_len=cache_len,
        )
        if cfg.remat:
            block_fn = jax.checkpoint(block_fn, policy=policy)
        x, nc, st = block_fn(params["prefix"][i], x, cache=c, enc_out=enc_out)
        x = sp(x)
        new_prefix.append(nc)
        stats = _merge_stats(stats, st)

    # Remat is per layer: a one-layer period checkpoints the scan body, a
    # longer one each of its layers, so that the backward pass holds one
    # layer's intermediates at a time.
    per_layer_remat = cfg.remat and len(cfg.pattern) > 1

    def body_fn(carry, per_layer):
        x, acc = carry
        p_j, c_j = per_layer
        new_c = []
        for j, kind in enumerate(cfg.pattern):
            block_fn = partial(
                apply_block, cfg, kind,
                positions=positions, causal=causal, cache_len=cache_len,
            )
            if per_layer_remat:
                block_fn = jax.checkpoint(block_fn, policy=policy)
            x, nc, st = block_fn(
                p_j[j], x, cache=c_j[j] if c_j is not None else None,
                enc_out=enc_out,
            )
            x = sp(x)
            new_c.append(nc)
            acc = _merge_stats(acc, st)
        return (x, acc), tuple(new_c) if c_j is not None else None

    if cfg.repeats > 0:
        body_cache = cache["body"] if cache is not None else None
        fn = body_fn
        if cfg.remat and not per_layer_remat:
            fn = jax.checkpoint(body_fn, policy=policy)
        (x, stats), new_body = jax.lax.scan(
            fn, (x, stats), (params["body"], body_cache)
        )
    else:
        new_body = cache["body"] if cache is not None else None

    new_cache = None
    if cache is not None:
        new_cache = {"prefix": new_prefix, "body": new_body}
    if "moe_held_share" in stats:
        stats["moe_held_share"] = stats["moe_held_share"] / n_moe
    return x, new_cache, stats


# ---------------------------------------------------------------------------
# The Model facade
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- init ----------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg)
        ks = jax.random.split(key, 6)
        vp = padded_vocab(cfg)
        params: Params = {
            "embed": (
                jax.random.normal(ks[0], (vp, cfg.d_model), jnp.float32)
                * (1.0 / math.sqrt(cfg.d_model))
            ).astype(dtype),
            "final_norm": init_norm(cfg, dtype),
        }
        if not cfg.tied_embeddings:
            params["lm_head"] = init_linear(ks[1], cfg.d_model, vp, dtype)
        if cfg.is_encoder_decoder:
            enc_cfg = dataclasses.replace(
                cfg,
                n_layers=cfg.n_enc_layers,
                prefix_pattern=(),
                pattern=(("attn", "mlp"),),
            )
            params["encoder"] = _init_stack(ks[2], enc_cfg, cross=False, dtype=dtype)
            params["enc_norm"] = init_norm(cfg, dtype)
            params["decoder"] = _init_stack(ks[3], cfg, cross=True, dtype=dtype)
        else:
            params["decoder"] = _init_stack(ks[3], cfg, cross=False, dtype=dtype)
        if cfg.frontend == "vision":
            params["vision_proj"] = init_linear(ks[4], cfg.d_model, cfg.d_model, dtype)
        return params

    # -- embedding / head ------------------------------------------------------
    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        x = params["embed"][tokens]
        if self.cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(self.cfg.d_model), x.dtype)
        return x

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = apply_norm(cfg, params["final_norm"], x)
        if cfg.tied_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = linear(params["lm_head"], x)
        if cfg.final_logit_softcap > 0.0:
            c = cfg.final_logit_softcap
            logits = jnp.tanh(logits / c) * c
        vp = padded_vocab(cfg)
        if vp != cfg.vocab:  # mask padded rows
            pad_mask = jnp.arange(vp) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e30, logits)
        return logits

    def _encode(self, params: Params, src_embeds: jax.Array) -> jax.Array:
        cfg = self.cfg
        enc_cfg = dataclasses.replace(
            cfg,
            n_layers=cfg.n_enc_layers,
            prefix_pattern=(),
            pattern=(("attn", "mlp"),),
        )
        pos = jnp.arange(src_embeds.shape[1], dtype=jnp.int32)
        x, _, _ = _apply_stack(
            enc_cfg, params["encoder"], src_embeds,
            positions=pos, causal=False, cache=None, cache_len=None,
        )
        return apply_norm(cfg, params["enc_norm"], x)

    # -- training forward -------------------------------------------------------
    def forward(
        self,
        params: Params,
        tokens: jax.Array,  # [B, L]
        *,
        src_embeds: jax.Array | None = None,  # audio frontend (enc-dec)
        patch_embeds: jax.Array | None = None,  # vision frontend (prepended)
    ) -> tuple[jax.Array, jax.Array]:
        """Returns (logits [B, L(+P), Vp], aux_loss)."""
        logits, stats = self._forward(
            params, tokens, src_embeds=src_embeds, patch_embeds=patch_embeds
        )
        return logits, stats["aux"]

    def _forward(
        self,
        params: Params,
        tokens: jax.Array,
        *,
        src_embeds: jax.Array | None,
        patch_embeds: jax.Array | None,
    ) -> tuple[jax.Array, dict[str, jax.Array]]:
        """Returns (logits, the stack's stats: aux loss and MoE counters)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        enc_out = None
        if cfg.is_encoder_decoder:
            assert src_embeds is not None, "enc-dec model needs src_embeds"
            enc_out = self._encode(params, src_embeds.astype(x.dtype))
        if cfg.frontend == "vision":
            assert patch_embeds is not None, "vlm needs patch_embeds"
            pe = linear(params["vision_proj"], patch_embeds.astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, _, stats = _apply_stack(
            cfg, params["decoder"], x,
            positions=pos, causal=True, cache=None, cache_len=None,
            enc_out=enc_out,
        )
        return self._logits(params, x), stats

    # -- loss ----------------------------------------------------------------
    def loss(
        self,
        params: Params,
        batch: dict[str, jax.Array],
        *,
        seq_chunk: int = 1024,
    ) -> tuple[jax.Array, dict[str, jax.Array]]:
        """Next-token CE (labels == -1 ignored) + MoE aux loss; a held
        experts' layer adds its counters to the metrics."""
        cfg = self.cfg
        logits, stats = self._forward(
            params,
            batch["tokens"],
            src_embeds=batch.get("src_embeds"),
            patch_embeds=batch.get("patch_embeds"),
        )
        labels = batch["labels"]
        if cfg.frontend == "vision":  # loss only over the token region
            logits = logits[:, -labels.shape[1] :]
        b, l, vp = logits.shape
        chunk = min(seq_chunk, l)
        total, count = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
        for s in range(0, l, chunk):
            lg = logits[:, s : s + chunk].astype(jnp.float32)
            lb = labels[:, s : s + chunk]
            logp = jax.nn.log_softmax(lg, axis=-1)
            tgt = jnp.take_along_axis(
                logp, jnp.maximum(lb, 0)[..., None], axis=-1
            )[..., 0]
            mask = (lb >= 0).astype(jnp.float32)
            total = total - jnp.sum(tgt * mask)
            count = count + jnp.sum(mask)
        ce = total / jnp.maximum(count, 1.0)
        aux = stats.pop("aux")
        loss = ce + cfg.moe.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux, "tokens": count, **stats}

    # -- serving ----------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg)
        cache: Params = {
            "len": jnp.zeros((), jnp.int32),
            "decoder": _init_stack_cache(cfg, batch, max_len, dtype),
        }
        if cfg.is_encoder_decoder:
            cache["enc_out"] = jnp.zeros(
                (batch, cfg.frontend_len, cfg.d_model), dtype
            )
        return cache

    def prefill(
        self,
        params: Params,
        tokens: jax.Array,  # [B, L0]
        cache: Params,
        *,
        src_embeds: jax.Array | None = None,
        patch_embeds: jax.Array | None = None,
    ) -> tuple[jax.Array, Params]:
        """Consume the prompt; returns (last-position logits, cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        enc_out = cache.get("enc_out")
        if cfg.is_encoder_decoder:
            assert src_embeds is not None
            enc_out = self._encode(params, src_embeds.astype(x.dtype))
            cache = dict(cache, enc_out=enc_out)
        if cfg.frontend == "vision":
            assert patch_embeds is not None
            pe = linear(params["vision_proj"], patch_embeds.astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
        ln = cache["len"]
        pos = ln + jnp.arange(x.shape[1], dtype=jnp.int32)
        x, dec_cache, _ = _apply_stack(
            cfg, params["decoder"], x,
            positions=pos, causal=True,
            cache=cache["decoder"], cache_len=ln, enc_out=enc_out,
        )
        logits = self._logits(params, x[:, -1:])
        new_cache = dict(
            cache, decoder=dec_cache, len=ln + x.shape[1]
        )
        return logits, new_cache

    def decode_step(
        self, params: Params, token: jax.Array, cache: Params
    ) -> tuple[jax.Array, Params]:
        """One decode step: token [B, 1] → (logits [B, 1, Vp], cache)."""
        cfg = self.cfg
        x = self._embed(params, token)
        ln = cache["len"]
        pos = ln + jnp.arange(1, dtype=jnp.int32)
        x, dec_cache, _ = _apply_stack(
            cfg, params["decoder"], x,
            positions=pos, causal=True,
            cache=cache["decoder"], cache_len=ln,
            enc_out=cache.get("enc_out"),
        )
        logits = self._logits(params, x)
        return logits, dict(cache, decoder=dec_cache, len=ln + 1)
