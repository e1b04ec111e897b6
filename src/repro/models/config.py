"""Model configuration — one dataclass covering all 10 assigned families.

A model is a stack of *blocks*.  ``prefix_pattern`` lists non-repeating
leading blocks (e.g. DeepSeek-MoE's dense layer 0); ``pattern`` is the
repeating unit (e.g. Gemma-2's ``(local, global)`` pair, Jamba's 8-layer
Mamba/attention/MoE period); ``repeats × len(pattern) + len(prefix_pattern)``
must equal ``n_layers``.  Blocks of the same pattern position are stacked and
scanned (`jax.lax.scan`) so the lowered HLO stays small for 80-layer models.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal

BlockKind = Literal[
    "attn",  # self-attention (causal or bidirectional per model kind)
    "attn_local",  # sliding-window self-attention
    "mlp",
    "moe",
    "mamba",
    "mlstm",
    "slstm",
]

Activation = Literal["silu_glu", "gelu_glu", "relu_sq", "gelu"]
NormKind = Literal["rmsnorm", "layernorm"]


@dataclass(frozen=True)
class MoECfg:
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    n_shared: int = 0  # always-active shared experts (DeepSeek-MoE)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01  # load-balance loss weight
    # Expert parallelism's share, as one chip holds it: the router scores
    # ``n_routed`` experts (0 -> ``n_experts``), of which this layer holds
    # the ``n_experts`` from ``first_expert`` on and computes only their
    # part of the result.  ``dropless`` computes every routed choice that
    # lands on a held expert as a grouped product, with no capacity.
    n_routed: int = 0
    first_expert: int = 0
    dropless: bool = False

    @property
    def router_width(self) -> int:
        return self.n_routed or self.n_experts


@dataclass(frozen=True)
class SSMCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    chunk: int = 1024  # selective-scan chunk length (memory/HLO trade-off)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # Block layout.  Each layer is "<mixer>+<ffn>" where mixer is one of
    # attn/attn_local/mamba/mlstm/slstm and ffn one of mlp/moe/none.
    # pattern entries are (mixer, ffn) pairs.
    prefix_pattern: tuple[tuple[str, str], ...] = ()
    pattern: tuple[tuple[str, str], ...] = (("attn", "mlp"),)

    # Attention details
    use_rope: bool = True  # Jamba: attention without positional encoding
    rope_theta: float = 10_000.0
    # RoPE of attn_local layers: its own theta (0 -> rope_theta), never YaRN.
    rope_local_theta: float = 0.0
    # YaRN on the attn layers (yarn_factor 0 -> plain RoPE): the published
    # blend of extrapolated and interpolated frequencies between the
    # correction dimensions of beta_fast/beta_slow at the original length,
    # with cos and sin scaled by the attention factor.
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    qkv_bias: bool = False
    sliding_window: int = 0  # used by attn_local
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    post_block_norm: bool = False  # Gemma-2 pre+post norms

    # FFN / embeddings
    activation: Activation = "silu_glu"
    norm: NormKind = "rmsnorm"
    tied_embeddings: bool = False
    embed_scale: bool = False  # Gemma-style sqrt(d) embedding multiplier

    moe: MoECfg = field(default_factory=MoECfg)
    ssm: SSMCfg = field(default_factory=SSMCfg)

    # Encoder-decoder (seamless-m4t): n_enc_layers encoder blocks with
    # bidirectional attention; decoder blocks gain cross-attention.
    is_encoder_decoder: bool = False
    n_enc_layers: int = 0
    cross_attn: bool = False  # set on decoder blocks internally

    # Modality frontend stub: if set, the model consumes precomputed
    # embeddings of this length prepended (vlm) or as encoder input (audio).
    frontend: Literal["none", "vision", "audio"] = "none"
    frontend_len: int = 256  # patches / audio frames provided by input_specs

    # Numerics
    dtype: str = "bfloat16"
    remat: bool = True

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        n_body = self.n_layers - len(self.prefix_pattern)
        if self.pattern and n_body % len(self.pattern) != 0:
            raise ValueError(
                f"{self.name}: {n_body} body layers not divisible by "
                f"pattern of {len(self.pattern)}"
            )
        if not self.pattern and n_body != 0:
            raise ValueError(f"{self.name}: empty pattern with {n_body} body layers")

    # -- derived -------------------------------------------------------------
    @property
    def repeats(self) -> int:
        if not self.pattern:
            return 0
        return (self.n_layers - len(self.prefix_pattern)) // len(self.pattern)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def layer_seq(self) -> tuple[tuple[str, str], ...]:
        """The full per-layer (mixer, ffn) sequence."""
        return self.prefix_pattern + self.pattern * self.repeats

    # -- parameter counting (used for MODEL_FLOPS and roofline) -------------
    def _mixer_params(self, mixer: str) -> int:
        d = self.d_model
        if mixer in ("attn", "attn_local"):
            p = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            if self.qkv_bias:
                p += self.q_dim + 2 * self.kv_dim
            return p
        if mixer == "mamba":
            di = self.ssm.expand * d
            ds = self.ssm.d_state
            dtr = max(1, d // 16)  # dt_rank
            return (
                d * 2 * di  # in_proj (x, z)
                + self.ssm.d_conv * di + di  # depthwise conv w + b
                + di * (dtr + 2 * ds)  # x_proj → (dt, B, C)
                + dtr * di + di  # dt_proj + bias
                + di * ds + di  # A_log, D
                + di * d  # out_proj
            )
        if mixer == "mlstm":
            # qkv + gates (i, f per head) + out
            return d * 3 * self.q_dim + 2 * d * self.n_heads + self.q_dim * d
        if mixer == "slstm":
            # recurrent cell: 4 gates × (input + recurrent) projections
            return 8 * d * d + 4 * d
        raise ValueError(mixer)

    def _ffn_params(self, ffn: str) -> int:
        d = self.d_model
        if ffn == "none":
            return 0
        if ffn == "mlp":
            mult = 3 if self.activation.endswith("_glu") else 2
            return mult * d * self.d_ff
        if ffn == "moe":
            m = self.moe
            mult = 3  # experts are gated MLPs
            routed = m.n_experts * mult * d * m.d_expert  # held here
            shared = m.n_shared * mult * d * m.d_expert
            router = d * m.router_width
            return routed + shared + router
        if ffn == "dense0":  # DeepSeek layer-0 dense MLP (d_ff stored in d_ff)
            return 3 * self.d_model * self.d_ff
        raise ValueError(ffn)

    def param_count(self) -> int:
        d = self.d_model
        n = self.vocab * d  # embedding
        if not self.tied_embeddings:
            n += self.vocab * d  # lm head
        layers = self.layer_seq()
        if self.is_encoder_decoder:
            # encoder self-attn blocks + decoder (self + cross) blocks
            enc = self.n_enc_layers * (
                self._mixer_params("attn") + self._ffn_params("mlp") + 2 * d
            )
            dec = self.n_layers * (
                2 * self._mixer_params("attn") + self._ffn_params("mlp") + 3 * d
            )
            return n + enc + dec + d
        for mixer, ffn in layers:
            n += self._mixer_params(mixer) + self._ffn_params(ffn)
            n += 2 * d if ffn != "none" else d  # norms
            if self.post_block_norm:
                n += 2 * d
        n += d  # final norm
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k + shared experts only).

        Of the top_k choices over the router's width, a held share of
        ``n_experts / router_width`` lands here on average."""
        if not any(f == "moe" for _, f in self.layer_seq()):
            return self.param_count()
        d, m = self.d_model, self.moe
        inactive_experts = m.n_experts - m.top_k * m.n_experts / m.router_width
        per_moe_layer = inactive_experts * 3 * d * m.d_expert
        n_moe = sum(1 for _, f in self.layer_seq() if f == "moe")
        return round(self.param_count() - n_moe * per_moe_layer)


def unrolled_variant(cfg: ModelConfig, *, ssm_chunk: int | None = None) -> ModelConfig:
    """All layers in ``prefix_pattern`` (no scan) — used by the dry-run so
    ``cost_analysis`` / HLO collective parsing see every layer (a scanned
    body is a while-loop whose cost is counted once)."""
    kw = dict(prefix_pattern=cfg.layer_seq(), pattern=())
    if ssm_chunk is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, chunk=ssm_chunk)
    return dataclasses.replace(cfg, **kw)


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """A reduced same-family config for CPU smoke tests."""
    pat = cfg.pattern
    prefix = cfg.prefix_pattern
    n_layers = len(prefix) + len(pat)  # one repeat of the pattern
    moe = cfg.moe
    if moe.n_experts and moe.router_width > moe.n_experts:
        # A held share of a wider router: 8 routed, the share's fraction
        # of them held (at least 2), from the same relative offset.
        held = max(2, 8 * moe.n_experts // moe.router_width)
        moe = dataclasses.replace(
            moe,
            n_routed=8,
            n_experts=held,
            first_expert=min(8 - held, 8 * moe.first_expert // moe.router_width),
            top_k=min(moe.top_k, 2),
            d_expert=32,
        )
    elif moe.n_experts:
        n = max(4, moe.top_k + 1) if moe.n_experts > 4 else moe.n_experts
        moe = dataclasses.replace(
            moe,
            n_experts=n,
            n_routed=0,
            top_k=min(moe.top_k, 2),
            d_expert=32,
        )
    head_dim = 16
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=128 if cfg.d_ff else 0,
        vocab=512,
        n_enc_layers=min(cfg.n_enc_layers, 2),
        sliding_window=min(cfg.sliding_window, 16) if cfg.sliding_window else 0,
        moe=moe,
        ssm=dataclasses.replace(cfg.ssm, chunk=16),
        frontend_len=8 if cfg.frontend != "none" else cfg.frontend_len,
        remat=False,
        dtype="float32",
    )
