"""mellum2-12b-a2.5b [moe, hybrid attention] — 64 experts top-8, 3:1 windowed.

28L, d_model=2304, 32H (GQA kv=4, head_dim=128), vocab=98304, untied
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].  ``layer_types``
repeats sliding, sliding, sliding, full with a 1024-token window; every MLP
is sparse: 64 experts of width 896, top-8 with renormalised weights
(``norm_topk_prob``), no shared expert, routed without drops.  RoPE theta
500,000 on every layer; the full layers add YaRN (factor 16 over an
original 8,192 positions, beta_fast 32, beta_slow 1, attention factor
1.2772588722239782).  ``d_ff`` is the config's dense width, which no layer
uses.
"""

from repro.models import ModelConfig, MoECfg

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=7168,
    vocab=98304,
    pattern=(
        ("attn_local", "moe"),
        ("attn_local", "moe"),
        ("attn_local", "moe"),
        ("attn", "moe"),
    ),
    sliding_window=1024,
    rope_theta=500_000.0,
    rope_local_theta=500_000.0,
    yarn_factor=16.0,
    yarn_original_len=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    moe=MoECfg(n_experts=64, top_k=8, d_expert=896, dropless=True),
)
