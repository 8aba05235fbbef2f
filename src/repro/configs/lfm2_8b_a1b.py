"""lfm2-8b-a1b [moe] — 24L d_model=2048, 18 gated short-conv + 6 GQA layers
(32H, kv=8, head_dim 64), vocab=65536.

The first 2 layers have a dense SwiGLU FFN of 7168; the other 22 an MoE of
32 SwiGLU experts of 1792, top-4, sigmoid router with a selection-only
expert bias, gates normalised to sum 1.  q/k RMSNorm before RoPE.
[hf:LiquidAI/LFM2-8B-A1B config.json]
"""
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig

C, A = "conv", "attention"
# config.json "layer_types": attention at layers 2, 6, 10, 14, 18 and 21
MIXERS = (C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, A,
          C, C)

CONFIG = ModelConfig(
    name="lfm2-8b-a1b",
    family="moe",
    num_layers=24,
    d_model=2048,
    d_ff=7168,                   # the dense layers' FFN width
    vocab_size=65536,
    attention=AttentionConfig(
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=1_000_000.0,
        qk_norm=True,
    ),
    moe=MoEConfig(
        num_experts=32,
        top_k=4,
        expert_ffw=1792,
        dense_layers=2,
        dense_ffw=7168,
        score="sigmoid",
        expert_bias=True,
    ),
    mixers=MIXERS,
    conv_width=3,
    norm="rmsnorm",
    norm_eps=1e-5,
    act="silu",
    ffn_glu=True,
    tie_embeddings=True,
    max_seq_len=128000,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        num_layers=6,
        d_model=64,
        d_ff=96,
        vocab_size=512,
        attention=AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16,
                                  rope_theta=1_000_000.0, qk_norm=True),
        moe=MoEConfig(num_experts=16, top_k=4, expert_ffw=32,
                      dense_layers=2, dense_ffw=96, score="sigmoid",
                      expert_bias=True),
        mixers=(C, C, A, C, C, A),
        max_seq_len=128,
    )
