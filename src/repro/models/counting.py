"""Analytic parameter counts for MODEL_FLOPS = 6*N*D (§Roofline).

These count *trainable* parameters from the config alone so the roofline's
"useful FLOPs" term never depends on actually materialising weights.
"""
from __future__ import annotations

from repro.configs.base import ModelConfig


def _attn_params(cfg: ModelConfig) -> int:
    a = cfg.attention
    if a is None:
        return 0
    d = cfg.d_model
    q = d * a.num_heads * a.head_dim
    kv = 2 * d * a.num_kv_heads * a.head_dim
    o = a.num_heads * a.head_dim * d
    bias = (a.num_heads + 2 * a.num_kv_heads) * a.head_dim if a.qkv_bias else 0
    return q + kv + o + bias


def _ffn_params(d_model: int, d_ff: int, glu: bool) -> int:
    if d_ff == 0:
        return 0
    n_in = 2 if glu else 1
    return n_in * d_model * d_ff + d_ff * d_model


def _ssm_params(cfg: ModelConfig) -> int:
    s = cfg.ssm
    if s is None:
        return 0
    d = cfg.d_model
    d_inner = s.expand * d
    nheads = s.num_heads or (d_inner // s.head_dim)
    # in_proj: [z, x, B, C, dt] (mamba2 fused projection)
    in_proj = d * (2 * d_inner + 2 * s.state_dim + nheads)
    conv = s.conv_width * (d_inner + 2 * s.state_dim)
    extras = 3 * nheads               # A_log, D, dt_bias
    out_proj = d_inner * d
    norm = d_inner                    # gated RMSNorm
    return in_proj + conv + extras + out_proj + norm


def _norm_params(cfg: ModelConfig) -> int:
    if cfg.norm == "nonparam_ln":
        return 0
    scale = cfg.d_model
    if cfg.norm == "layernorm":
        scale *= 2
    return scale


def _moe_layer_params(cfg: ModelConfig) -> int:
    m = cfg.moe
    d = cfg.d_model
    router = d * m.num_experts
    experts = m.num_experts * _ffn_params(d, m.expert_ffw, cfg.ffn_glu)
    shared = m.num_shared_experts * _ffn_params(d, m.shared_ffw, cfg.ffn_glu)
    return router + experts + shared


def _moe_active_layer_params(cfg: ModelConfig) -> int:
    m = cfg.moe
    d = cfg.d_model
    router = d * m.num_experts
    experts = m.top_k * _ffn_params(d, m.expert_ffw, cfg.ffn_glu)
    shared = m.num_shared_experts * _ffn_params(d, m.shared_ffw, cfg.ffn_glu)
    return router + experts + shared


def _decoder_layer_params(cfg: ModelConfig, layer_idx: int, active: bool) -> int:
    p = 0
    n_norms = 2
    if cfg.family in ("dense", "audio", "vlm"):
        p += _attn_params(cfg) + _ffn_params(cfg.d_model, cfg.d_ff, cfg.ffn_glu)
    elif cfg.family == "moe":
        if cfg.mixers and cfg.mixers[layer_idx] == "conv":
            d = cfg.d_model          # gated short conv: in/out proj + taps
            p += 4 * d * d + cfg.conv_width * d
        else:
            p += _attn_params(cfg)
        if layer_idx < cfg.moe.dense_layers:
            p += _ffn_params(cfg.d_model, cfg.moe.dense_ffw, cfg.ffn_glu)
        else:
            p += (_moe_active_layer_params(cfg) if active
                  else _moe_layer_params(cfg))
    elif cfg.family == "ssm":
        p += _ssm_params(cfg)
        n_norms = 1
    elif cfg.family == "hybrid":
        p += _attn_params(cfg) + _ssm_params(cfg)
        p += _ffn_params(cfg.d_model, cfg.d_ff, cfg.ffn_glu)
    if cfg.post_norm:
        n_norms *= 2
    p += n_norms * _norm_params(cfg)
    return p


def _dlrm_params(cfg: ModelConfig) -> int:
    d = cfg.dlrm
    total = 0
    for t in d.tables:
        total += t.vocab_size * t.dim
    # bottom tower
    prev = d.dense_features
    for h in d.bottom_mlp:
        total += prev * h + h
        prev = h
    # interaction output width (cat): bottom out + sum of table dims
    inter = prev + sum(t.dim for t in d.tables)
    prev = inter
    for h in d.top_mlp:
        total += prev * h + h
        prev = h
    return total


def _dlrm_dense_params(cfg: ModelConfig) -> int:
    d = cfg.dlrm
    total = 0
    prev = d.dense_features
    for h in d.bottom_mlp:
        total += prev * h + h
        prev = h
    inter = prev + sum(t.dim for t in d.tables)
    prev = inter
    for h in d.top_mlp:
        total += prev * h + h
        prev = h
    return total


def param_count(cfg: ModelConfig) -> int:
    if cfg.family == "dlrm":
        return _dlrm_params(cfg)
    total = cfg.vocab_size * cfg.d_model            # token embedding
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model       # LM head
    if cfg.vision_prefix:
        total += cfg.vision_dim * cfg.d_model       # patch projection
    for i in range(cfg.num_layers):
        total += _decoder_layer_params(cfg, i, active=False)
    # encoder stack (whisper): self-attn + ffn per layer, plus decoder cross-attn
    if cfg.encoder_layers:
        enc_layer = _attn_params(cfg) + _ffn_params(cfg.d_model, cfg.d_ff, cfg.ffn_glu)
        enc_layer += 2 * _norm_params(cfg)
        total += cfg.encoder_layers * enc_layer
        total += cfg.num_layers * (_attn_params(cfg) + _norm_params(cfg))  # cross-attn
    total += _norm_params(cfg)                      # final norm
    return total


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top_k experts only)."""
    if cfg.family != "moe":
        return param_count(cfg)
    total = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model
    for i in range(cfg.num_layers):
        total += _decoder_layer_params(cfg, i, active=True)
    total += _norm_params(cfg)
    return total


def embedding_param_count(cfg: ModelConfig) -> int:
    if cfg.family == "dlrm":
        return sum(t.vocab_size * t.dim for t in cfg.dlrm.tables)
    return cfg.vocab_size * cfg.d_model
