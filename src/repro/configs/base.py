"""Config system for the repro framework.

Every architecture in the assigned pool (plus the paper's own DLRM0) is a
``ModelConfig``.  Configs are plain frozen dataclasses so they hash, compare,
and print cleanly; ``replace`` / ``reduced`` derive smoke-test variants.

Shape points (the four assigned input-shape cells per LM arch) are
``ShapeConfig`` instances; ``repro.configs.registry`` binds archs to shapes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------

# kind: which step function the cell lowers.
#   "train"   -> train_step   (forward + backward + optimizer update)
#   "prefill" -> serve_prefill (forward over full sequence, builds KV cache)
#   "decode"  -> serve_decode  (one new token against a seq_len KV cache/state)
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                 # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int

    def __post_init__(self):
        assert self.kind in ("train", "prefill", "decode"), self.kind


TRAIN_4K = ShapeConfig("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524288, 1)

LM_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


# ---------------------------------------------------------------------------
# Attention / block variants
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttentionConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False            # qwen2
    logit_softcap: Optional[float] = None   # gemma2: 50.0
    # Sliding-window pattern: window size for local layers; None = all global.
    sliding_window: Optional[int] = None
    # every `global_every`-th layer is global; others local (gemma2: 2).
    # 0 means all layers global.
    global_every: int = 0
    rope_theta: float = 10000.0
    # attention logit scale override; None -> 1/sqrt(head_dim)
    attn_scale: Optional[float] = None
    # RMSNorm over each query and key head (learned, head_dim wide), applied
    # before RoPE (lfm2)
    qk_norm: bool = False


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_ffw: int                    # per-expert FFN hidden dim
    num_shared_experts: int = 0        # kimi-k2 style shared expert(s)
    shared_ffw: int = 0
    router_softcap: Optional[float] = None
    # first `dense_layers` layers use a dense FFN instead of MoE (deepseek/kimi style)
    dense_layers: int = 0
    dense_ffw: int = 0
    # router scoring: "softmax" over all experts, or "sigmoid" per expert
    # (lfm2); with `expert_bias` a learned per-expert bias is added to the
    # scores for the top-k choice only, never to the gates
    score: str = "softmax"
    expert_bias: bool = False
    # experts this chip holds: `held_experts` of them from `first_expert`
    # (0 = all `num_experts`).  The router still scores all `num_experts`;
    # the layer computes, dropless, the part of the result its own experts
    # give (models/moe.py `moe_held`)
    held_experts: int = 0
    first_expert: int = 0


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int                     # N (ssm_state)
    head_dim: int = 64                 # P per SSD head
    num_heads: int = 0                 # 0 -> derive: d_inner // head_dim
    expand: int = 2                    # d_inner = expand * d_model
    chunk: int = 256                   # SSD chunk length
    conv_width: int = 4


# ---------------------------------------------------------------------------
# Embedding / DLRM (SparseCore) configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EmbeddingTableConfig:
    name: str
    vocab_size: int
    dim: int
    # average number of categorical values per example (1 = univalent)
    avg_valency: float = 1.0
    max_valency: int = 1
    combiner: str = "sum"              # "sum" | "mean"

    def __post_init__(self):
        assert self.combiner in ("sum", "mean")


@dataclass(frozen=True)
class DLRMConfig:
    tables: Tuple[EmbeddingTableConfig, ...]
    # dense tower
    bottom_mlp: Tuple[int, ...] = (512, 256, 128)
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
    dense_features: int = 13
    interaction: str = "dot"           # "dot" | "cat"


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm", "dlrm")
MIXERS = ("attention", "conv")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # one of FAMILIES
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    dlrm: Optional[DLRMConfig] = None

    norm: str = "rmsnorm"              # "rmsnorm" | "layernorm" | "nonparam_ln"
    act: str = "silu"                  # "silu" | "gelu" (glu applied per ffn_glu)
    ffn_glu: bool = True               # gated FFN (SwiGLU/GeGLU)
    tie_embeddings: bool = False
    final_logit_softcap: Optional[float] = None   # gemma2: 30.0
    post_norm: bool = False            # gemma2 post-layer norms
    embed_scale: bool = False          # gemma2 scales embeddings by sqrt(d_model)
    max_seq_len: int = 131072

    # encoder-decoder (whisper): encoder layer count; 0 = decoder-only
    encoder_layers: int = 0
    encoder_seq_reduction: int = 1     # conv frontend downsampling (stubbed)

    # vlm: number of prefix patch positions fed as stub embeddings
    vision_prefix: int = 0
    vision_dim: int = 0

    # hybrid: run attention and SSM in parallel per layer (hymba)
    parallel_heads: bool = False

    # per-layer mixer schedule, one of MIXERS per layer ("conv" is the gated
    # short convolution of models/ssm.py); empty = the family's own mixer in
    # every layer
    mixers: Tuple[str, ...] = ()
    conv_width: int = 3                # short-convolution kernel length
    norm_eps: float = 1e-6             # rmsnorm epsilon

    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        # a schedule read from JSON arrives as a list; keep the config
        # hashable
        object.__setattr__(self, "mixers", tuple(self.mixers))

    # --- derived helpers ------------------------------------------------
    @property
    def head_dim(self) -> int:
        assert self.attention is not None
        return self.attention.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        from repro.models.counting import param_count
        return param_count(self)

    def active_param_count(self) -> int:
        from repro.models.counting import active_param_count
        return active_param_count(self)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is the short convolution (they keep conv
        state in serving instead of KV)."""
        return sum(1 for m in self.mixers if m == "conv")

    def supports_long_context(self) -> bool:
        """True if decode at 500k context is sub-quadratic (SSM/hybrid/local-attn)."""
        if self.family == "ssm":
            return True
        if self.family == "hybrid":
            return True
        return False

    def has_decoder(self) -> bool:
        return True  # all assigned archs have an autoregressive decoder


# ---------------------------------------------------------------------------
# Run-level config (parallelism + training knobs), consumed by launch/*
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelConfig:
    # axis names must match the mesh axes
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: Optional[str] = None     # None on single-pod meshes
    fsdp: bool = True                  # shard params over data axis (ZeRO-3 style)
    zero1: bool = True                 # shard optimizer state over data axis
    tensor_parallel: bool = True       # shard heads/ffn/vocab over model axis
    expert_parallel: bool = True       # shard experts over model axis (MoE)
    sequence_parallel: bool = True     # shard long sequences / KV over model axis
    # Table 3 hyperparameter: activation/weight partitioning dimensionality
    activation_partition: str = "1d"   # "1d" | "2d"
    weight_partition: str = "1d"       # "1d" | "2d"
    pipeline_stages: int = 1           # >1 maps pipeline onto pod axis
    remat: str = "block"               # "none" | "block" | "full"
    grad_compression: str = "none"     # "none" | "int8" | "topk"
    overlap_decomposition: int = 1     # >1: split matmuls to overlap collectives
    use_sparse_embed: bool = True      # SparseCore-style vocab embedding path
    # §Perf: compute the LM loss in sequence chunks so the (tokens x vocab)
    # logits tensor never materialises; lets grad-accumulation drop to 1-2
    # steps and with it the per-microbatch FSDP weight regathers.
    xent_chunk: int = 0                # 0 = off (materialise full logits)
    # §Perf: cast FSDP-gathered weights to bf16 BEFORE the all-gather
    bf16_fsdp_gather: bool = False
    # §Perf: attention implementation. "qchunked" scans a static list of
    # reachable (q-chunk, kv-chunk) pairs: causal skips the upper triangle,
    # static sliding windows keep only the diagonal band.
    attn_impl: str = "blocked"         # "blocked" | "qchunked"
    # §Perf: SparseCore embedding exchange knobs
    emb_wire_bf16: bool = False        # bf16 vectors on the ICI wire
    emb_capacity_factor: float = 2.0   # all-to-all send slot provisioning
    emb_method: str = "auto"           # "auto" | "a2a" | "psum"
    emb_pipeline: bool = True          # fused multi-group pipelined executor


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adam"                 # "adam" | "adafactor" | "sgd"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    state_dtype: str = "float32"       # "bfloat16" for the 1T config
    warmup_steps: int = 100


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)
