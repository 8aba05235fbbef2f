"""Family-dispatching model API.

Every architecture exposes the same five functions through this module:
    init_params(cfg, key)                 -> params
    forward(cfg, p, batch, ctx)           -> (logits, aux)        [train]
    prefill(cfg, p, batch, ctx, max_len)  -> (last_logits, cache) [serve]
    decode_step(cfg, p, cache, tokens, ctx) -> (logits, cache)    [serve]
    make_batch(cfg, shape, key) / batch_specs(cfg, shape)         [data]

batch_specs returns ShapeDtypeStructs (no allocation) for the dry-run.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as TF
from repro.models import whisper as WH
from repro.parallel.context import LOCAL, ParallelContext


def init_params(cfg: ModelConfig, key, ctx: ParallelContext = LOCAL):
    if cfg.family == "audio":
        return WH.init_params(cfg, key)
    if cfg.family == "dlrm":
        from repro.models import dlrm as DL
        return DL.init_params(cfg, key, num_shards=ctx.model_axis_size)
    return TF.init_params(cfg, key)


def forward(cfg: ModelConfig, p, batch, ctx: ParallelContext = LOCAL, **kw):
    if cfg.family == "audio":
        return WH.forward(cfg, p, batch, ctx, **kw)
    if cfg.family == "dlrm":
        from repro.models import dlrm as DL
        return DL.forward(cfg, p, batch, ctx, **kw)
    return TF.forward(cfg, p, batch, ctx, **kw)


def prefill(cfg: ModelConfig, p, batch, ctx: ParallelContext = LOCAL,
            *, max_len: Optional[int] = None, **kw):
    if cfg.family == "audio":
        return WH.prefill(cfg, p, batch, ctx, max_len=max_len, **kw)
    return TF.prefill(cfg, p, batch, ctx, max_len=max_len, **kw)


def decode_step(cfg: ModelConfig, p, cache, tokens,
                ctx: ParallelContext = LOCAL, **kw):
    if cfg.family == "audio":
        return WH.decode_step(cfg, p, cache, tokens, ctx, **kw)
    return TF.decode_step(cfg, p, cache, tokens, ctx, **kw)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               enc_len: Optional[int] = None):
    if cfg.family == "audio":
        return WH.init_cache(cfg, batch, max_len, enc_len or max_len)
    return TF.init_cache(cfg, batch, max_len)


# -- serve fast path --------------------------------------------------------
# Incremental continuous batching: admit ONE request by prefilling ONLY its
# slot (prefill_slot), then advance every live slot `num_steps` tokens per
# dispatch with per-slot cache lengths (decode_n).  The whisper enc-dec stack
# has its own cache layout and stays on the legacy full-batch path.


def prefill_slot(cfg: ModelConfig, p, batch, cache, slot,
                 ctx: ParallelContext = LOCAL, *,
                 max_len: Optional[int] = None, **kw):
    """Prefill newly admitted request(s) and write their KV/state rows into
    batch rows ``slot`` of the live ``cache`` — active slots are never
    recomputed.  ``batch`` holds n prompts and ``slot`` n slot indices (a
    scalar admits one); a whole admission wave is one dispatch.
    Returns (last_logits (n, V), cache)."""
    if cfg.family == "audio":
        raise NotImplementedError(
            "incremental admission is transformer-cache only; serve whisper "
            "through the legacy full-batch path")
    logits, slot_cache = TF.prefill(cfg, p, batch, ctx, max_len=max_len,
                                    **kw)
    return logits, TF.cache_insert(cache, slot_cache, slot)


def cache_insert(cache, slot_cache, slot):
    return TF.cache_insert(cache, slot_cache, slot)


def decode_n(cfg: ModelConfig, p, cache, tokens, seq_lens, budget,
             ctx: ParallelContext = LOCAL, *, num_steps: int, **kw):
    """Multi-step on-device decode with per-slot lengths/budgets; see
    transformer.decode_n (``moe_load=True`` also returns each step's
    held-expert load).  Pass ``tables=(B, nb)`` to decode over a pooled
    prefix-shared KV cache (init_kv_pool) instead of per-slot rows."""
    if cfg.family == "audio":
        raise NotImplementedError(
            "decode_n is transformer-cache only; serve whisper through the "
            "legacy per-token path")
    return TF.decode_n(cfg, p, cache, tokens, seq_lens, budget, ctx,
                       num_steps=num_steps, **kw)


# -- pooled prefix-shared KV (serve/kvpool.py block tables) ------------------


def has_pooled_layout(cfg: ModelConfig) -> bool:
    """Whether ``cfg`` can serve on the pooled KV layout."""
    return TF.has_pooled_layout(cfg)


def _require_pool(cfg: ModelConfig) -> None:
    if not has_pooled_layout(cfg):
        raise NotImplementedError(
            f"{cfg.name}: pooled KV serves dense attention stacks and "
            f"per-layer mixer schedules, not the {cfg.family} family")


def init_kv_pool(cfg: ModelConfig, num_blocks: int, block_size: int, **kw):
    """Pooled KV cache (Ls, NB, bs, KH, hd) for the attention layers, and
    per-slot conv state for a schedule's conv layers (pass ``slots``); see
    transformer.init_kv_pool."""
    _require_pool(cfg)
    return TF.init_kv_pool(cfg, num_blocks, block_size, **kw)


def prefill_suffix(cfg: ModelConfig, p, cache, tokens, start, valid, tables,
                   ctx: ParallelContext = LOCAL, **kw):
    """Fixed-width suffix prefill over a pooled KV cache: rows resume at
    logical position ``start`` with ``valid`` fresh tokens, KV lands in the
    blocks named by ``tables``; see transformer.prefill_suffix."""
    _require_pool(cfg)
    return TF.prefill_suffix(cfg, p, cache, tokens, start, valid, tables,
                             ctx, **kw)


# ---------------------------------------------------------------------------
# Batches: concrete (smoke/tests) and spec-only (dry-run)
# ---------------------------------------------------------------------------

def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.family == "vlm":
        return max(2, seq_len - cfg.vision_prefix)
    return seq_len


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, T = shape.global_batch, shape.seq_len
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    if cfg.family == "dlrm":
        from repro.models import dlrm as DL
        return DL.batch_specs(cfg, shape)
    if shape.kind == "decode":
        return {"tokens": sds((B,), i32)}
    if cfg.family == "audio":
        enc, dec = WH.split_seq(cfg, T)
        out = {"frames": sds((B, enc, cfg.d_model), f32),
               "tokens": sds((B, dec), i32)}
        if shape.kind == "train":
            out["labels"] = sds((B, dec), i32)
        return out
    out = {"tokens": sds((B, _text_len(cfg, T)), i32)}
    if cfg.family == "vlm":
        out["patches"] = sds((B, cfg.vision_prefix, cfg.vision_dim), f32)
    if shape.kind == "train":
        out["labels"] = sds((B, _text_len(cfg, T)), i32)
        if cfg.family == "vlm":
            # labels cover only the text region; prefix is masked in-loss
            pass
    return out


def make_batch(cfg: ModelConfig, shape: ShapeConfig, key) -> Dict[str, Any]:
    """Concrete random batch matching batch_specs."""
    if cfg.family == "dlrm":
        from repro.models import dlrm as DL
        return DL.make_batch(cfg, shape, key)
    specs = batch_specs(cfg, shape)
    ks = jax.random.split(key, len(specs))
    out = {}
    for (name, spec), k in zip(sorted(specs.items()), ks):
        if spec.dtype == jnp.int32:
            hi = max(cfg.vocab_size, 2) if cfg.family != "dlrm" else 2
            out[name] = jax.random.randint(k, spec.shape, 0, hi, jnp.int32)
        else:
            out[name] = jax.random.normal(k, spec.shape, jnp.float32)
    return out


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct pytree for the decode cache of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        enc, _ = WH.split_seq(cfg, S)
        fn = lambda: WH.init_cache(cfg, B, S, enc)
    else:
        fn = lambda: TF.init_cache(cfg, B, S)
    return jax.eval_shape(fn)
