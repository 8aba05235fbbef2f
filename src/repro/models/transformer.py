"""Unified decoder stack for the assigned LM families.

Families handled here: dense (gemma2/olmo/qwen2/mistral-nemo), moe (kimi-k2,
qwen3-moe), ssm (mamba2), hybrid (hymba), vlm (internvl2 — stub patch
embeddings prepended).  whisper (enc-dec) wraps this in models/whisper.py.

Design notes
  * Layers are stacked and executed with ``jax.lax.scan`` so the lowered HLO
    is one layer body + a loop — essential to keep 512-device dry-run compiles
    tractable and matches production JAX LM frameworks.
  * Heterogeneous layers (gemma2 local/global alternation, hymba's sparse
    global layers) are expressed with per-layer *data* (window sizes as an
    int32 array scanned as xs), never per-layer Python branches.
  * MoE layers with a dense prefix (kimi-k2) unroll the prefix outside the
    scan and scan the uniform MoE remainder.
  * A per-layer mixer schedule that does not repeat (lfm2: short-conv and
    attention layers in an irregular order, a dense-FFN prefix, then MoE) is
    cut into runs of like layers (`segments`); each run's weights are
    stacked and scanned, the runs unrolled in order.  Such a model serves
    on the pooled layout only: KV for its attention layers in the block
    pool, and per slot the short convolutions' state beside it.
  * Every model with the pooled layout prefills and decodes through one
    layer loop over its runs (`_pooled_layers`), the pool in the scan's
    carry, read and written in place.  The dense per-slot cache is stacked
    over layers and scanned as xs/ys.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MIXERS, ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import quant as Q
from repro.models import ssm as SSM
from repro.parallel.context import LOCAL, ParallelContext, hint

GLOBAL_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# per-layer window schedule
# ---------------------------------------------------------------------------

def window_schedule(cfg: ModelConfig) -> np.ndarray:
    """int32 (num_layers,): attention window per layer (GLOBAL_WINDOW = full)."""
    a = cfg.attention
    n = cfg.num_layers
    if a is None:
        return np.full((n,), GLOBAL_WINDOW, np.int32)
    if a.sliding_window is None or a.global_every == 0:
        return np.full((n,), GLOBAL_WINDOW, np.int32)
    win = np.full((n,), a.sliding_window, np.int32)
    for l in range(n):
        if l % a.global_every == a.global_every - 1:
            win[l] = GLOBAL_WINDOW
    return win


def num_moe_layers(cfg: ModelConfig) -> int:
    return cfg.num_layers - (cfg.moe.dense_layers if cfg.moe else 0)


def attn_group_size(cfg: ModelConfig) -> int:
    """Layer-group size for static-window scanning (§Perf qchunked path):
    the local/global pattern repeats every `global_every` layers, so scanning
    groups of that size gives every position a STATIC window."""
    a = cfg.attention
    n = num_moe_layers(cfg) if cfg.family == "moe" else cfg.num_layers
    if (a and a.sliding_window and a.global_every > 0
            and n % a.global_every == 0):
        return a.global_every
    return 1


def can_qchunk(cfg: ModelConfig) -> bool:
    """qchunked attention needs static windows: either no sliding windows at
    all, or a local/global pattern that tiles the stack exactly."""
    a = cfg.attention
    if a is None:
        return True
    if a.sliding_window is None or a.global_every == 0:
        return True
    n = num_moe_layers(cfg) if cfg.family == "moe" else cfg.num_layers
    return n % a.global_every == 0


def static_window_for(cfg: ModelConfig, idx_in_group: int, group: int):
    a = cfg.attention
    if a is None or a.sliding_window is None or a.global_every == 0:
        return None
    if group == 1:
        return None
    return None if idx_in_group == group - 1 else a.sliding_window


# ---------------------------------------------------------------------------
# per-layer mixer schedule: runs of like layers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    """A run of consecutive layers of one kind, scanned over its stacked
    weights.  ``slot`` is the run's first index among the layers of its
    mixer: its KV pool layer, or its row of the conv state."""
    mixer: str              # "attention" | "conv"
    ffn: str                # "mlp" | "moe"
    start: int
    n: int
    slot: int


def segments(cfg: ModelConfig) -> Tuple[Segment, ...]:
    """The runs of ``cfg.mixers``: a dense-FFN prefix of ``moe.dense_layers``
    layers, then MoE layers where the config has experts."""
    assert len(cfg.mixers) == cfg.num_layers and set(cfg.mixers) <= set(
        MIXERS), (cfg.name, cfg.mixers)
    dense = cfg.moe.dense_layers if cfg.moe else 0
    kinds = [(m, "moe" if cfg.moe is not None and i >= dense else "mlp")
             for i, m in enumerate(cfg.mixers)]
    out, seen = [], {"attention": 0, "conv": 0}
    for i, kind in enumerate(kinds):
        if out and kinds[i - 1] == kind:
            out[-1] = dataclasses.replace(out[-1], n=out[-1].n + 1)
        else:
            out.append(Segment(kind[0], kind[1], i, 1, seen[kind[0]]))
        seen[kind[0]] += 1
    return tuple(out)


def _refuse_schedule(cfg: ModelConfig, what: str) -> None:
    if cfg.mixers:
        raise NotImplementedError(
            f"{cfg.name}: {what} has no per-layer mixer schedule; a model "
            "with conv layers serves on the pooled KV layout "
            "(init_kv_pool / prefill_suffix / decode_n with tables)")


def _init_segment(cfg: ModelConfig, seg: Segment, key):
    ks = jax.random.split(key, 4)
    lp = {"ln1": L.norm_init(cfg, ks[0], stacked=seg.n),
          "ln2": L.norm_init(cfg, ks[1], stacked=seg.n)}
    if seg.mixer == "attention":
        lp["attn"] = L.attention_init(cfg, ks[2], stacked=seg.n)
    else:
        lp["conv"] = SSM.short_conv_init(cfg, ks[2], stacked=seg.n)
    if seg.ffn == "moe":
        lp["moe"] = MOE.moe_init(cfg, ks[3], stacked=seg.n)
    else:
        lp["mlp"] = L.mlp_init(cfg, ks[3], stacked=seg.n, d_ff=(
            cfg.moe.dense_ffw if cfg.moe else cfg.d_ff))
    return lp


def _stream_dtype(cfg: ModelConfig):
    """The residual stream's dtype: float32 for a schedule (`_normed`)."""
    return jnp.float32 if cfg.mixers else jnp.bfloat16


def _normed(cfg: ModelConfig, w, x, dtype=jnp.bfloat16):
    """A scheduled or pooled layer's norm: read the residual stream, hand
    the products bf16.  A schedule's stream stays float32 because the
    router's top-k is a discrete choice: a bf16 stream rounds once per
    layer, and where two experts' biased scores lie within that rounding
    the choice flips.  On a bf16 stream this is `layers.apply_norm`, whose
    norms all return their input's dtype."""
    return L.apply_norm(cfg, w, x).astype(dtype)


def _segment_ffn(cfg: ModelConfig, seg: Segment, lp, h):
    """The FFN of a scheduled layer on (B, T, D); returns (out, routed
    (B*T, held) bool or None)."""
    if seg.ffn == "mlp":
        return L.mlp_apply(cfg, lp["mlp"], h), None
    B, T, D = h.shape
    out, routed = MOE.moe_held(cfg, lp["moe"], h.reshape(B * T, D))
    return out.reshape(B, T, D), routed


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, 16)
    p: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": L.norm_init(cfg, keys[1]),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(keys[2], cfg.d_model, cfg.vocab_size)
    if cfg.mixers:
        segs = segments(cfg)
        p["layers"] = [_init_segment(cfg, seg, k) for seg, k in
                       zip(segs, jax.random.split(keys[4], len(segs)))]
        return p
    if cfg.vision_prefix:
        p["vision_proj"] = L.dense_init(keys[3], cfg.vision_dim, cfg.d_model)

    n_scan = num_moe_layers(cfg) if cfg.family == "moe" else cfg.num_layers
    lp: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "vlm", "hybrid"):
        lp["ln1"] = L.norm_init(cfg, keys[4], stacked=n_scan)
        lp["attn"] = L.attention_init(cfg, keys[5], stacked=n_scan)
        lp["ln2"] = L.norm_init(cfg, keys[6], stacked=n_scan)
        if cfg.post_norm:
            lp["post_ln1"] = L.norm_init(cfg, keys[7], stacked=n_scan)
            lp["post_ln2"] = L.norm_init(cfg, keys[8], stacked=n_scan)
    if cfg.family in ("dense", "vlm", "hybrid"):
        lp["mlp"] = L.mlp_init(cfg, keys[9], stacked=n_scan)
    if cfg.family == "moe":
        lp["moe"] = MOE.moe_init(cfg, keys[9], stacked=n_scan)
    if cfg.family == "ssm":
        lp["ln1"] = L.norm_init(cfg, keys[4], stacked=n_scan)
        lp["ssm"] = SSM.ssd_init(cfg, keys[10], stacked=n_scan)
    if cfg.family == "hybrid":
        lp["ssm"] = SSM.ssd_init(cfg, keys[10], stacked=n_scan)
        lp["alpha_attn"] = jnp.zeros((n_scan, cfg.d_model), jnp.float32)
        lp["alpha_ssm"] = jnp.zeros((n_scan, cfg.d_model), jnp.float32)
    p["layers"] = lp

    if cfg.family == "moe" and cfg.moe.dense_layers:
        dense_cfg = cfg  # same dims, dense FFN of width dense_ffw
        prefix = []
        dkeys = jax.random.split(keys[11], cfg.moe.dense_layers)
        for i in range(cfg.moe.dense_layers):
            ks = jax.random.split(dkeys[i], 4)
            blk = {
                "ln1": L.norm_init(cfg, ks[0]),
                "attn": L.attention_init(cfg, ks[1]),
                "ln2": L.norm_init(cfg, ks[2]),
                "mlp": L.mlp_init(cfg, ks[3], d_ff=cfg.moe.dense_ffw),
            }
            prefix.append(blk)
        p["dense_prefix"] = prefix
    return p


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, p, tokens, dtype=jnp.bfloat16):
    x = Q.take(p["embed"], tokens, dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    return x


def unembed(cfg: ModelConfig, p, x, dtype=jnp.bfloat16):
    w = (Q.cast(p["embed"], dtype).T if cfg.tie_embeddings
         else Q.cast(p["head"], dtype))
    logits = jnp.einsum("btd,dv->btv", x, w,
                        preferred_element_type=jnp.float32)
    logits = hint(logits, "batch", None, "model")
    return L.softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

def _ffn_part(cfg: ModelConfig, lp, h, ctx, *, decode: bool,
              batch_spec, seq_spec, moe_cf: Optional[float] = None):
    """Returns (ffn_out, aux)."""
    if cfg.family == "moe":
        if decode:
            out, aux, _ = MOE.moe_decode(
                cfg, lp["moe"], h, ctx, batch_spec=batch_spec,
                capacity_factor=moe_cf or 2.0)
        else:
            out, aux, _ = MOE.moe_ep(
                cfg, lp["moe"], h, ctx, batch_spec=batch_spec,
                seq_spec=seq_spec, capacity_factor=moe_cf or 1.25)
        return out, aux
    return L.mlp_apply(cfg, lp["mlp"], h), jnp.zeros((), jnp.float32)


def _self_attn(cfg, lp, h, positions, window, *, kv_chunk, attn_impl):
    a = cfg.attention
    if attn_impl == "qchunked":
        # window must be static here (int or None)
        q, k, v = L.attention_qkv(lp["attn"], h, a, positions)
        o = L.blocked_attention_qchunked(
            q, k, v, positions, positions,
            window=window if not hasattr(window, "dtype") else None,
            softcap=a.logit_softcap, scale=a.attn_scale,
            kv_chunk=kv_chunk)
        return L.attention_out(lp["attn"], o)
    return L.self_attention(lp["attn"], h, a, positions,
                            window=window, kv_chunk=kv_chunk)


def _mixer_part(cfg: ModelConfig, lp, h, positions, window, *,
                kv_chunk: int = 1024, attn_impl: str = "blocked"):
    """Full-sequence (training/prefill) token mixer.  Returns (out, ssm_state,
    conv_tail) — states are None for pure-attention families."""
    a = cfg.attention
    attn_out = ssm_out = None
    state = tail = None
    if cfg.family in ("dense", "moe", "vlm"):
        attn_out = _self_attn(cfg, lp, h, positions, window,
                              kv_chunk=kv_chunk, attn_impl=attn_impl)
        return attn_out, None, None
    if cfg.family == "ssm":
        out, state, tail = SSM.ssd_forward(cfg, lp["ssm"], h)
        return out, state, tail
    # hybrid: attention ∥ SSM on the same input
    attn_out = _self_attn(cfg, lp, h, positions, window,
                          kv_chunk=kv_chunk, attn_impl=attn_impl)
    ssm_out, state, tail = SSM.ssd_forward(cfg, lp["ssm"], h)
    out = 0.5 * (attn_out * (1.0 + lp["alpha_attn"].astype(attn_out.dtype))
                 + ssm_out * (1.0 + lp["alpha_ssm"].astype(attn_out.dtype)))
    return out, state, tail


def _dense_layer(cfg: ModelConfig, lp, x, positions, window, ctx, *,
                 decode=False, batch_spec=None, seq_spec=None,
                 kv_chunk=1024, d_ff=None, moe_cf=None,
                 attn_impl="blocked"):
    """One standard pre-norm transformer layer (used by the kimi dense prefix
    and as the scan body for pure-attention families)."""
    x = hint(x, "batch", None, None)
    h = L.apply_norm(cfg, lp["ln1"], x)
    h, state, tail = _mixer_part(cfg, lp, h, positions, window,
                                 kv_chunk=kv_chunk, attn_impl=attn_impl)
    if cfg.post_norm:
        h = L.apply_norm(cfg, lp["post_ln1"], h)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        return x, aux, state, tail
    h = L.apply_norm(cfg, lp["ln2"], x)
    if "mlp" in lp and cfg.family != "moe":
        h = L.mlp_apply(cfg, lp["mlp"], h)
    elif "mlp" in lp:   # kimi dense prefix layer
        h = L.mlp_apply(cfg, lp["mlp"], h)
    else:
        h, aux = _ffn_part(cfg, lp, h, ctx, decode=decode,
                           batch_spec=batch_spec, seq_spec=seq_spec,
                           moe_cf=moe_cf)
    if cfg.post_norm:
        h = L.apply_norm(cfg, lp["post_ln2"], h)
    return x + h, aux, state, tail


# ---------------------------------------------------------------------------
# Forward (training / teacher-forced)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, p, batch: Dict[str, Any],
            ctx: ParallelContext = LOCAL, *,
            collect_states: bool = False, kv_chunk: int = 1024,
            remat: bool = False, moe_cf=None, return_hidden: bool = False,
            attn_impl: str = "blocked"):
    """Returns (logits (B, T, V), aux_losses scalar[, states])."""
    if cfg.mixers:
        assert not collect_states, "scheduled layers keep no state here"
        return _forward_scheduled(cfg, p, batch["tokens"], remat=remat,
                                  kv_chunk=kv_chunk,
                                  return_hidden=return_hidden)
    tokens = batch["tokens"]
    B, T_text = tokens.shape
    x = embed_tokens(cfg, p, tokens)
    if cfg.vision_prefix:
        patches = batch["patches"]                  # (B, P, vision_dim)
        pv = jnp.einsum("bpe,ed->bpd", patches.astype(x.dtype),
                        p["vision_proj"].astype(x.dtype))
        x = jnp.concatenate([pv, x], axis=1)
    T = x.shape[1]
    positions = hint(jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32), (B, T)), "batch", None)

    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    ms = ctx.model_axis_size
    sspec = (ctx.model_axis
             if ctx.has_mesh and ms > 1 and T % ms == 0 else None)

    aux_total = jnp.zeros((), jnp.float32)

    # kimi dense prefix (unrolled)
    for blk in p.get("dense_prefix", []):
        x, aux, _, _ = _dense_layer(cfg, blk, x, positions, None, ctx,
                                    batch_spec=bspec, seq_spec=sspec,
                                    kv_chunk=kv_chunk)
        aux_total += aux

    windows = jnp.asarray(window_schedule(cfg)[
        (cfg.moe.dense_layers if cfg.family == "moe" and cfg.moe else 0):])

    if attn_impl == "qchunked" and can_qchunk(cfg):
        # regroup the stack so every scan position has a STATIC window
        g = attn_group_size(cfg)
        lp_g = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // g, g) + a.shape[1:]),
            p["layers"])

        def body(carry, lp_group):
            x, aux_acc = carry
            states = []
            for idx in range(g):
                lp = jax.tree.map(lambda a: a[idx], lp_group)
                win = static_window_for(cfg, idx, g)
                x, aux, state, tail = _dense_layer(
                    cfg, lp, x, positions, win, ctx,
                    batch_spec=bspec, seq_spec=sspec, kv_chunk=kv_chunk,
                    moe_cf=moe_cf, attn_impl="qchunked")
                aux_acc = aux_acc + aux
            ys = (state, tail) if collect_states else (None, None)
            return (x, aux_acc), ys

        if remat:
            body = jax.checkpoint(body)
        (x, aux_total), states = jax.lax.scan(
            body, (x, aux_total), lp_g)
    else:
        def body(carry, xs):
            x, aux_acc = carry
            lp, win = xs
            x, aux, state, tail = _dense_layer(
                cfg, lp, x, positions, win, ctx,
                batch_spec=bspec, seq_spec=sspec, kv_chunk=kv_chunk,
                moe_cf=moe_cf)
            ys = (state, tail) if collect_states else (None, None)
            return (x, aux_acc + aux), ys

        if remat:
            body = jax.checkpoint(body)
        (x, aux_total), states = jax.lax.scan(
            body, (x, aux_total), (p["layers"], windows))
    x = L.apply_norm(cfg, p["final_norm"], x)
    if return_hidden:
        return x, aux_total
    logits = unembed(cfg, p, x)
    if collect_states:
        return logits, aux_total, states
    return logits, aux_total


def _forward_scheduled(cfg: ModelConfig, p, tokens, *, remat: bool,
                       kv_chunk: int, return_hidden: bool):
    """`forward` of a per-layer mixer schedule: each run of like layers
    scanned, attention over the whole sequence, conv from a zero state."""
    a = cfg.attention
    B, T = tokens.shape
    x = embed_tokens(cfg, p, tokens).astype(_stream_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    full = jnp.full((B,), T, jnp.int32)
    for seg, lp_seg in zip(segments(cfg), p["layers"]):
        def body(x, lp, seg=seg):
            h = _normed(cfg, lp["ln1"], x)
            if seg.mixer == "attention":
                q, k, v = L.attention_qkv(lp["attn"], h, a, positions,
                                          norm_eps=cfg.norm_eps)
                o = L.blocked_attention(q, k, v, positions, positions,
                                        kv_chunk=kv_chunk)
                h = L.attention_out(lp["attn"], o)
            else:
                zero = jnp.zeros((B, cfg.conv_width - 1, cfg.d_model),
                                 h.dtype)
                with jax.named_scope("short_conv"):
                    h, _ = SSM.short_conv(cfg, lp["conv"], h, zero, full)
            x = x + h
            h, _ = _segment_ffn(cfg, seg, lp, _normed(cfg, lp["ln2"], x))
            return x + h, None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, lp_seg)
    x = _normed(cfg, p["final_norm"], x)
    aux = jnp.zeros((), jnp.float32)
    if return_hidden:
        return x, aux
    return unembed(cfg, p, x), aux


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cache:
    k: Optional[jax.Array] = None        # (Ls, B, S, KH, hd)
    v: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None      # (Ls, B, H, P, N)
    conv: Optional[jax.Array] = None     # (Ls, B, W-1, conv_dim); pooled
                                         # schedule: (conv layers, slots,
                                         # W-1, D)
    prefix_k: Optional[list] = None      # kimi dense prefix (unrolled layers)
    prefix_v: Optional[list] = None
    pos: Optional[jax.Array] = None      # scalar int32: tokens already cached


jax.tree_util.register_dataclass(
    Cache, data_fields=["k", "v", "ssm", "conv", "prefix_k", "prefix_v",
                        "pos"],
    meta_fields=[])


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Cache:
    _refuse_schedule(cfg, "the dense per-slot cache")
    a = cfg.attention
    n_scan = num_moe_layers(cfg) if cfg.family == "moe" else cfg.num_layers
    c = Cache(pos=jnp.zeros((), jnp.int32))
    if a is not None:
        kv = (n_scan, batch, max_len, a.num_kv_heads, a.head_dim)
        c.k = jnp.zeros(kv, dtype)
        c.v = jnp.zeros(kv, dtype)
        npre = cfg.moe.dense_layers if cfg.family == "moe" and cfg.moe else 0
        if npre:
            c.prefix_k = [jnp.zeros(kv[1:], dtype) for _ in range(npre)]
            c.prefix_v = [jnp.zeros(kv[1:], dtype) for _ in range(npre)]
    if cfg.family in ("ssm", "hybrid"):
        DI, H, Pd, N = SSM.ssm_dims(cfg)
        c.ssm = jnp.zeros((n_scan, batch, H, Pd, N), jnp.float32)
        c.conv = jnp.zeros((n_scan, batch, cfg.ssm.conv_width - 1,
                            DI + 2 * N), jnp.bfloat16)
    return c


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, p, batch: Dict[str, Any],
            ctx: ParallelContext = LOCAL, *, max_len: Optional[int] = None,
            kv_chunk: int = 1024, moe_cf=None,
            attn_impl: str = "blocked") -> Tuple[jax.Array, Cache]:
    """Forward over the prompt; returns (last-position logits, filled cache)."""
    _refuse_schedule(cfg, "prefill")
    tokens = batch["tokens"]
    B, T_text = tokens.shape
    x = embed_tokens(cfg, p, tokens)
    if cfg.vision_prefix:
        pv = jnp.einsum("bpe,ed->bpd", batch["patches"].astype(x.dtype),
                        p["vision_proj"].astype(x.dtype))
        x = jnp.concatenate([pv, x], axis=1)
    T = x.shape[1]
    S = max_len or T
    positions = hint(jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32), (B, T)), "batch", None)
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    ms = ctx.model_axis_size
    sspec = (ctx.model_axis
             if ctx.has_mesh and ms > 1 and T % ms == 0 else None)

    cache = init_cache(cfg, B, S)
    a = cfg.attention

    def attn_with_cache(lp, h, win):
        q, k, v = L.attention_qkv(lp["attn"], h, a, positions)
        if attn_impl == "qchunked" and not hasattr(win, "dtype"):
            o = L.blocked_attention_qchunked(
                q, k, v, positions, positions, window=win,
                softcap=a.logit_softcap, scale=a.attn_scale,
                kv_chunk=kv_chunk)
        else:
            o = L.blocked_attention(q, k, v, positions, positions,
                                    window=win, softcap=a.logit_softcap,
                                    scale=a.attn_scale, kv_chunk=kv_chunk)
        kpad = jnp.pad(k, ((0, 0), (0, S - T), (0, 0), (0, 0)))
        vpad = jnp.pad(v, ((0, 0), (0, S - T), (0, 0), (0, 0)))
        return L.attention_out(lp["attn"], o), kpad, vpad

    aux = jnp.zeros((), jnp.float32)
    for i, blk in enumerate(p.get("dense_prefix", [])):
        h = L.apply_norm(cfg, blk["ln1"], x)
        h, kc, vc = attn_with_cache(blk, h, None)
        cache.prefix_k[i] = kc
        cache.prefix_v[i] = vc
        x = x + h
        h = L.apply_norm(cfg, blk["ln2"], x)
        x = x + L.mlp_apply(cfg, blk["mlp"], h)

    windows = jnp.asarray(window_schedule(cfg)[
        (cfg.moe.dense_layers if cfg.family == "moe" and cfg.moe else 0):])

    def body(x_and_aux, xs):
        x, aux_acc = x_and_aux
        lp, win = xs
        kc = vc = state = tail = None
        h = L.apply_norm(cfg, lp["ln1"], x)
        if cfg.family in ("dense", "moe", "vlm"):
            h, kc, vc = attn_with_cache(lp, h, win)
        elif cfg.family == "ssm":
            h, state, tail = SSM.ssd_forward(cfg, lp["ssm"], h)
        else:  # hybrid
            h_attn, kc, vc = attn_with_cache(lp, h, win)
            h_ssm, state, tail = SSM.ssd_forward(cfg, lp["ssm"], h)
            h = 0.5 * (h_attn * (1.0 + lp["alpha_attn"].astype(h.dtype))
                       + h_ssm * (1.0 + lp["alpha_ssm"].astype(h.dtype)))
        if cfg.post_norm:
            h = L.apply_norm(cfg, lp["post_ln1"], h)
        x = x + h
        aux = jnp.zeros((), jnp.float32)
        if cfg.family != "ssm":
            h = L.apply_norm(cfg, lp["ln2"], x)
            if cfg.family == "moe":
                h, aux = _ffn_part(cfg, lp, h, ctx, decode=False,
                                   batch_spec=bspec, seq_spec=sspec,
                                   moe_cf=moe_cf)
            else:
                h = L.mlp_apply(cfg, lp["mlp"], h)
            if cfg.post_norm:
                h = L.apply_norm(cfg, lp["post_ln2"], h)
            x = x + h
        return (x, aux_acc + aux), (kc, vc, state, tail)

    if attn_impl == "qchunked" and can_qchunk(cfg):
        g = attn_group_size(cfg)
        lp_g = jax.tree.map(
            lambda a_: a_.reshape((a_.shape[0] // g, g) + a_.shape[1:]),
            p["layers"])

        def gbody(x_and_aux, lp_group):
            acc_ys = None
            for idx in range(g):
                lp = jax.tree.map(lambda a_: a_[idx], lp_group)
                win = static_window_for(cfg, idx, g)
                x_and_aux, ys = body(x_and_aux, (lp, win))
                ys = jax.tree.map(lambda t: t[None] if t is not None else t,
                                  ys, is_leaf=lambda t: t is None)
                acc_ys = ys if acc_ys is None else jax.tree.map(
                    lambda a_, b_: (jnp.concatenate([a_, b_])
                                    if a_ is not None else None),
                    acc_ys, ys, is_leaf=lambda t: t is None)
            return x_and_aux, acc_ys

        (x, aux), grouped = jax.lax.scan(gbody, (x, aux), lp_g)
        # grouped ys: (n_groups, g, ...) -> flatten layer dim
        ks, vs, states, tails = jax.tree.map(
            lambda t: (t.reshape((-1,) + t.shape[2:])
                       if t is not None else None),
            grouped, is_leaf=lambda t: t is None)
    else:
        (x, aux), (ks, vs, states, tails) = jax.lax.scan(
            body, (x, aux), (p["layers"], windows))
    if ks is not None:
        cache.k, cache.v = ks, vs
    if states is not None:
        cache.ssm = states
        cache.conv = tails
    cache.pos = jnp.asarray(T, jnp.int32)
    x = L.apply_norm(cfg, p["final_norm"], x)
    logits = unembed(cfg, p, x[:, -1:, :])
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def _decode_layer(cfg: ModelConfig, lp, win, x, kc, vc, sst, scv, ctx, *,
                  attn_fn, bspec, moe_cf=None, active=None):
    """Shared per-layer decode body for the legacy and paged decode paths.

    ``attn_fn(lp, h, kc, vc, win) -> (h, kc, vc)`` supplies the path's
    attention (shared-position dense vs per-slot paged); ``active`` (B,)
    bool, when given, freezes the recurrent state of done slots (paged
    done-masking).  Returns (x, (kc, vc, state, conv))."""
    h = L.apply_norm(cfg, lp["ln1"], x)
    state = conv = None
    if cfg.family in ("dense", "moe", "vlm"):
        h, kc, vc = attn_fn(lp, h, kc, vc, win)
    elif cfg.family == "ssm":
        o, state, conv = SSM.ssd_step(cfg, lp["ssm"], h[:, 0], sst, scv)
        h = o[:, None, :]
    else:  # hybrid
        ha, kc, vc = attn_fn(lp, h, kc, vc, win)
        o, state, conv = SSM.ssd_step(cfg, lp["ssm"], h[:, 0], sst, scv)
        hs = o[:, None, :]
        h = 0.5 * (ha * (1.0 + lp["alpha_attn"].astype(ha.dtype))
                   + hs * (1.0 + lp["alpha_ssm"].astype(ha.dtype)))
    if state is not None and active is not None:
        B = x.shape[0]
        keep = active.reshape((B,) + (1,) * (state.ndim - 1))
        state = jnp.where(keep, state, sst)
        conv = jnp.where(active.reshape((B,) + (1,) * (conv.ndim - 1)),
                         conv, scv)
    if cfg.post_norm:
        h = L.apply_norm(cfg, lp["post_ln1"], h)
    x = x + h
    if cfg.family != "ssm":
        h = L.apply_norm(cfg, lp["ln2"], x)
        if cfg.family == "moe":
            h, _ = _ffn_part(cfg, lp, h, ctx, decode=True,
                             batch_spec=bspec, seq_spec=None, moe_cf=moe_cf)
        else:
            h = L.mlp_apply(cfg, lp["mlp"], h)
        if cfg.post_norm:
            h = L.apply_norm(cfg, lp["post_ln2"], h)
        x = x + h
    return x, (kc, vc, state, conv)


def decode_step(cfg: ModelConfig, p, cache: Cache, tokens,
                ctx: ParallelContext = LOCAL, *, kv_chunk: int = 2048,
                moe_cf=None):
    """One decode step.  tokens: (B,) int32.  Returns (logits (B, V), cache).

    The new token is written at index ``cache.pos``; attention sees positions
    [0, pos] (windowed per layer).
    """
    _refuse_schedule(cfg, "decode_step")
    a = cfg.attention
    B = tokens.shape[0]
    pos = cache.pos
    x = embed_tokens(cfg, p, tokens[:, None])           # (B, 1, D)
    q_pos = hint(jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32),
                 "batch", None)
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None

    def attn_decode(lp, h, kc, vc, win):
        q, k, v = L.attention_qkv(lp["attn"], h, a, q_pos)
        S = kc.shape[1]
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, pos, 0, 0))
        kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        o = L.blocked_attention(q, kc, vc, q_pos, kv_pos,
                                window=win, softcap=a.logit_softcap,
                                scale=a.attn_scale, kv_chunk=kv_chunk)
        return L.attention_out(lp["attn"], o), kc, vc

    new_prefix_k, new_prefix_v = [], []
    for i, blk in enumerate(p.get("dense_prefix", [])):
        h = L.apply_norm(cfg, blk["ln1"], x)
        h, kc, vc = attn_decode(blk, h, cache.prefix_k[i], cache.prefix_v[i],
                                None)
        new_prefix_k.append(kc)
        new_prefix_v.append(vc)
        x = x + h
        h = L.apply_norm(cfg, blk["ln2"], x)
        x = x + L.mlp_apply(cfg, blk["mlp"], h)

    windows = jnp.asarray(window_schedule(cfg)[
        (cfg.moe.dense_layers if cfg.family == "moe" and cfg.moe else 0):])

    def body(x, xs):
        lp, win, kc, vc, sst, scv = xs
        return _decode_layer(cfg, lp, win, x, kc, vc, sst, scv, ctx,
                             attn_fn=attn_decode, bspec=bspec,
                             moe_cf=moe_cf)

    dummy = jnp.zeros((num_moe_layers(cfg) if cfg.family == "moe"
                       else cfg.num_layers,), jnp.float32)
    xs = (p["layers"], windows,
          cache.k if cache.k is not None else dummy,
          cache.v if cache.v is not None else dummy,
          cache.ssm if cache.ssm is not None else dummy,
          cache.conv if cache.conv is not None else dummy)
    x, (ks, vs, states, convs) = jax.lax.scan(body, x, xs)

    new_cache = Cache(
        k=ks if cache.k is not None else None,
        v=vs if cache.v is not None else None,
        ssm=states if cache.ssm is not None else None,
        conv=convs if cache.conv is not None else None,
        prefix_k=new_prefix_k or None,
        prefix_v=new_prefix_v or None,
        pos=pos + 1,
    )
    x = L.apply_norm(cfg, p["final_norm"], x)
    logits = unembed(cfg, p, x)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Serve fast path: per-slot cache insertion + paged multi-step decode
# ---------------------------------------------------------------------------
# Continuous batching keeps ONE batch-wide cache alive across admissions;
# slots differ in valid length.  `cache_insert` writes a freshly prefilled
# (batch=1) slot cache into its batch row; `decode_step_paged` advances every
# slot one token at ITS OWN position (per-slot seq_lens replaces the shared
# cache.pos); `decode_n` scans that step on-device so the host syncs once per
# chunk instead of once per token.


def cache_insert(cache: Cache, slot_cache: Cache, slot) -> Cache:
    """Write the (batch=n) ``slot_cache`` into batch rows ``slot`` of
    ``cache``.  ``slot`` is a scalar or an (n,) vector of slot indices (a
    whole admission wave lands in ONE dispatch); scalars/traced values both
    work, so one jitted admission program serves every slot."""
    slots = jnp.atleast_1d(jnp.asarray(slot, jnp.int32))

    def ins(dst, src, axis):
        src = src.astype(dst.dtype)
        if axis == 0:
            return dst.at[slots].set(src)
        return dst.at[:, slots].set(src)

    new = Cache(pos=jnp.maximum(cache.pos, slot_cache.pos))
    if cache.k is not None:
        new.k = ins(cache.k, slot_cache.k, 1)
        new.v = ins(cache.v, slot_cache.v, 1)
    if cache.ssm is not None:
        new.ssm = ins(cache.ssm, slot_cache.ssm, 1)
        new.conv = ins(cache.conv, slot_cache.conv, 1)
    if cache.prefix_k is not None:
        new.prefix_k = [ins(d, s, 0) for d, s in
                        zip(cache.prefix_k, slot_cache.prefix_k)]
        new.prefix_v = [ins(d, s, 0) for d, s in
                        zip(cache.prefix_v, slot_cache.prefix_v)]
    return new


def _decode_attn_impl(ctx: ParallelContext) -> str:
    return {"auto": "auto", "paged": "pallas", "dense": "xla"}[
        getattr(ctx, "decode_attn", "auto")]


def attended_lens(seq_lens, active, width: int):
    """Cache rows a decode step's attention reads per slot: the slot's
    valid rows and the one just written, capped at the cache's ``width``;
    none for a slot that is not ``active`` (done, unadmitted or out of
    budget), whose answer nothing reads, so the kernel reads none of its
    rows.  Its cache, state and length are left as they are."""
    return jnp.where(active, jnp.minimum(seq_lens + 1, width), 0)


def decode_step_paged(cfg: ModelConfig, p, cache: Cache, tokens, seq_lens,
                      active, ctx: ParallelContext = LOCAL, *, moe_cf=None):
    """One decode step with PER-SLOT cache lengths (continuous batching).

    tokens (B,) int32 — previous token per slot;
    seq_lens (B,) int32 — valid cached tokens per slot (the new token is
    written at this row, then attended);
    active (B,) bool — slots past their budget keep their cache, state, and
    seq_len frozen, and their attention reads no rows (`attended_lens`);
    the rest of their lane still computes, output discarded upstream.

    Returns (logits (B, V), cache, seq_lens + active).  Attention runs
    through ``ops.paged_decode_attention`` — the Pallas paged kernel on TPU,
    the dense XLA reference elsewhere (ctx.decode_attn overrides).  The
    pooled layout has its own step, `decode_step_pooled`.
    """
    from repro.kernels import ops as OPS

    _refuse_schedule(cfg, "the dense per-slot layout")
    a = cfg.attention
    seq_lens = seq_lens.astype(jnp.int32)
    act_i = active.astype(jnp.int32)
    x = embed_tokens(cfg, p, tokens[:, None])            # (B, 1, D)
    q_pos = hint(seq_lens[:, None], "batch", None)       # per-slot positions
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    impl = _decode_attn_impl(ctx)
    kv_block = getattr(ctx, "decode_kv_block", 128)

    def attn_paged(lp, h, kc, vc, win):
        q, k, v = L.attention_qkv(lp["attn"], h, a, q_pos)
        S = kc.shape[1]
        # per-slot KV write at each slot's own next row.  Frozen slots write
        # a garbage row one past their (frozen) valid length — never read,
        # and overwritten by the next admission's cache_insert.
        idx = jnp.minimum(seq_lens, S - 1)

        def wr(dst_b, new_b, i):
            return jax.lax.dynamic_update_slice(
                dst_b, new_b.astype(dst_b.dtype), (i, 0, 0))

        kc = jax.vmap(wr)(kc, k, idx)
        vc = jax.vmap(wr)(vc, v, idx)
        o = OPS.paged_decode_attention(
            q[:, 0], kc, vc, attended_lens(seq_lens, active, S), window=win,
            softcap=a.logit_softcap, scale=a.attn_scale, bk=kv_block,
            impl=impl)
        return L.attention_out(lp["attn"], o[:, None]), kc, vc

    new_prefix_k, new_prefix_v = [], []
    for i, blk in enumerate(p.get("dense_prefix", [])):
        h = L.apply_norm(cfg, blk["ln1"], x)
        h, kc, vc = attn_paged(blk, h, cache.prefix_k[i], cache.prefix_v[i],
                               None)
        new_prefix_k.append(kc)
        new_prefix_v.append(vc)
        x = x + h
        h = L.apply_norm(cfg, blk["ln2"], x)
        x = x + L.mlp_apply(cfg, blk["mlp"], h)

    windows = jnp.asarray(window_schedule(cfg)[
        (cfg.moe.dense_layers if cfg.family == "moe" and cfg.moe else 0):])

    def body(x, xs):
        lp, win, kc, vc, sst, scv = xs
        return _decode_layer(cfg, lp, win, x, kc, vc, sst, scv, ctx,
                             attn_fn=attn_paged, bspec=bspec,
                             moe_cf=moe_cf, active=active)

    dummy = jnp.zeros((num_moe_layers(cfg) if cfg.family == "moe"
                       else cfg.num_layers,), jnp.float32)
    xs = (p["layers"],
          cache.k if cache.k is not None else dummy,
          cache.v if cache.v is not None else dummy,
          cache.ssm if cache.ssm is not None else dummy,
          cache.conv if cache.conv is not None else dummy)
    if can_qchunk(cfg):
        # regroup the stack so every scan position has a STATIC window
        # (the prefill/forward qchunked trick) — with a static window the
        # attention dispatcher can launch the Pallas paged kernel; a traced
        # window would force the dense XLA fallback on every layer.
        g = attn_group_size(cfg)
        xs_g = jax.tree.map(
            lambda t: t.reshape((t.shape[0] // g, g) + t.shape[1:]), xs)

        def gbody(x, xs_):
            lp_g, kcg, vcg, sstg, scvg = xs_
            acc = None
            for idx in range(g):
                lp = jax.tree.map(lambda t: t[idx], lp_g)
                win = static_window_for(cfg, idx, g)
                x, ys = body(x, (lp, win, kcg[idx], vcg[idx],
                                 sstg[idx], scvg[idx]))
                ys = jax.tree.map(lambda t: t[None] if t is not None else t,
                                  ys, is_leaf=lambda t: t is None)
                acc = ys if acc is None else jax.tree.map(
                    lambda a_, b_: (jnp.concatenate([a_, b_])
                                    if a_ is not None else None),
                    acc, ys, is_leaf=lambda t: t is None)
            return x, acc

        x, grouped = jax.lax.scan(gbody, x, xs_g)
        ks, vs, states, convs = jax.tree.map(
            lambda t: (t.reshape((-1,) + t.shape[2:])
                       if t is not None else None),
            grouped, is_leaf=lambda t: t is None)
    else:
        x, (ks, vs, states, convs) = jax.lax.scan(
            body, x, (xs[0], windows) + xs[1:])

    new_cache = Cache(
        k=ks if cache.k is not None else None,
        v=vs if cache.v is not None else None,
        ssm=states if cache.ssm is not None else None,
        conv=convs if cache.conv is not None else None,
        prefix_k=new_prefix_k or None,
        prefix_v=new_prefix_v or None,
        pos=jnp.maximum(cache.pos, jnp.max(seq_lens + act_i)),
    )
    x = L.apply_norm(cfg, p["final_norm"], x)
    logits = unembed(cfg, p, x)
    return logits[:, 0], new_cache, seq_lens + act_i


def sample_tokens(logits, key, salt, pos, *, greedy: bool,
                  temperature: float = 1.0):
    """The serving token choice for logits (B, V): argmax, or a draw at
    ``temperature`` keyed by folding each row's request ``salt`` (B,), then
    its position ``pos`` (B,), into ``key``: chunking and slot drop out."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits / jnp.asarray(max(temperature, 1e-6), logits.dtype)
    keys = jax.vmap(lambda b, s: jax.random.fold_in(
        jax.random.fold_in(key, b), s))(salt, pos)
    return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)


def decode_n(cfg: ModelConfig, p, cache: Cache, tokens, seq_lens, budget,
             ctx: ParallelContext = LOCAL, *, num_steps: int,
             greedy: bool = True, key=None, temperature: float = 1.0,
             salt=None, moe_cf=None, tables=None, moe_load: bool = False):
    """Advance all slots up to ``num_steps`` tokens in ONE dispatch.

    A ``lax.scan`` over ``decode_step_paged`` with on-device token selection
    (argmax, or temperature sampling when ``greedy=False``) and per-slot
    done-masking: slot b decodes exactly ``budget[b]`` tokens, then its
    cache/seq_len freeze and its emitted token repeats.  The host syncs once
    per chunk instead of once per token.

    Chunking is numerics-neutral: the scan body is the same program the
    per-token path runs, so greedy outputs are bitwise identical for any
    ``num_steps`` split of the same (tokens, seq_lens, budget) trajectory.
    (Across a serving session, MoE capacity coupling can still observe
    admission timing — see serve/engine.py.)  Sampling (`sample_tokens`)
    keys each draw by ``salt`` (B,) int32, a per-request value (the engine
    passes the request id; default: the slot index), and the position, so
    sampled outputs are chunk-invariant AND decorrelated across slots and
    across requests reusing a slot.

    Returns (toks (num_steps, B) int32, cache, seq_lens, last_tokens), and
    with ``moe_load`` a fifth, ``load``: for a pooled model with routed
    experts, (num_steps, MoE layers, 2) int32, per step the live slots'
    `moe.routed_load`; else None.  It comes back with the tokens, so
    reading it costs no extra sync.

    With ``tables`` (pooled cache from `init_kv_pool`), the scan runs
    `decode_step_pooled` and carries the pool itself, so no per-slot view
    is ever materialised and the pool (donated by the serve engine) is
    updated in place.  The kernel's body is the per-slot path's, so where its
    block size (``ctx.decode_kv_block``) is the pool's, pooled and
    per-slot decode give the same tokens.
    """
    budget = jnp.asarray(budget, jnp.int32)
    if not greedy and key is None:
        raise ValueError("sampling decode (greedy=False) needs a PRNG key")
    salt = (jnp.asarray(salt, jnp.int32) if salt is not None
            else jnp.arange(budget.shape[0], dtype=jnp.int32))

    if tables is None:
        kv = cache

        def advance(kv, toks, lens, active):
            return decode_step_paged(cfg, p, kv, toks, lens, active, ctx,
                                     moe_cf=moe_cf) + (None,)
    else:
        tables = jnp.asarray(tables, jnp.int32)
        shape = cache.k.shape
        flat = (shape[0] * shape[1],) + shape[2:]
        kv = (cache.k.reshape(flat), cache.v.reshape(flat), cache.conv)

        def advance(kv, toks, lens, active):
            return decode_step_pooled(cfg, p, kv, toks, lens, active, tables,
                                      ctx)

    def step(carry, _):
        kv, toks, lens, produced = carry
        active = produced < budget
        logits, kv, lens, load = advance(kv, toks, lens, active)
        nxt = jnp.where(active, sample_tokens(
            logits, key, salt, lens, greedy=greedy,
            temperature=temperature), toks)
        return (kv, nxt, lens, produced + active.astype(jnp.int32)), (nxt,
                                                                      load)

    init = (kv, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(seq_lens, jnp.int32), jnp.zeros_like(budget))
    (kv, last, seq_lens, _), (toks, load) = jax.lax.scan(
        step, init, None, length=num_steps)
    if tables is not None:
        kv = Cache(k=kv[0].reshape(shape), v=kv[1].reshape(shape),
                   conv=kv[2], pos=jnp.maximum(cache.pos, jnp.max(seq_lens)))
    if moe_load:
        return toks, kv, seq_lens, last, load
    return toks, kv, seq_lens, last


# ---------------------------------------------------------------------------
# Pooled prefix-shared KV (serve/kvpool.py block tables)
# ---------------------------------------------------------------------------
# The pooled layout replaces each slot's private (S, KH, hd) KV region with
# an indirection over a shared pool of fixed-size blocks: k/v are
# (Ls, NB, bs, KH, hd) and each slot carries a (nb,) physical-block table.
# Admissions sharing a prompt prefix map their leading table entries onto
# blocks another request already prefilled and prefill only the suffix —
# `prefill_suffix` is that fixed-width dispatch.  Attention always sees the
# LOGICAL view (lane index == token position), so pooled outputs are
# bitwise-identical whether a prefix is shared, freshly computed, or
# re-computed chunk by chunk: masked lanes contribute exact zeros
# (`layers.blocked_attention` / the paged kernels) and per-position math
# never depends on which physical block a lane lives in.


def has_pooled_layout(cfg: ModelConfig) -> bool:
    """Dense attention families, and models with a per-layer mixer
    schedule (attention and short-conv layers, dense or held-expert FFNs).
    SSM, hybrid, vision-prefix and dense-prefix stacks have no pooled
    layout yet."""
    return (cfg.family == "dense" and cfg.attention is not None
            and not cfg.vision_prefix) or bool(cfg.mixers)


def num_attention_layers(cfg: ModelConfig) -> int:
    """Layers that keep KV (every layer without a mixer schedule)."""
    if cfg.mixers:
        return cfg.num_layers - cfg.conv_layers
    return cfg.num_layers


def init_kv_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                 dtype=jnp.bfloat16, *, slots: int = 0) -> Cache:
    """Pooled KV cache: k/v (Ls, NB, bs, KH, hd) for the Ls attention
    layers, indexed by block tables; with conv layers also ``conv``
    (n_conv, slots, conv_width - 1, D), each slot's short-convolution state
    (the last B*x rows), which lives beside the pool, one row per slot."""
    a = cfg.attention
    assert has_pooled_layout(cfg), \
        f"{cfg.name}: no pooled KV layout for the {cfg.family} family"
    kv = (num_attention_layers(cfg), num_blocks, block_size, a.num_kv_heads,
          a.head_dim)
    conv = None
    if cfg.conv_layers:
        assert slots > 0, "conv state needs the slot count"
        conv = jnp.zeros((cfg.conv_layers, slots, cfg.conv_width - 1,
                          cfg.d_model), dtype)
    return Cache(k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype), conv=conv,
                 pos=jnp.zeros((), jnp.int32))


def _pool_runs(cfg: ModelConfig, p):
    """``(Segment, stacked weights)`` runs of a model with the pooled
    layout: a schedule's `segments`, or a uniform stack as one run."""
    assert has_pooled_layout(cfg), \
        f"{cfg.name}: no pooled KV layout for the {cfg.family} family"
    if cfg.mixers:
        return tuple(zip(segments(cfg), p["layers"]))
    return ((Segment("attention", "mlp", 0, cfg.num_layers, 0),
             p["layers"]),)


def _pooled_layers(cfg: ModelConfig, p, x, state, mixer, *, active=None,
                   group: bool = False):
    """Every layer of a model with the pooled layout, one scan per run
    (`_pool_runs`).  ``state`` (the pool, layer and block axes merged, and
    the conv state) rides the scan's carry, never its xs/ys, so each layer
    updates its own rows in place.  ``mixer(seg, lp, h, state, l, window)
    -> (h, state)`` is the caller's token mixer; ``l`` indexes the layer
    among those of its mixer (its pool layer, or its conv state row).
    ``group`` scans a run whose windows tile by `attn_group_size` in groups
    of that many layers, each place with a STATIC window (None = global),
    as the decode kernel needs; else layer by layer (a prefill keeps the
    per-slot `prefill`'s program and bits), static where the run has one
    window.  Returns (x, state, load: per MoE layer the `moe.routed_load`
    of the ``active`` (B,) slots, (n, 2) int32; None without either)."""
    x = x.astype(_stream_dtype(cfg))
    loads = []
    for seg, lp_run in _pool_runs(cfg, p):
        w = window_schedule(cfg)[seg.start:seg.start + seg.n]
        g = attn_group_size(cfg) if group else 1
        if seg.n % g or (w.reshape(-1, g) != w[:g]).any():
            g = 1
        static = ([None if v == GLOBAL_WINDOW else int(v) for v in w[:g]]
                  if (w.reshape(-1, g) == w[:g]).all() else None)

        xs = (lp_run, jnp.arange(seg.n), jnp.asarray(w))
        if g > 1:       # a stack of weights reshaped can cost a copy of it
            xs = jax.tree.map(
                lambda t: t.reshape((seg.n // g, g) + t.shape[1:]), xs)

        def body(carry, xs, seg=seg, g=g, static=static):
            x, state = carry
            load = []
            for j in range(g):
                lp, l, win = jax.tree.map(lambda t: t[j], xs) if g > 1 else xs
                win = win if static is None else static[j]
                h, state = mixer(seg, lp, _normed(cfg, lp["ln1"], x), state,
                                 seg.slot + l, win)
                if cfg.post_norm:
                    h = L.apply_norm(cfg, lp["post_ln1"], h)
                x = x + h
                h, routed = _segment_ffn(cfg, seg, lp,
                                         _normed(cfg, lp["ln2"], x))
                if cfg.post_norm:
                    h = L.apply_norm(cfg, lp["post_ln2"], h)
                x = x + h
                if routed is not None and active is not None:
                    load.append(MOE.routed_load(routed, active))
            return (x, state), (jnp.stack(load) if load else None)

        (x, state), load = jax.lax.scan(body, (x, state), xs)
        if load is not None:
            loads.append(load.reshape((-1,) + load.shape[2:]))
    return x, state, (jnp.concatenate(loads) if loads else None)


def prefill_suffix(cfg: ModelConfig, p, cache: Cache, tokens, start, valid,
                   tables, ctx: ParallelContext = LOCAL, *, slots=None
                   ) -> Tuple[jax.Array, Cache]:
    """Fixed-width suffix prefill over a pooled KV cache.

    tokens (B, T) int32 — row b holds suffix tokens for logical positions
    ``[start[b], start[b] + valid[b])``, left-aligned (lanes past ``valid``
    are padding — their KV is computed but dropped at the scatter);
    start (B,) int32 — logical position of ``tokens[:, 0]`` (the shared /
    already-prefilled prefix length for this chunk);
    valid (B,) int32 — valid suffix tokens this dispatch (0 = idle row);
    tables (B, nb) int32 — each row's slot block table (out-of-range =
    unadmitted);
    slots (B,) int32, optional — the slot each row prefills (None: row b is
    slot b).  Only per-slot state beside the pool (a schedule's conv
    state) is indexed by it; an out-of-range slot's state writes drop.

    Each attention layer scatters the fresh suffix KV into its pool rows
    FIRST, then gathers the slot's full logical view (prefix blocks written
    by earlier dispatches + this chunk) and runs blocked attention with
    logical positions — masked lanes (unwritten tail, idle rows) use the
    kv_pos=-1 sentinel and contribute exact zeros.  A long suffix prefills
    in ``ceil(len/T)`` chained dispatches of this ONE program.  A conv
    layer continues each row from its slot's conv state (zero where the
    row starts a prompt) and leaves the state after the row's last valid
    token; rows with nothing valid keep theirs.  Returns (logits (B, V)
    at each row's last valid suffix position, updated pooled cache).
    """
    a = cfg.attention
    B, T = tokens.shape
    Ls, NB, bs, KH, hd = cache.k.shape
    nb = tables.shape[1]
    W = nb * bs
    rows = NB * bs                            # pool rows of one layer
    tables = tables.astype(jnp.int32)
    start = start.astype(jnp.int32)
    valid = valid.astype(jnp.int32)
    slots = (jnp.arange(B, dtype=jnp.int32) if slots is None
             else jnp.asarray(slots, jnp.int32))

    x = embed_tokens(cfg, p, tokens)
    positions = hint(start[:, None] + jnp.arange(T, dtype=jnp.int32)[None],
                     "batch", None)
    # logical lane positions of the slot's KV view; lanes at/after the
    # suffix end are unwritten — the -1 sentinel masks them exactly
    lane = jnp.arange(W, dtype=jnp.int32)[None, :]
    kv_pos = jnp.where(lane < (start + valid)[:, None], lane, -1)
    # gather map: logical lane -> pool row of layer 0 (OOB tables clamp;
    # their lanes are always masked)
    gidx = ((jnp.clip(tables, 0, NB - 1) * bs)[:, :, None]
            + jnp.arange(bs, dtype=jnp.int32)[None, None]).reshape(-1)
    # scatter map: suffix token -> pool row of layer 0; what is not kept
    # (padding, idle rows, unadmitted blocks) aims past every layer: dropped
    blk = positions // bs
    phys = jnp.take_along_axis(tables, jnp.clip(blk, 0, nb - 1), axis=1)
    keep = ((jnp.arange(T, dtype=jnp.int32)[None] < valid[:, None])
            & (blk < nb) & (phys < NB)).reshape(-1)
    dest = (phys * bs + positions % bs).reshape(-1)
    # a row that starts its prompt here starts from a zero conv state
    fresh = ((start == 0) & (valid > 0))[:, None, None]

    def mix(seg, lp, h, state, l, win):
        kf, vf, conv = state
        if seg.mixer == "attention":
            off = l * rows
            q, k, v = L.attention_qkv(lp["attn"], h, a, positions,
                                      norm_eps=cfg.norm_eps)
            d = jnp.where(keep, off + dest, Ls * rows)
            kf = kf.at[d].set(k.reshape(-1, KH, hd).astype(kf.dtype),
                              mode="drop")
            vf = vf.at[d].set(v.reshape(-1, KH, hd).astype(vf.dtype),
                              mode="drop")
            kfull = jnp.take(kf, off + gidx, axis=0).reshape(B, W, KH, hd)
            vfull = jnp.take(vf, off + gidx, axis=0).reshape(B, W, KH, hd)
            o = L.blocked_attention(q, kfull, vfull, positions, kv_pos,
                                    window=win, softcap=a.logit_softcap,
                                    scale=a.attn_scale, kv_chunk=max(W, 1024))
            return L.attention_out(lp["attn"], o), (kf, vf, conv)
        held = jnp.take(conv[l], slots, axis=0, mode="clip")
        st = jnp.where(fresh, jnp.zeros((), conv.dtype), held)
        with jax.named_scope("short_conv"):
            h, st = SSM.short_conv(cfg, lp["conv"], h, st, valid)
        conv = conv.at[l, slots].set(st, mode="drop")
        return h, (kf, vf, conv)

    x, (kf, vf, conv), _ = _pooled_layers(
        cfg, p, x, (cache.k.reshape(Ls * rows, KH, hd),
                    cache.v.reshape(Ls * rows, KH, hd), cache.conv), mix)
    x = _normed(cfg, p["final_norm"], x)
    li = jnp.clip(valid - 1, 0, T - 1)
    xlast = jnp.take_along_axis(x, li[:, None, None], axis=1)   # (B, 1, D)
    logits = unembed(cfg, p, xlast)
    return logits[:, 0], Cache(k=kf.reshape(cache.k.shape),
                               v=vf.reshape(cache.v.shape), conv=conv,
                               pos=cache.pos)


def decode_step_pooled(cfg: ModelConfig, p, kv, tokens, seq_lens, active,
                       tables, ctx: ParallelContext = LOCAL):
    """One decode step over a pooled KV cache, read and written in place.

    kv — (k, v, conv): k and v each ``init_kv_pool``'s (Ls, NB, bs, KH, hd)
    pool with the layer and block axes merged: (Ls * NB, bs, KH, hd), so
    attention layer l's pool block t is block ``l * NB + t``; conv the
    short convolutions' per-slot state (or None where the model has none);
    tables (B, nb) int32 — slot block tables (out-of-range = unadmitted);
    tokens, seq_lens, active — as in `decode_step_paged`.

    Each layer writes one row per slot in place (`_pooled_layers`) and the
    block-table kernel reads the slot's blocks where they lie.  Rows land
    strictly past the prompt, in the slot's private blocks; unadmitted and
    done slots write nothing, their conv state stays, and attention reads
    none of their rows (`attended_lens`).  Returns (logits
    (B, V), kv, seq_lens + active, load: per MoE layer `moe.routed_load`
    of the active slots, or None without routed experts).
    """
    from repro.kernels import ops as OPS
    from repro.kernels.decode_attention import held_block_tables

    a = cfg.attention
    kp, vp, conv = kv
    Ls = num_attention_layers(cfg)
    NB, bs = kp.shape[0] // Ls, kp.shape[1]
    W = tables.shape[1] * bs
    seq_lens = seq_lens.astype(jnp.int32)
    step = active.astype(jnp.int32)
    x = embed_tokens(cfg, p, tokens[:, None])            # (B, 1, D)
    q_pos = hint(seq_lens[:, None], "batch", None)
    impl = _decode_attn_impl(ctx)

    # the fresh row: overflow clamps into the slot's (private) last block.
    # Unadmitted slots (table entry >= NB) and done slots aim past every
    # layer's blocks, where the scatter drops them — an entry of NB plus a
    # layer offset would land in the next layer's blocks
    pos = jnp.minimum(seq_lens, W - 1)
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    writes = active & (phys < NB)
    row = pos % bs
    lens_now = attended_lens(seq_lens, active, W)
    # once a step for every layer: slots attention skips re-name a block
    # the kernel already holds, so it fetches nothing for them
    tclip = held_block_tables(lens_now, jnp.clip(tables, 0, NB - 1), bs)

    def attn(lp, h, kp, vp, win, l):
        q, k, v = L.attention_qkv(lp["attn"], h, a, q_pos,
                                  norm_eps=cfg.norm_eps)
        blk = jnp.where(writes, l * NB + phys, Ls * NB)
        kp = kp.at[blk, row].set(k[:, 0].astype(kp.dtype), mode="drop")
        vp = vp.at[blk, row].set(v[:, 0].astype(vp.dtype), mode="drop")
        o = OPS.paged_decode_attention_bt(
            q[:, 0], kp, vp, lens_now, tclip + l * NB, window=win,
            softcap=a.logit_softcap, scale=a.attn_scale, impl=impl)
        return L.attention_out(lp["attn"], o[:, None]), kp, vp

    def mix(seg, lp, h, state, l, win):
        kp, vp, conv = state
        if seg.mixer == "attention":
            h, kp, vp = attn(lp, h, kp, vp, win, l)
            return h, (kp, vp, conv)
        with jax.named_scope("short_conv"):
            h, st = SSM.short_conv(cfg, lp["conv"], h, conv[l], step)
        return h, (kp, vp, conv.at[l].set(st))

    x, kv, load = _pooled_layers(cfg, p, x, (kp, vp, conv), mix,
                                 active=active, group=True)
    logits = unembed(cfg, p, _normed(cfg, p["final_norm"], x))
    return logits[:, 0], kv, seq_lens + step, load
