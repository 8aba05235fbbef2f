"""Shared model layers: norms, RoPE, blocked (online-softmax) attention, MLP.

All code is mesh-agnostic pure JAX; sharding is applied from outside via
parameter PartitionSpecs + activation constraints (parallel/sharding.py).
Attention is *blocked* — a lax.scan over KV chunks with an online softmax —
so the T×S logits tensor never materialises (required for the 32k prefill and
500k decode shapes).  A Pallas flash-attention kernel (kernels/flash_attention)
is the TPU fast path for the same computation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig, ModelConfig
from repro.models import quant as Q
from repro.parallel.context import active_ctx, hint

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, *out_dims: int, dtype=jnp.float32):
    """Truncated-normal fan-in init, matching common LM practice."""
    shape = (in_dim,) + tuple(out_dims)
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def nonparam_ln(x, eps: float = 1e-5):
    """OLMo's non-parametric LayerNorm (no scale, no bias)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def norm_init(cfg: ModelConfig, key, stacked: Optional[int] = None):
    d = cfg.d_model
    shape = (d,) if stacked is None else (stacked, d)
    if cfg.norm == "nonparam_ln":
        return {}
    if cfg.norm == "layernorm":
        return {"w": jnp.ones(shape, jnp.float32),
                "b": jnp.zeros(shape, jnp.float32)}
    return {"w": jnp.zeros(shape, jnp.float32)}   # rmsnorm: stored as (w-1)


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "nonparam_ln":
        return nonparam_ln(x)
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE (NeoX half-rotation convention)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                     / (head_dim // 2))


def apply_rope(x, positions, theta: float):
    """x: (..., T, H, D); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., T, d/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., T, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked online-softmax attention
# ---------------------------------------------------------------------------

def _mask_block(q_pos, kv_pos, causal, window):
    """(Tq, Tk) bool allow-mask. window: None or traced scalar (tokens)."""
    allow = kv_pos[None, :] >= 0                        # padding slots use -1
    if causal:
        allow &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        allow &= (q_pos[:, None] - kv_pos[None, :]) < window
    return allow


def _heads_shardable(kh: int) -> bool:
    ctx = active_ctx()
    if ctx is None:
        return True
    ms = ctx.model_axis_size
    return ms <= 1 or kh % ms == 0


def blocked_attention(q, k, v, q_pos, kv_pos, *,
                      causal: bool = True,
                      window=None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None,
                      kv_chunk: int = 1024):
    """Online-softmax attention, scanning KV in chunks.

    q: (B, Tq, H, D)    k, v: (B, S, KH, D)   (GQA: H % KH == 0)
    q_pos: (B, Tq) int32; kv_pos: (B, S) int32 (-1 marks invalid slots).
    window may be a python int, None, or a traced scalar (per-layer choice).
    Returns (B, Tq, H, D).
    """
    B, Tq, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    ck = min(kv_chunk, S)
    if S % ck:
        pad = ck - S % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        S += pad
    nc = S // ck

    # When kv heads don't divide the model axis, shard the QUERY TIME dim
    # over it instead (context-parallel attention): carries stay T-sharded
    # and the chunk loop needs no per-iteration resharding (§Perf).
    t_role = None if _heads_shardable(KH) else "model"
    h_role = "heads" if _heads_shardable(KH) else None
    qr = (q.reshape(B, Tq, KH, G, D) * scale).astype(jnp.bfloat16)
    qr = hint(qr, "batch", t_role, h_role, None, None)
    # chunk-major layout for scan
    kc = k.reshape(B, nc, ck, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, ck, KH, D).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(B, nc, ck).transpose(1, 0, 2)
    kc = hint(kc, None, "batch", None, h_role, None)
    vc = hint(vc, None, "batch", None, h_role, None)

    m0 = hint(jnp.full((B, Tq, KH, G), NEG_INF, jnp.float32),
              "batch", t_role, h_role, None)
    l0 = hint(jnp.zeros((B, Tq, KH, G), jnp.float32),
              "batch", t_role, h_role, None)
    a0 = hint(jnp.zeros((B, Tq, KH, G, D), jnp.float32),
              "batch", t_role, h_role, None, None)

    if window is not None:
        window = jnp.asarray(window, jnp.int32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs                                  # (B,ck,KH,D), (B,ck)
        s = jnp.einsum("btkgd,bckd->btkgc", qr, kb.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        allow = jax.vmap(
            lambda qp, kp: _mask_block(qp, kp, causal, window))(q_pos, pb)
        allow = allow[:, :, None, None, :]               # (B,Tq,1,1,ck)
        s = jnp.where(allow, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * allow        # kill fully-masked rows
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "btkgc,bckd->btkgd", p.astype(jnp.bfloat16),
            vb.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        m_new = hint(m_new, "batch", t_role, h_role, None)
        l_new = hint(l_new, "batch", t_role, h_role, None)
        acc_new = hint(acc_new, "batch", t_role, h_role, None, None)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Tq, H, D).astype(q.dtype)


def blocked_attention_qchunked(q, k, v, q_pos, kv_pos, *,
                               causal: bool = True,
                               window: Optional[int] = None,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None,
                               q_chunk: int = 2048, kv_chunk: int = 1024):
    """§Perf variant of blocked_attention: q is chunked too, and the scan
    runs over a STATIC list of reachable (q-chunk, kv-chunk) pairs — causal
    masking skips the upper triangle entirely (2x fewer FLOPs) and a static
    sliding window keeps only the diagonal band (window/T of the work).

    ``window`` must be a python int here (static pair pruning); the layer
    scan regroups local/global layers so each gets a static window
    (transformer.attn_group_size).  The online-softmax merge is associative,
    so pair order doesn't matter.
    """
    B, Tq, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    cq = min(q_chunk, Tq)
    ck = min(kv_chunk, S)
    assert Tq % cq == 0 and S % ck == 0, (Tq, cq, S, ck)
    nq, nk = Tq // cq, S // ck

    # static reachable-pair list (assumes aligned layouts: q chunk i covers
    # positions [i*cq, (i+1)*cq) — true for training/prefill)
    pairs = []
    for i in range(nq):
        qlo, qhi = i * cq, (i + 1) * cq - 1
        for j in range(nk):
            klo, khi = j * ck, (j + 1) * ck - 1
            if causal and klo > qhi:
                continue
            if window is not None and (qlo - khi) >= window:
                continue
            pairs.append((i, j))
    pair_arr = jnp.asarray(pairs, jnp.int32)

    t_role = None if _heads_shardable(KH) else "model"
    h_role = "heads" if _heads_shardable(KH) else None
    qr = (q.reshape(B, nq, cq, KH, G, D) * scale).astype(jnp.bfloat16)
    qr = qr.transpose(1, 0, 2, 3, 4, 5)              # (nq, B, cq, KH, G, D)
    qp = q_pos.reshape(B, nq, cq).transpose(1, 0, 2)
    kc = k.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(B, nk, ck).transpose(1, 0, 2)
    qr = hint(qr, None, "batch", t_role, h_role, None, None)
    kc = hint(kc, None, "batch", None, h_role, None)
    vc = hint(vc, None, "batch", None, h_role, None)

    m0 = hint(jnp.full((nq, B, cq, KH, G), NEG_INF, jnp.float32),
              None, "batch", t_role, h_role, None)
    l0 = hint(jnp.zeros((nq, B, cq, KH, G), jnp.float32),
              None, "batch", t_role, h_role, None)
    a0 = hint(jnp.zeros((nq, B, cq, KH, G, D), jnp.float32),
              None, "batch", t_role, h_role, None, None)

    def body(carry, ij):
        m, l, acc = carry
        i, j = ij[0], ij[1]
        qb = jax.lax.dynamic_index_in_dim(qr, i, 0, keepdims=False)
        qpb = jax.lax.dynamic_index_in_dim(qp, i, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kc, j, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vc, j, 0, keepdims=False)
        pb = jax.lax.dynamic_index_in_dim(pc, j, 0, keepdims=False)
        s = jnp.einsum("btkgd,bckd->btkgc", qb, kb.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        allow = jax.vmap(
            lambda a_, b_: _mask_block(a_, b_, causal, window))(qpb, pb)
        allow = allow[:, :, None, None, :]
        s = jnp.where(allow, s, NEG_INF)
        m_i = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        m_new = jnp.maximum(m_i, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * allow
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + p.sum(axis=-1)
        a_new = a_i * alpha[..., None] + jnp.einsum(
            "btkgc,bckd->btkgd", p.astype(jnp.bfloat16),
            vb.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, 0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), pair_arr)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tq, H, D)
    return out.astype(q.dtype)


def reference_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                        softcap=None, scale=None):
    """Unblocked oracle for tests (materialises the full logits tensor)."""
    B, Tq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    qr = q.reshape(B, Tq, KH, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("btkgd,bskd->btkgs", qr, k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    allow = jax.vmap(
        lambda qp, kp: _mask_block(qp, kp, causal, window))(q_pos, kv_pos)
    s = jnp.where(allow[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Tq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA, optional cross-attention, optional KV cache)
# ---------------------------------------------------------------------------

def attention_init(cfg: ModelConfig, key, stacked: Optional[int] = None,
                   cross: bool = False):
    a = cfg.attention
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    L = () if stacked is None else (stacked,)

    def mk(k, *dims):
        full = L + dims
        flat = jax.random.truncated_normal(
            k, -2.0, 2.0, full, jnp.float32) / np.sqrt(dims[0])
        return flat

    p = {
        "wq": mk(ks[0], d, a.num_heads, a.head_dim),
        "wk": mk(ks[1], d, a.num_kv_heads, a.head_dim),
        "wv": mk(ks[2], d, a.num_kv_heads, a.head_dim),
        "wo": mk(ks[3], a.num_heads * a.head_dim, d),
    }
    if a.qkv_bias and not cross:
        p["bq"] = jnp.zeros(L + (a.num_heads, a.head_dim), jnp.float32)
        p["bk"] = jnp.zeros(L + (a.num_kv_heads, a.head_dim), jnp.float32)
        p["bv"] = jnp.zeros(L + (a.num_kv_heads, a.head_dim), jnp.float32)
    if a.qk_norm:
        p["q_norm"] = jnp.zeros(L + (a.head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros(L + (a.head_dim,), jnp.float32)
    return p


def attention_qkv(p, x, a: AttentionConfig, positions, *, rope: bool = True,
                  dtype=jnp.bfloat16, norm_eps: float = 1e-6):
    """Project to q, k, v and apply RoPE (after the per-head q/k RMSNorm
    where the config has one).  x: (B, T, D)."""
    q = jnp.einsum("btd,dhk->bthk", x, Q.cast(p["wq"], dtype))
    k = jnp.einsum("btd,dhk->bthk", x, Q.cast(p["wk"], dtype))
    v = jnp.einsum("btd,dhk->bthk", x, Q.cast(p["wv"], dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"], norm_eps)
        k = rmsnorm(k, p["k_norm"], norm_eps)
    if rope:
        q = apply_rope(q, positions, a.rope_theta)
        k = apply_rope(k, positions, a.rope_theta)
    q = hint(q, "batch", None, "heads", None)
    k = hint(k, "batch", None, "heads", None)
    v = hint(v, "batch", None, "heads", None)
    return q, k, v


def attention_out(p, o, dtype=jnp.bfloat16):
    B, T, H, D = o.shape
    return jnp.einsum("bthk,hkd->btd",
                      o.astype(dtype),
                      Q.cast(p["wo"], dtype).reshape(H, D, -1))


def self_attention(p, x, a: AttentionConfig, positions, *,
                   causal: bool = True, window=None, kv_chunk: int = 1024,
                   dtype=jnp.bfloat16):
    q, k, v = attention_qkv(p, x, a, positions, dtype=dtype)
    o = blocked_attention(q, k, v, positions, positions, causal=causal,
                          window=window, softcap=a.logit_softcap,
                          scale=a.attn_scale, kv_chunk=kv_chunk)
    return attention_out(p, o, dtype=dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None,
             stacked: Optional[int] = None):
    d = cfg.d_model
    f = cfg.d_ff if d_ff is None else d_ff
    ks = jax.random.split(key, 3)
    L = () if stacked is None else (stacked,)

    def mk(k, din, dout):
        return (jax.random.truncated_normal(k, -2.0, 2.0, L + (din, dout),
                                            jnp.float32) / np.sqrt(din))
    p = {"wo": mk(ks[2], f, d)}
    if cfg.ffn_glu:
        p["wg"] = mk(ks[0], d, f)
        p["wu"] = mk(ks[1], d, f)
    else:
        p["wi"] = mk(ks[0], d, f)
    return p


def _act(name: str, x):
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def mlp_apply(cfg: ModelConfig, p, x, dtype=jnp.bfloat16):
    if cfg.ffn_glu:
        g = jnp.einsum("btd,df->btf", x, Q.cast(p["wg"], dtype))
        u = jnp.einsum("btd,df->btf", x, Q.cast(p["wu"], dtype))
        h = _act(cfg.act, g) * u
    else:
        h = _act(cfg.act, jnp.einsum("btd,df->btf", x, Q.cast(p["wi"], dtype)))
    h = hint(h, "batch", None, "model")
    return jnp.einsum("btf,fd->btd", h, Q.cast(p["wo"], dtype))


# ---------------------------------------------------------------------------
# softcap
# ---------------------------------------------------------------------------

def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
