"""Pallas TPU kernel: paged decode attention (single query per slot).

The serving decode hot spot: every slot holds ONE fresh query token and a KV
cache whose *valid* length differs per slot (continuous batching admits and
retires requests independently).  A dense decode attention scans all
``max_len`` cache rows for every slot; this kernel gathers only each slot's
valid prefix — a per-slot ``seq_lens`` vector rides in scalar-prefetch SMEM,
KV blocks entirely past a slot's length are skipped with ``pl.when``, and
the index maps clamp to the slot's last valid block, so a freshly admitted
slot costs ``ceil(len/bk)`` block reads no matter how long the
compile-time cache envelope is.

Semantics are shared with ``flash_attention``: flash-style online softmax
over KV blocks, GQA by head grouping (no KV duplication), sliding
windows, and gemma2-style logit soft-capping.  ``ref.paged_decode_attention_
ref`` is the dense XLA oracle and serving fallback for non-TPU backends.

Tiling: grid (B, nk); the slot's query rows (H, d) stay resident; k/v
blocks (bk, KH, d) — every KV head of bk cache rows, so the block's last
two dims are the cache's own — stream through VMEM, and the body walks the
KV heads with strided (bk, d) loads; m/l/acc (H, ·) live in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_body(sl_ref, q_ref, load_kv, o_ref,
                 m_ref, l_ref, acc_ref, *,
                 scale: float, window: Optional[int],
                 softcap: Optional[float], bk: int, nk: int, kh: int):
    """Shared online-softmax body over one (bk, KH, d) KV block of one
    slot.  ``load_kv(h)`` yields KV head h's (bk, d) k and v tiles — a
    strided VMEM load on the full-width path, plus an int8-row dequant
    (1-byte rows times a per-row scale) on the quantized path.  The
    G = H // KH query heads of KV head h are rows [h*G, (h+1)*G) of the
    (H, d) query block."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    g = q_ref.shape[1] // kh

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sl = sl_ref[b]                                   # valid rows for slot b
    k0 = j * bk
    # block-level skip: anything in [k0, k0+bk) visible to the query row?
    reachable = k0 < sl
    if window is not None:
        # query position is sl-1; the window keeps kv_pos > qpos - window
        reachable = jnp.logical_and(
            reachable, (sl - 1) - (k0 + bk - 1) < window)

    @pl.when(reachable)
    def _():
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        allow = kpos < sl
        if window is not None:
            allow = jnp.logical_and(allow, (sl - 1) - kpos < window)
        for h in range(kh):
            rows = slice(h * g, (h + 1) * g)
            k, v = load_kv(h)                        # (bk, d) each
            q = q_ref[0, rows].astype(jnp.float32) * scale    # (G, d)
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (G, bk)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(allow, s, NEG_INF)
            m_prev = m_ref[rows]                     # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows] = l_ref[rows] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[rows] = (acc_ref[rows] * alpha
                             + jax.lax.dot_general(
                                 p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32))
            m_ref[rows] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(sl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, **kw):
    _decode_body(sl_ref, q_ref,
                 lambda h: (k_ref[0, :, h, :], v_ref[0, :, h, :]),
                 o_ref, m_ref, l_ref, acc_ref, **kw)


def _decode_kernel_q(sl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                     m_ref, l_ref, acc_ref, **kw):
    """int8-KV variant: k/v tiles arrive as int8 rows + per-row fp32 scales
    (models/quant.quantize_kv layout) and dequantise in VMEM right after the
    DMA — the HBM stream is 1 byte/element."""
    def load_kv(h):
        k = (k_ref[0, :, h, :].astype(jnp.float32)
             * ks_ref[0, :, h][:, None])
        v = (v_ref[0, :, h, :].astype(jnp.float32)
             * vs_ref[0, :, h][:, None])
        return k, v
    _decode_body(sl_ref, q_ref, load_kv, o_ref, m_ref, l_ref, acc_ref, **kw)


def _scratch(H: int, d: int):
    return [pltpu.VMEM((H, 1), jnp.float32),         # running max
            pltpu.VMEM((H, 1), jnp.float32),         # running denominator
            pltpu.VMEM((H, d), jnp.float32)]         # output accumulator


def _last_block(sl, b, bk: int):
    """Index of slot b's last valid KV block (0 for an empty slot).  Index
    maps clamp to it, so steps past a slot's length re-name the block
    already in VMEM and the pipeline issues no DMA for them."""
    return jnp.maximum(sl[b] - 1, 0) // bk


def paged_decode_attention_kernel_call(
        q: jax.Array, k: jax.Array, v: jax.Array, seq_lens: jax.Array, *,
        window: Optional[int] = None,
        softcap: Optional[float] = None,
        scale: Optional[float] = None,
        bk: int = 128,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        interpret: Optional[bool] = None) -> jax.Array:
    """q (B, H, d); k, v (B, S, KH, d); seq_lens (B,) int32 -> (B, H, d).

    ``seq_lens[b]`` counts the valid cache rows of slot b INCLUDING the
    just-written current token (the query attends to kv_pos < seq_lens[b]).
    GQA: query heads [h*G, (h+1)*G) read KV head h (H % KH == 0).  The
    cache length S is padded to a multiple of ``bk``; padded rows sit past
    every seq_len and are never touched.

    int8 KV: pass ``k``/``v`` as int8 with per-row fp32 ``k_scale``/
    ``v_scale`` (B, S, KH) — ``models/quant.quantize_kv`` layout.  Rows
    stream through VMEM as 1-byte lanes and dequantise in-kernel.
    """
    B, H, d = q.shape
    S, KH = k.shape[1], k.shape[2]
    quantized = k_scale is not None
    if scale is None:
        scale = d ** -0.5
    bk = min(bk, S)
    if S % bk:
        pad = bk - S % bk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if quantized:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
        S += pad
    nk = S // bk
    seq_lens = seq_lens.astype(jnp.int32)

    # one slot's whole (bk, KH, d) block per grid step: the block's last two
    # dims are the array's own (KH, d), as the TPU tiling requires
    def kv_map(b, j, sl):
        return (b, jnp.minimum(j, _last_block(sl, b, bk)), 0, 0)

    def sc_map(b, j, sl):
        return kv_map(b, j, sl)[:3]

    q_spec = pl.BlockSpec((1, H, d), lambda b, j, sl: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, KH, d), kv_map)
    sc_spec = pl.BlockSpec((1, bk, KH), sc_map)
    kw = dict(scale=scale, window=window, softcap=softcap, bk=bk, nk=nk,
              kh=KH)
    if quantized:
        kern = functools.partial(_decode_kernel_q, **kw)
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        operands = (q, k, k_scale, v, v_scale)
    else:
        kern = functools.partial(_decode_kernel, **kw)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, k, v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_scratch(H, d),
    )
    fn = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(seq_lens, *operands)


# ---------------------------------------------------------------------------
# Block-table-indexed variant (pooled prefix-shared KV)
# ---------------------------------------------------------------------------
# Same kernel body — it only ever reasons about LOGICAL positions (seq_lens,
# block index j) — but the KV lives in a shared physical block pool and each
# slot carries an indirection table.  The table rides in scalar-prefetch SMEM
# next to ``seq_lens`` and the k/v BlockSpec index maps translate logical
# block j of slot b to pool block ``tables[b, j]``; the existing block-skip
# (``j * bk < seq_lens[b]``) keeps invalid table tail entries unread.


def _decode_kernel_bt(sl_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, **kw):
    # the table is consumed by the index maps; the math is position-based
    del bt_ref
    _decode_kernel(sl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, **kw)


def _decode_kernel_bt_q(sl_ref, bt_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                        o_ref, m_ref, l_ref, acc_ref, **kw):
    del bt_ref
    _decode_kernel_q(sl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                     m_ref, l_ref, acc_ref, **kw)


def _bt_block(b, j, sl, bt, bs: int):
    """Pool block that grid step (b, j) names: slot b's logical block j,
    clamped to its last valid one."""
    return bt[b, jnp.minimum(j, _last_block(sl, b, bs))]


def held_block_tables(seq_lens: jax.Array, tables: jax.Array,
                      bs: int) -> jax.Array:
    """``tables`` (B, nb) with every empty slot's row (``seq_lens`` 0)
    pointed at the block the grid step before it names: the last block of
    the nearest non-empty slot before it, or, for the empty slots that
    lead the grid, the first block of the first non-empty one.  An empty
    slot computes nothing, and with its steps re-naming the block in VMEM
    the pipeline fetches nothing for it either.

    Layers that share ``seq_lens`` and ``tables`` share the result, so a
    caller makes it once for all of them (`transformer.decode_step_pooled`,
    once a step): made inside the wrapper it would cost a dozen small ops
    per layer.  A constant offset added to every entry keeps it held."""
    B, nb = tables.shape
    live = seq_lens > 0
    last = jnp.take_along_axis(
        tables, jnp.minimum(jnp.maximum(seq_lens - 1, 0) // bs,
                            nb - 1)[:, None], axis=1)[:, 0]
    prev = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1))
    held = jnp.where(prev >= 0, last[jnp.maximum(prev, 0)],
                     tables[jnp.argmax(live), 0])
    return jnp.where(live[:, None], tables, held[:, None])


def paged_decode_attention_bt_kernel_call(
        q: jax.Array, k: jax.Array, v: jax.Array, seq_lens: jax.Array,
        tables: jax.Array, *,
        window: Optional[int] = None,
        softcap: Optional[float] = None,
        scale: Optional[float] = None,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        interpret: Optional[bool] = None) -> jax.Array:
    """q (B, H, d); k, v (NB, bs, KH, d) physical block pool;
    seq_lens (B,) int32; tables (B, nb) int32 logical->physical block map
    -> (B, H, d).

    ``seq_lens[b]`` counts valid LOGICAL rows (< nb * bs) including the
    just-written token; lanes past it are masked, so garbage in partially
    written or stale pool blocks never contributes.  The kernel block size
    equals the pool block size ``bs`` (one grid step streams one physical
    block).  A slot of length 0 computes nothing, and fetches nothing
    where its row of ``tables`` is held (`held_block_tables`).

    int8 KV: int8 ``k``/``v`` pools + per-row fp32 ``k_scale``/``v_scale``
    (NB, bs, KH); the indirection tables address scale blocks and value
    blocks identically."""
    B, H, d = q.shape
    NB, bs, KH = k.shape[0], k.shape[1], k.shape[2]
    nk = tables.shape[1]
    quantized = k_scale is not None
    if scale is None:
        scale = d ** -0.5
    seq_lens = seq_lens.astype(jnp.int32)
    # OOB sentinel entries (unadmitted slots) clamp to a real block: the
    # pipeline fetches whatever the index map names, and seq_lens=0 masks
    # the compute — mirrors the reference's clamped gather
    tables = jnp.clip(tables.astype(jnp.int32), 0, NB - 1)

    def kv_map(b, j, sl, bt):
        return (_bt_block(b, j, sl, bt, bs), 0, 0, 0)

    def sc_map(b, j, sl, bt):
        return kv_map(b, j, sl, bt)[:3]

    q_spec = pl.BlockSpec((1, H, d), lambda b, j, sl, bt: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, bs, KH, d), kv_map)
    sc_spec = pl.BlockSpec((1, bs, KH), sc_map)
    kw = dict(scale=scale, window=window, softcap=softcap, bk=bs, nk=nk,
              kh=KH)
    if quantized:
        kern = functools.partial(_decode_kernel_bt_q, **kw)
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        operands = (q, k, k_scale, v, v_scale)
    else:
        kern = functools.partial(_decode_kernel_bt, **kw)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, k, v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_scratch(H, d),
    )
    fn = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(seq_lens, tables, *operands)
