"""Pallas TPU kernel: fused embedding gather + segment combine.

This is the SparseCore Fetch-unit/scVPU analogue (paper §3.5, Figure 7):
  * the scalar-prefetched id list plays the Fetch unit's descriptor stream —
    BlockSpec index_maps consume the prefetched ids so each grid step DMAs
    exactly one embedding row HBM→VMEM (the SC's per-tile HBM channel),
  * the VMEM accumulator is the Spmem tile slice,
  * the multiply-accumulate combine is the scVPU / cross-channel reduce.

Three entry points:
  * ``gather_kernel_call``  — (V, D), (B, Vl) -> (B, Vl, D) row gather.
  * ``lookup_kernel_call``  — (V, D), (B, Vl) -> (B, D) fused gather+combine
    (sum or mean over the valency axis) without materialising (B, Vl, D) —
    the win over the XLA gather+reduce path.
  * ``fused_lookup_kernel_call`` — ONE launch over every table: the fused
    row space (R, Dm) is the concatenation of all width-groups (rows padded
    to a common lane width Dm) and the scalar-prefetched descriptor stream
    ``rows (B, S)`` / ``slots (S,)`` plays the SC Fetch unit's per-table
    descriptor list.  Each grid step DMAs one absolute row and accumulates
    it into the output slot of the table that owns descriptor column ``s``;
    the accumulator flushes when the slot id changes.  This amortises one
    CISC-instruction issue (one ``pallas_call``) across the whole table
    batch instead of paying it per width-group.

Invalid ids (< 0) contribute zero.  On real TPU hardware D should be padded
to a multiple of 128 lanes; interpret mode (CPU validation) has no such
constraint.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


# ---------------------------------------------------------------------------
# Row gather
# ---------------------------------------------------------------------------

def _gather_kernel(ids_ref, table_ref, out_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)
    valid = ids_ref[b, j] >= 0

    @pl.when(valid)
    def _():
        out_ref[0, 0, :] = table_ref[0, :]

    @pl.when(jnp.logical_not(valid))
    def _():
        out_ref[0, 0, :] = jnp.zeros_like(out_ref[0, 0, :])


def gather_kernel_call(table: jax.Array, ids: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """table (V, D) f32, ids (B, Vl) i32 -> (B, Vl, D) f32."""
    V, D = table.shape
    B, Vl = ids.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Vl),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, j, ids: (jnp.maximum(ids[b, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, j, ids: (b, j, 0)),
    )
    fn = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Vl, D), table.dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(ids, table)


# ---------------------------------------------------------------------------
# Fused gather + combine
# ---------------------------------------------------------------------------

def _lookup_kernel(ids_ref, table_ref, out_ref, acc_ref, *, n_val: int,
                   mean: bool):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = ids_ref[b, j] >= 0

    @pl.when(valid)
    def _():
        acc_ref[...] += table_ref[0, :].astype(jnp.float32)

    @pl.when(j == n_val - 1)
    def _():
        acc = acc_ref[...]
        if mean:
            count = jnp.zeros((), jnp.float32)
            for jj in range(n_val):
                count += (ids_ref[b, jj] >= 0).astype(jnp.float32)
            acc = acc / jnp.maximum(count, 1.0)
        out_ref[0, :] = acc.astype(out_ref.dtype)


def lookup_kernel_call(table: jax.Array, ids: jax.Array, *,
                       combiner: str = "sum",
                       interpret: Optional[bool] = None) -> jax.Array:
    """table (V, D), ids (B, Vl) -> (B, D) combined (sum/mean over valency)."""
    V, D = table.shape
    B, Vl = ids.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Vl),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, j, ids: (jnp.maximum(ids[b, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, j, ids: (b, 0)),
        scratch_shapes=[pltpu.VMEM((D,), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_lookup_kernel, n_val=Vl, mean=(combiner == "mean")),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(ids, table)


# ---------------------------------------------------------------------------
# Fused multi-group lookup (one grid over every table)
# ---------------------------------------------------------------------------

def _fused_lookup_kernel(rows_ref, slots_ref, means_ref, table_ref, out_ref,
                         acc_ref, cnt_ref, *, n_desc: int):
    b = pl.program_id(0)
    s = pl.program_id(1)
    slot = slots_ref[s]
    # descriptor columns are sorted by slot, so each output slot is a
    # contiguous run of grid steps: reset at the run head, flush at its tail
    prev_same = jnp.where(s > 0, slots_ref[jnp.maximum(s - 1, 0)] == slot,
                          False)

    @pl.when(jnp.logical_not(prev_same))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    valid = rows_ref[b, s] >= 0

    @pl.when(valid)
    def _():
        acc_ref[...] += table_ref[0, :].astype(jnp.float32)
        cnt_ref[...] += 1.0

    last = jnp.where(s < n_desc - 1,
                     slots_ref[jnp.minimum(s + 1, n_desc - 1)] != slot, True)

    @pl.when(last)
    def _():
        acc = acc_ref[...]
        acc = jnp.where(means_ref[slot] > 0,
                        acc / jnp.maximum(cnt_ref[0], 1.0), acc)
        out_ref[0, 0, :] = acc.astype(out_ref.dtype)


def _fused_lookup_kernel_q(rows_ref, slots_ref, means_ref, table_ref,
                           scale_ref, out_ref, acc_ref, cnt_ref, *,
                           n_desc: int, tile: int):
    """int8-table variant: dequantise the gathered row in VMEM before the
    accumulate.  ``table_ref`` block is (1, Dm) int8, ``scale_ref`` block is
    (1, nt) f32 with ``nt * tile == Dm`` (QTensor per-row tile scales)."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    slot = slots_ref[s]
    prev_same = jnp.where(s > 0, slots_ref[jnp.maximum(s - 1, 0)] == slot,
                          False)

    @pl.when(jnp.logical_not(prev_same))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    valid = rows_ref[b, s] >= 0

    @pl.when(valid)
    def _():
        q = table_ref[0, :].astype(jnp.float32).reshape(-1, tile)
        row = (q * scale_ref[0, :][:, None]).reshape(-1)
        acc_ref[...] += row
        cnt_ref[...] += 1.0

    last = jnp.where(s < n_desc - 1,
                     slots_ref[jnp.minimum(s + 1, n_desc - 1)] != slot, True)

    @pl.when(last)
    def _():
        acc = acc_ref[...]
        acc = jnp.where(means_ref[slot] > 0,
                        acc / jnp.maximum(cnt_ref[0], 1.0), acc)
        out_ref[0, 0, :] = acc.astype(out_ref.dtype)


def fused_lookup_kernel_call(table: jax.Array, rows: jax.Array,
                             slots: jax.Array, means: jax.Array, *,
                             scales: jax.Array = None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """One launch over every table of a fused row space.

    table (R, Dm); rows (B, S) absolute fused row ids (-1 invalid);
    slots (S,) i32 non-decreasing output-slot id per descriptor column;
    means (K,) i32, 1 where slot k mean-combines -> (B, K, Dm) combined.

    int8 tables (inference serving): pass ``table`` as int8 with per-row
    tile-wise fp32 ``scales (R, nt)`` (``models/quant.QTensor`` layout,
    ``nt = Dm // tile``).  Each grid step then DMAs a 1-byte row plus its
    scale row and dequantises inside the accumulate — the HBM row stream
    shrinks ~4x while the combine math stays fp32.
    """
    R, Dm = table.shape
    B, S = rows.shape
    K = means.shape[0]
    quantized = scales is not None
    in_specs = [
        pl.BlockSpec((1, Dm),
                     lambda b, s, rows, slots, means:
                     (jnp.maximum(rows[b, s], 0), 0)),
    ]
    operands = [table]
    kern = functools.partial(_fused_lookup_kernel, n_desc=S)
    out_dtype = table.dtype
    if quantized:
        nt = scales.shape[1]
        in_specs.append(
            pl.BlockSpec((1, nt),
                         lambda b, s, rows, slots, means:
                         (jnp.maximum(rows[b, s], 0), 0)))
        operands.append(scales)
        kern = functools.partial(_fused_lookup_kernel_q, n_desc=S,
                                 tile=Dm // nt)
        out_dtype = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, S),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Dm),
                               lambda b, s, rows, slots, means:
                               (b, slots[s], 0)),
        scratch_shapes=[pltpu.VMEM((Dm,), jnp.float32),
                        pltpu.VMEM((1,), jnp.float32)],
    )
    fn = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Dm), out_dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(rows, slots, means, *operands)
