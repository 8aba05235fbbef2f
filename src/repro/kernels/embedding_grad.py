"""Pallas TPU kernels: embedding gradient scatter (the SC Flush unit, §3.5).

"The Flush Unit writes updated parameters to HBM during the backward pass."

``scatter_kernel_call``: ids are UNIQUE (the engine always deduplicates
before the backward all-to-all, paper §3.4) and sorted ascending with -1
padding at the tail.  Each grid step DMAs one gradient row VMEM→HBM into the
(aliased) table-shaped gradient buffer; untouched rows keep their zero
initialisation via input/output aliasing.

``fused_scatter_kernel_call``: the backward of the fused multi-group lookup —
the same (rows, slots) descriptor stream drives one grid over every table,
read-modify-writing each descriptor's upstream slot gradient into its fused
row.  Descriptor rows may repeat (interpret mode runs the grid sequentially,
so read-after-write accumulation is exact; on real hardware duplicate rows
would be serialised per HBM channel by the Flush unit).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _scatter_kernel(ids_ref, grads_ref, zeros_ref, out_ref):
    i = pl.program_id(0)
    valid = ids_ref[i] >= 0

    @pl.when(valid)
    def _():
        out_ref[...] = zeros_ref[...] + grads_ref[...]


def scatter_kernel_call(grads: jax.Array, ids: jax.Array, vocab: int, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """grads (N, D), unique sorted ids (N,) i32 (-1 tail) -> (V, D) grad table."""
    N, D = grads.shape
    dtable0 = jnp.zeros((vocab, D), grads.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, D), lambda i, ids: (i, 0)),                 # grads
            pl.BlockSpec((1, D), lambda i, ids: (jnp.maximum(ids[i], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda i, ids: (jnp.maximum(ids[i], 0), 0)),
    )
    fn = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vocab, D), grads.dtype),
        input_output_aliases={2: 0},   # alias the zero table (arg idx incl. ids)
        interpret=resolve_interpret(interpret),
    )
    return fn(ids, grads, dtable0)


# ---------------------------------------------------------------------------
# Fused multi-group gradient scatter
# ---------------------------------------------------------------------------

def _fused_scatter_kernel(rows_ref, slots_ref, gout_ref, zeros_ref, out_ref):
    b = pl.program_id(0)
    s = pl.program_id(1)
    del zeros_ref  # present only to seed the aliased output with zeros
    valid = rows_ref[b, s] >= 0

    @pl.when(valid)
    def _():
        out_ref[0, :] += gout_ref[0, 0, :].astype(out_ref.dtype)


def fused_scatter_kernel_call(gout: jax.Array, rows: jax.Array,
                              slots: jax.Array, vocab: int, *,
                              interpret: Optional[bool] = None) -> jax.Array:
    """gout (B, K, Dm) slot grads (pre-scaled for mean combiners); rows (B, S)
    absolute fused row ids (-1 invalid); slots (S,) i32 slot per descriptor
    column -> (R, Dm) accumulated gradient over the fused row space."""
    B, K, Dm = gout.shape
    S = rows.shape[1]
    dtable0 = jnp.zeros((vocab, Dm), gout.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S),
        in_specs=[
            pl.BlockSpec((1, 1, Dm),
                         lambda b, s, rows, slots: (b, slots[s], 0)),
            pl.BlockSpec((1, Dm),
                         lambda b, s, rows, slots:
                         (jnp.maximum(rows[b, s], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, Dm),
                               lambda b, s, rows, slots:
                               (jnp.maximum(rows[b, s], 0), 0)),
    )
    fn = pl.pallas_call(
        _fused_scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vocab, Dm), gout.dtype),
        # alias the zero table (arg index counts the two prefetched
        # descriptor args)
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret),
    )
    return fn(rows, slots, gout, dtable0)
