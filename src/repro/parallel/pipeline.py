"""Pipeline parallelism via shard_map + collective_permute (DESIGN.md §5).

Maps pipeline stages onto a mesh axis (the "pod" axis on the multi-pod mesh —
Table 3's GPT-3 best pick used pipeline=16 across the slice).  GPipe-style
schedule: M microbatches flow through S stages; stage s runs layer block s;
activations hop to the next stage with ``lax.ppermute``.

The whole schedule is one shard_map program: a scan over (M + S - 1) ticks
where every stage computes its resident microbatch then shifts activations —
the standard JAX SPMD pipeline pattern.  Bubble fraction = (S-1)/(M+S-1).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec


def pipeline_apply(layer_fn: Callable, params_stacked, x, *, mesh,
                   stage_axis: str, microbatches: int):
    """Run a layer stack split into |stage_axis| pipeline stages.

    layer_fn(stage_params, x) -> x: applies one stage's layer block.
    params_stacked: pytree with leading dim = num_stages (sharded over
    stage_axis).  x: (B, ...) with B % microbatches == 0.
    Returns y with the same shape as x.
    """
    S = mesh.shape[stage_axis]
    M = microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)

    def local(params_local, x_local):
        # x_local: full batch on every stage (replicated over stage_axis);
        # only stage 0's input matters — others consume permuted activations.
        stage = jax.lax.axis_index(stage_axis)
        params_l = jax.tree.map(lambda p: p[0], params_local)
        mb = x_local.reshape((M, B // M) + x_local.shape[1:])
        ticks = M + S - 1

        def tick(carry, t):
            buf, out = carry                      # buf: (B//M, ...) resident
            # stage 0 loads microbatch t (if in range)
            load = jnp.where(t < M, t, M - 1)
            incoming = mb[load]
            buf = jnp.where(stage == 0, incoming, buf)
            y = layer_fn(params_l, buf)
            # last stage stores its finished microbatch (t - (S-1))
            store = t - (S - 1)
            ok = (stage == S - 1) & (store >= 0) & (store < M)
            out = jax.lax.cond(
                ok,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(store, 0),) + (0,) * y.ndim),
                lambda o: o, out)
            # shift activations to the next stage
            y = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            return (y, out), None

        buf0 = jnp.zeros_like(mb[0])
        out0 = jnp.zeros_like(mb)
        (buf, out), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(ticks))
        # only the last stage holds the result; broadcast it
        out = jax.lax.psum(
            jnp.where(stage == S - 1, out, jnp.zeros_like(out)), stage_axis)
        return out.reshape(x_local.shape)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(), check_vma=False)
    return fn(params_stacked, x)


def bubble_fraction(num_stages: int, microbatches: int) -> float:
    return (num_stages - 1) / (microbatches + num_stages - 1)
