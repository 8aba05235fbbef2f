"""Compute/communication overlap by matmul decomposition (ASPLOS'23 [59],
cited by the paper §7.10: "effective compute-communication overlap").

``overlapped_matmul_ag``: y = all_gather(x) @ w, decomposed into |axis|
chunks: at every step each shard multiplies the chunk it currently holds
while ``lax.ppermute`` rotates the next chunk in — the collective rides under
the MXU work instead of serialising before it.

``overlapped_matmul_rs``: y = reduce_scatter(x @ w) with the same rotation on
the output side.

``software_pipeline``: the generic two-stage double-buffer the SparseCore
embedding executor uses — stage A (id all-to-all) of item k+1 is issued
before stage B (gather + combine) of item k consumes its buffer, so the
collective rides under the previous group's compute.

Used by the §Perf hillclimb for TP layers; correctness is tested against the
naive gather-then-matmul in tests/test_overlap.py.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec


def software_pipeline(stage_a: Callable, stage_b: Callable,
                      items: Sequence) -> List:
    """Run ``[stage_b(stage_a(x), x) for x in items]`` software-pipelined.

    Double-buffered issue order: stage A of item k+1 is emitted *before*
    stage B of item k, so when stage A ends in a collective (the embedding
    id all-to-all) and stage B is compute (owner gather + combine), the
    compiler can overlap item k+1's communication with item k's compute.
    Pure reordering — results are identical to the sequential loop.
    """
    items = list(items)
    if not items:
        return []
    out = []
    buf = stage_a(items[0])
    for k, item in enumerate(items):
        nxt = stage_a(items[k + 1]) if k + 1 < len(items) else None
        out.append(stage_b(buf, item))
        buf = nxt
    return out


def overlapped_matmul_ag(x_shard, w, axis: str):
    """x_shard: (m_local, k); w: (k, n) local weight shard of a matmul whose
    LHS is row-sharded over `axis`.  Computes all_gather(x) @ w with the
    gather decomposed into size-1 ring hops (runs inside shard_map)."""
    s = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m_l = x_shard.shape[0]
    perm_fwd = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, t):
        chunk, acc = carry
        # the chunk currently held came from shard (idx - t) mod s
        src = (idx - t) % s
        part = chunk @ w                      # compute current chunk
        acc = jax.lax.dynamic_update_slice(
            acc, part, (src * m_l, jnp.zeros((), jnp.int32)))
        chunk = jax.lax.ppermute(chunk, axis, perm_fwd)  # prefetch next
        return (chunk, acc), None

    acc0 = jnp.zeros((m_l * s, w.shape[1]), x_shard.dtype)
    (chunk, acc), _ = jax.lax.scan(
        step, (x_shard, acc0), jnp.arange(s))
    return acc


def overlapped_matmul_rs(x, w_shard, axis: str):
    """reduce_scatter(x @ w, axis) with rotation: x (m, k_local) row-major
    activations, w_shard (k_local, n): each step computes one output block
    and passes the partial around the ring (ring reduce-scatter fused with
    the matmul)."""
    s = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = x.shape[0]
    assert m % s == 0
    m_b = m // s
    perm = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, t):
        acc = carry                            # (m_b, n) partial in flight
        # block this shard contributes at step t: after the remaining
        # (s - t) ring hops the partial lands on the block's owner
        blk = (idx - t) % s
        xb = jax.lax.dynamic_slice(
            x, (blk * m_b, jnp.zeros((), jnp.int32)), (m_b, x.shape[1]))
        acc = acc + xb @ w_shard
        acc = jax.lax.ppermute(acc, axis, perm)
        return acc, None

    acc0 = jnp.zeros((m_b, w_shard.shape[1]), x.dtype)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(s))
    # after s hops the accumulated block lands on its owner
    return acc
