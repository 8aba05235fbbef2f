"""Mixture-of-Experts layer with expert-parallel all-to-all dispatch.

Four dispatch paths share one router:

  * ``moe_local``   — sort-based dispatch, no collectives.  The reference
    implementation and the single-device (smoke-test) path.
  * ``moe_ep``      — shard_map expert parallelism: tokens are exchanged with
    ``lax.all_to_all`` over the model axis (the paper's SparseCore traffic
    pattern — variable-length all-to-all, §3.4), experts live ``E/|model|``
    per shard, expert weights are FSDP-gathered over the data axes.
  * ``moe_decode``  — tiny-token-count path (decode): tokens are replicated
    over the model axis (they are ~KiB), every shard computes its local
    experts at small capacity, partial outputs are psum-merged.

  * ``moe_held``    — the layer one chip of an expert-parallel deployment
    runs when it holds ``held_experts`` of the experts and no exchange:
    the router scores all ``num_experts``, and the layer computes, for
    every token, the part of the result its own experts give.  It is
    *dropless*: every held expert sees every token, weighted by the token's
    gate for it (0 where not chosen), so a token's result never depends on
    which tokens share its batch.  The pooled serving path uses it.

The first three are *dropping* MoE with a static capacity factor, matching
GSPMD-style production MoE.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.parallel.context import LOCAL, ParallelContext

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def held_experts(m: MoEConfig) -> int:
    """Experts whose weights this layer holds."""
    return m.held_experts or m.num_experts


def moe_init(cfg: ModelConfig, key, stacked: Optional[int] = None):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    L = () if stacked is None else (stacked,)
    E = held_experts(m)

    def mk(k, *dims):
        return (jax.random.truncated_normal(k, -2.0, 2.0, L + dims,
                                            jnp.float32)
                / np.sqrt(dims[-2]))

    p = {
        "router": mk(ks[0], d, m.num_experts),
        "wo": mk(ks[3], E, m.expert_ffw, d),
    }
    if cfg.ffn_glu:
        p["wg"] = mk(ks[1], E, d, m.expert_ffw)
        p["wu"] = mk(ks[2], E, d, m.expert_ffw)
    else:
        p["wi"] = mk(ks[1], E, d, m.expert_ffw)
    if m.expert_bias:
        p["expert_bias"] = jnp.zeros(L + (m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        f = m.shared_ffw * m.num_shared_experts
        p["shared"] = {
            "wg": mk(ks[4], d, f),
            "wu": mk(ks[5], d, f),
            "wo": mk(ks[6], f, d),
        }
    return p


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

def router_topk(cfg: ModelConfig, p, x, dtype=jnp.bfloat16):
    """x: (S, D) -> gates (S, k), expert idx (S, k), aux load-balance loss."""
    m = cfg.moe
    logits = jnp.einsum("sd,de->se", x, p["router"].astype(dtype)
                        ).astype(jnp.float32)
    if m.router_softcap:
        logits = m.router_softcap * jnp.tanh(logits / m.router_softcap)
    if m.score == "sigmoid":
        # lfm2: independent scores; the expert bias steers the choice only
        # (auxiliary-loss-free balancing), so there is no auxiliary loss
        scores = jax.nn.sigmoid(logits)
        pick = scores
        if m.expert_bias:
            pick = scores + jax.lax.stop_gradient(
                p["expert_bias"].astype(jnp.float32))
        _, eidx = jax.lax.top_k(pick, m.top_k)
        gates = jnp.take_along_axis(scores, eidx, axis=-1)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        return gates, eidx, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    me = probs.mean(axis=0)                                   # (E,)
    onehot = jax.nn.one_hot(eidx[:, 0], m.num_experts)        # top-1 fraction
    ce = onehot.mean(axis=0)
    aux = m.num_experts * jnp.sum(me * ce)
    return gates, eidx, aux


def _shared_expert(cfg: ModelConfig, p, x, dtype=jnp.bfloat16):
    g = jnp.einsum("sd,df->sf", x, p["wg"].astype(dtype))
    u = jnp.einsum("sd,df->sf", x, p["wu"].astype(dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("sf,fd->sd", h, p["wo"].astype(dtype))


def _expert_ffn(cfg: ModelConfig, p, buf, dtype=jnp.bfloat16):
    """buf: (E, C, D) -> (E, C, D) with per-expert weights (E, D, F)/(E, F, D)."""
    if cfg.ffn_glu:
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dtype)))
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# Local sort-based dispatch (reference / single device)
# ---------------------------------------------------------------------------

def _dispatch_sorted(x, gates, eidx, num_experts: int, capacity: int):
    """Sort-based dropping dispatch.

    x: (S, D); gates/eidx: (S, k).  Returns (buf (E, C, D), combine closure).
    """
    S, D = x.shape
    k = eidx.shape[1]
    flat_e = eidx.reshape(-1)                                  # (S*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = order // k
    counts = jnp.bincount(flat_e, length=num_experts)
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(S * k) - starts[sorted_e]
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos, num_experts * capacity)
    src = x[token_of] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((num_experts * capacity + 1, x.shape[1]), x.dtype)
    buf = buf.at[slot].set(src, mode="drop",
                           unique_indices=False)
    buf = buf[:-1].reshape(num_experts, capacity, D)
    gate_sorted = gates.reshape(-1)[order]

    def combine(y):                                            # y: (E, C, D)
        y_flat = jnp.concatenate(
            [y.reshape(num_experts * capacity, D),
             jnp.zeros((1, D), y.dtype)], axis=0)
        contrib = (y_flat[slot] * gate_sorted[:, None].astype(y.dtype)
                   * keep[:, None].astype(y.dtype))
        out = jnp.zeros((S, D), y.dtype).at[token_of].add(contrib)
        return out

    dropped = 1.0 - keep.mean()
    return buf, combine, dropped


def capacity_for(tokens: int, m: MoEConfig, factor: float) -> int:
    return max(4, int(math.ceil(tokens * m.top_k * factor / m.num_experts)))


def moe_local(cfg: ModelConfig, p, x, *, capacity_factor: float = 1.25,
              dtype=jnp.bfloat16):
    """x: (S, D) -> (out (S, D), aux loss, dropped fraction)."""
    m = cfg.moe
    S = x.shape[0]
    gates, eidx, aux = router_topk(cfg, p, x, dtype)
    C = capacity_for(S, m, capacity_factor)
    buf, combine, dropped = _dispatch_sorted(x, gates, eidx, m.num_experts, C)
    y = _expert_ffn(cfg, p, buf, dtype)
    out = combine(y)
    if m.num_shared_experts:
        out = out + _shared_expert(cfg, p["shared"], x, dtype)
    return out, aux, dropped


# ---------------------------------------------------------------------------
# One chip's share of the experts, dropless, with no exchange
# ---------------------------------------------------------------------------

def moe_held(cfg: ModelConfig, p, x, *, dtype=jnp.bfloat16):
    """x: (S, D) -> (out (S, D), routed (S, held) bool).

    Routes over all ``num_experts`` and returns the part of the layer's
    result that the held experts ``[first_expert, first_expert + held)``
    give: each token's gated sum over those of its top-k choices that are
    held.  ``routed[s, e]`` is whether token s chose held expert e.  Every
    held expert computes every token (gate 0 where it was not chosen), so
    nothing is dropped and rows never interact."""
    m = cfg.moe
    E = held_experts(m)
    assert 0 <= m.first_expert and m.first_expert + E <= m.num_experts, m
    gates, eidx, _ = router_topk(cfg, p, x, dtype)
    mine = eidx[:, :, None] == (m.first_expert
                                + jnp.arange(E, dtype=eidx.dtype))
    g = jnp.sum(jnp.where(mine, gates[:, :, None], 0.0), axis=1)   # (S, E)
    with jax.named_scope("moe_held"):
        if cfg.ffn_glu:
            h = (jax.nn.silu(jnp.einsum("sd,edf->esf", x,
                                        p["wg"].astype(dtype)))
                 * jnp.einsum("sd,edf->esf", x, p["wu"].astype(dtype)))
        else:
            h = jax.nn.silu(jnp.einsum("sd,edf->esf", x,
                                       p["wi"].astype(dtype)))
        # the gates weight each expert's hidden units, and one product sums
        # over experts and units alike
        h = (h.astype(jnp.float32) * g.T[:, :, None]).astype(dtype)
        out = jnp.einsum("esf,efd->sd", h, p["wo"].astype(dtype),
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), jnp.any(mine, axis=1)


def routed_load(routed, live):
    """(token-expert pairs of ``live`` rows routed to held experts, held
    experts with at least one such pair), int32; routed (S, E), live (S,)."""
    hit = routed & live[:, None]
    return jnp.stack([jnp.sum(hit), jnp.sum(jnp.any(hit, axis=0))]
                     ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Expert-parallel all-to-all dispatch (shard_map)
# ---------------------------------------------------------------------------

def _fsdp_gather(w, axes: Tuple[str, ...], gather_dim: int,
                 bf16: bool = False):
    if bf16 and w.dtype == jnp.float32:
        # cast BEFORE the gather: halves FSDP wire traffic (§Perf)
        w = w.astype(jnp.bfloat16)
    for a in axes:
        w = jax.lax.all_gather(w, a, axis=gather_dim, tiled=True)
    return w


def moe_ep(cfg: ModelConfig, p, x, ctx: ParallelContext, *,
           batch_spec, seq_spec, capacity_factor: float = 1.25,
           dtype=jnp.bfloat16):
    """Expert-parallel MoE over (B, T, D) activations.

    Tokens sharded over (batch_spec, seq_spec); experts sharded over
    ctx.model_axis; expert weights FSDP-sharded on D over ctx.fsdp_axes.
    Emits lax.all_to_all over the model axis — the paper's §3.4 traffic.
    """
    m = cfg.moe
    ES = ctx.model_axis_size
    if ES <= 1 or not ctx.has_mesh:
        B, T, D = x.shape
        out, aux, dropped = moe_local(
            cfg, p, x.reshape(B * T, D),
            capacity_factor=capacity_factor, dtype=dtype)
        return out.reshape(B, T, D), aux, dropped
    E_loc = m.num_experts // ES
    axis = ctx.model_axis
    fsdp_axes = ctx.fsdp_axes
    bf16g = ctx.bf16_fsdp_gather

    B, T, D = x.shape
    # local token count per device (shard_map blocks)
    b_sh = math.prod(ctx.axis_size(a) for a in _as_tuple(batch_spec))
    t_sh = math.prod(ctx.axis_size(a) for a in _as_tuple(seq_spec))
    S_loc = (B // b_sh) * (T // t_sh)
    C_send = capacity_for(S_loc, m, capacity_factor) * E_loc  # per-dest slots
    C_loc = C_send * ES // E_loc                              # per-expert slots

    def local_fn(x_loc, router, wg, wu, wi, wo, shared):
        xs = x_loc.reshape(-1, D)                              # (S_loc, D)
        router = _fsdp_gather(router, fsdp_axes, 0, bf16g)
        gates, eidx, aux = router_topk(cfg, {"router": router}, xs, dtype)
        aux = jax.lax.pmean(aux, axis)

        # ---- forward all-to-all: route (token, k) pairs to expert shards
        flat_e = eidx.reshape(-1)                              # (S_loc*k,)
        dest = flat_e // E_loc
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        token_of = order // m.top_k
        counts = jnp.bincount(dest, length=ES)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(flat_e.shape[0]) - starts[sorted_dest]
        keep = pos < C_send
        slot = jnp.where(keep, sorted_dest * C_send + pos, ES * C_send)
        src = xs[token_of] * keep[:, None].astype(xs.dtype)
        send = jnp.zeros((ES * C_send + 1, D), xs.dtype).at[slot].set(
            src, mode="drop")[:-1]
        send_eloc = jnp.full((ES * C_send + 1,), E_loc, jnp.int32).at[slot].set(
            (flat_e[order] % E_loc).astype(jnp.int32), mode="drop")[:-1]
        # exchange: recv[j] = block sent to me by shard j
        recv = jax.lax.all_to_all(
            send.reshape(ES, C_send, D), axis, 0, 0, tiled=False)
        recv_eloc = jax.lax.all_to_all(
            send_eloc.reshape(ES, C_send), axis, 0, 0, tiled=False)

        # ---- local dispatch to E_loc experts
        r_flat = recv.reshape(ES * C_send, D)
        re = recv_eloc.reshape(ES * C_send)
        order2 = jnp.argsort(re, stable=True)
        sorted_e2 = re[order2]
        counts2 = jnp.bincount(re, length=E_loc + 1)[:E_loc]
        starts2 = jnp.concatenate(
            [jnp.zeros((1,), counts2.dtype), jnp.cumsum(counts2)[:-1]])
        pos2 = jnp.arange(re.shape[0]) - starts2[
            jnp.minimum(sorted_e2, E_loc - 1)]
        keep2 = (pos2 < C_loc) & (sorted_e2 < E_loc)
        slot2 = jnp.where(keep2, sorted_e2 * C_loc + pos2, E_loc * C_loc)
        buf = jnp.zeros((E_loc * C_loc + 1, D), xs.dtype).at[slot2].set(
            r_flat[order2] * keep2[:, None].astype(xs.dtype), mode="drop")[:-1]
        buf = buf.reshape(E_loc, C_loc, D)

        # ---- expert FFN with FSDP-gathered weights
        wloc = {}
        for name, w in (("wg", wg), ("wu", wu), ("wi", wi)):
            if w is not None:
                wloc[name] = _fsdp_gather(w, fsdp_axes, 1, bf16g)
        wloc["wo"] = _fsdp_gather(wo, fsdp_axes, 2, bf16g)
        y = _expert_ffn(cfg, wloc, buf, dtype)                 # (E_loc, C_loc, D)

        # ---- reverse path
        y_flat = jnp.concatenate(
            [y.reshape(E_loc * C_loc, D), jnp.zeros((1, D), y.dtype)], 0)
        y_sorted = y_flat[slot2] * keep2[:, None].astype(y.dtype)
        y_recv_order = jnp.zeros((ES * C_send, D), y.dtype).at[order2].set(
            y_sorted)
        y_back = jax.lax.all_to_all(
            y_recv_order.reshape(ES, C_send, D), axis, 0, 0, tiled=False)
        yb_flat = jnp.concatenate(
            [y_back.reshape(ES * C_send, D), jnp.zeros((1, D), y.dtype)], 0)
        gate_sorted = gates.reshape(-1)[order]
        contrib = (yb_flat[slot] * gate_sorted[:, None].astype(y.dtype)
                   * keep[:, None].astype(y.dtype))
        out = jnp.zeros((xs.shape[0], D), y.dtype).at[token_of].add(contrib)

        if m.num_shared_experts:
            sh = {k2: _fsdp_gather(v, fsdp_axes, 1 if k2 == "wo" else 0,
                                   bf16g)
                  for k2, v in shared.items()}
            out = out + _shared_expert(cfg, sh, xs, dtype)
        dropped = jax.lax.pmean(1.0 - keep.mean(), axis)
        return out.reshape(x_loc.shape), aux, dropped

    fs = tuple(fsdp_axes) if fsdp_axes else None
    w_specs = dict(
        router=P(fs, None),
        wg=P(axis, fs, None), wu=P(axis, fs, None), wi=P(axis, fs, None),
        wo=P(axis, None, fs),
        shared={"wg": P(fs, None), "wu": P(fs, None), "wo": P(None, fs)},
    )
    args = dict(
        router=p["router"],
        wg=p.get("wg"), wu=p.get("wu"), wi=p.get("wi"), wo=p["wo"],
        shared=p.get("shared", {"wg": None, "wu": None, "wo": None}),
    )
    in_specs = (P(batch_spec, seq_spec, None),
                w_specs["router"], w_specs["wg"], w_specs["wu"],
                w_specs["wi"], w_specs["wo"],
                {"wg": w_specs["shared"]["wg"], "wu": w_specs["shared"]["wu"],
                 "wo": w_specs["shared"]["wo"]})
    out_specs = (P(batch_spec, seq_spec, None), P(), P())
    fn = jax.shard_map(local_fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, args["router"], args["wg"], args["wu"], args["wi"],
              args["wo"], args["shared"])


def _as_tuple(spec):
    if spec is None:
        return ()
    if isinstance(spec, tuple):
        return spec
    return (spec,)


# ---------------------------------------------------------------------------
# Decode (tiny token count) path
# ---------------------------------------------------------------------------

def moe_decode(cfg: ModelConfig, p, x, ctx: ParallelContext, *,
               batch_spec, capacity_factor: float = 2.0,
               dtype=jnp.bfloat16):
    """x: (B, 1, D) with tiny B·1 — replicate tokens over model axis,
    compute local experts at small capacity, psum partial outputs."""
    m = cfg.moe
    ES = ctx.model_axis_size
    if ES <= 1 or not ctx.has_mesh:
        B, T, D = x.shape
        out, aux, dropped = moe_local(
            cfg, p, x.reshape(B * T, D),
            capacity_factor=capacity_factor, dtype=dtype)
        return out.reshape(B, T, D), aux, dropped
    axis = ctx.model_axis
    E_loc = m.num_experts // ES
    fsdp_axes = ctx.fsdp_axes
    B, T, D = x.shape
    b_sh = math.prod(ctx.axis_size(a) for a in _as_tuple(batch_spec))
    S_loc = (B // b_sh) * T
    C = capacity_for(max(S_loc, 1), m, capacity_factor) * ES

    def local_fn(x_loc, router, wg, wu, wi, wo, shared):
        xs = x_loc.reshape(-1, D)
        router = _fsdp_gather(router, fsdp_axes, 0,
                              ctx.bf16_fsdp_gather)
        gates, eidx, aux = router_topk(cfg, {"router": router}, xs, dtype)
        aux = jax.lax.pmean(aux, axis)
        my_shard = jax.lax.axis_index(axis)
        # keep only (token, k) pairs routed to my local experts
        local_mask = (eidx // E_loc) == my_shard
        local_e = jnp.where(local_mask, eidx % E_loc, E_loc)
        gates_m = jnp.where(local_mask, gates, 0.0)
        flat_e = local_e.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        token_of = order // m.top_k
        counts = jnp.bincount(flat_e, length=E_loc + 1)[:E_loc]
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(flat_e.shape[0]) - starts[
            jnp.minimum(sorted_e, E_loc - 1)]
        keep = (pos < C) & (sorted_e < E_loc)
        slot = jnp.where(keep, sorted_e * C + pos, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, D), xs.dtype).at[slot].set(
            xs[token_of] * keep[:, None].astype(xs.dtype), mode="drop")[:-1]
        buf = buf.reshape(E_loc, C, D)
        wloc = {}
        for name, w in (("wg", wg), ("wu", wu), ("wi", wi)):
            if w is not None:
                wloc[name] = _fsdp_gather(w, fsdp_axes, 1,
                                          ctx.bf16_fsdp_gather)
        wloc["wo"] = _fsdp_gather(wo, fsdp_axes, 2, ctx.bf16_fsdp_gather)
        y = _expert_ffn(cfg, wloc, buf, dtype)
        y_flat = jnp.concatenate(
            [y.reshape(E_loc * C, D), jnp.zeros((1, D), y.dtype)], 0)
        gate_sorted = gates_m.reshape(-1)[order]
        contrib = (y_flat[slot] * gate_sorted[:, None].astype(y.dtype)
                   * keep[:, None].astype(y.dtype))
        out = jnp.zeros((xs.shape[0], D), y.dtype).at[token_of].add(contrib)
        out = jax.lax.psum(out, axis)
        if m.num_shared_experts:
            sh = {k2: _fsdp_gather(v, fsdp_axes, 1 if k2 == "wo" else 0,
                                   ctx.bf16_fsdp_gather)
                  for k2, v in shared.items()}
            out = out + _shared_expert(cfg, sh, xs, dtype)
        return out.reshape(x_loc.shape), aux, jnp.zeros((), jnp.float32)

    fs = tuple(fsdp_axes) if fsdp_axes else None
    in_specs = (P(batch_spec, None, None),
                P(fs, None),
                P(axis, fs, None), P(axis, fs, None), P(axis, fs, None),
                P(axis, None, fs),
                {"wg": P(fs, None), "wu": P(fs, None), "wo": P(None, fs)})
    out_specs = (P(batch_spec, None, None), P(), P())
    fn = jax.shard_map(local_fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    shared = p.get("shared", {"wg": None, "wu": None, "wo": None})
    return fn(x, p["router"], p.get("wg"), p.get("wu"), p.get("wi"),
              p["wo"], shared)
