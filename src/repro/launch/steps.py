"""Train / serve step builders: the jit'd programs the dry-run lowers and the
trainer/server execute.

``make_train_step``: microbatched (gradient-accumulation) train step with
remat, optimizer update, and MoE aux losses.  Microbatching is what keeps the
(tokens × vocab) logits tensor bounded at 32k-seq × 256k-vocab scale.
``make_prefill_step`` / ``make_decode_step``: the serving programs.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelConfig,
                                ShapeConfig)
from repro.models import api
from repro.optim import adam as OPT
from repro.parallel import sharding as SH
from repro.parallel.context import LOCAL, ParallelContext, activate

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def _xent(logits, labels):
    """Token cross-entropy; logits fp32 (B, T, V), labels (B, T)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - ll).mean()


def _xent_chunked(cfg, params, x, labels, chunk: int):
    """Sequence-chunked cross-entropy: the (B, T, V) logits tensor never
    materialises — logits exist only per (B, chunk, V/tp) slice (§Perf).
    x: final hidden states (B, T, D); labels (B, T)."""
    from repro.models.transformer import unembed
    B, T, D = x.shape
    chunk = min(chunk, T)
    if T % chunk:
        chunk = T  # fall back (shapes in this repo are powers of two)
    nc = T // chunk
    xc = x.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        xb, lb = xs
        logits = unembed(cfg, params, xb)           # (B, chunk, V) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return acc + (lse - ll).sum(), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return tot / (B * T)


def loss_fn(cfg: ModelConfig, params, batch, ctx: ParallelContext,
            *, remat: str = "none", xent_chunk: int = 0,
            attn_impl: str = "blocked"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    with activate(ctx):
        return _loss_fn(cfg, params, batch, ctx, remat=remat,
                        xent_chunk=xent_chunk, attn_impl=attn_impl)


def _loss_fn(cfg: ModelConfig, params, batch, ctx: ParallelContext,
             *, remat: str = "none", xent_chunk: int = 0,
             attn_impl: str = "blocked"):
    if cfg.family == "dlrm":
        from repro.models import dlrm as DL
        loss, aux = DL.loss_fn(cfg, params, batch, ctx)
        return loss, {"loss": loss}
    labels = batch["labels"]
    fwd_batch = {k: v for k, v in batch.items() if k != "labels"}
    T = labels.shape[1]
    kw = {} if cfg.family == "audio" else {"attn_impl": attn_impl}
    if xent_chunk and cfg.family != "audio":
        x, aux = api.forward(cfg, params, fwd_batch, ctx,
                             remat=(remat != "none"), return_hidden=True,
                             **kw)
        ce = _xent_chunked(cfg, params, x[:, -T:, :], labels, xent_chunk)
    else:
        logits, aux = api.forward(cfg, params, fwd_batch, ctx,
                                  remat=(remat != "none"), **kw)
        logits = logits[:, -T:, :]        # vlm: skip the patch prefix
        ce = _xent(logits, labels)
    loss = ce + 0.01 * aux
    return loss, {"loss": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def pick_accum_steps(cfg: ModelConfig, shape: ShapeConfig,
                     ctx: ParallelContext, *,
                     logits_budget: int = 256 << 20,
                     xent_chunk: int = 0) -> int:
    """Accumulation steps so per-device microbatch logits stay bounded.

    With chunked cross-entropy the logits tensor is (B, chunk, V) instead of
    (B, T, V), so far fewer accumulation steps are needed — which divides the
    per-microbatch FSDP weight-gather traffic (§Perf)."""
    if cfg.family == "dlrm":
        return 1
    ndev = 1
    if ctx.mesh is not None:
        for s in ctx.mesh.devices.shape:
            ndev *= s
    eff_seq = min(xent_chunk, shape.seq_len) if xent_chunk else shape.seq_len
    bytes_per_sample = eff_seq * cfg.vocab_size * 4
    total = shape.global_batch * bytes_per_sample
    accum = 1
    while (total / (accum * ndev)) > logits_budget \
            and accum < shape.global_batch:
        accum *= 2
    while shape.global_batch % accum:
        accum //= 2
    return max(accum, 1)


def _pmean(x, axes):
    for a in axes:
        x = jax.lax.pmean(x, a)
    return x


def make_train_step(cfg: ModelConfig, shape: ShapeConfig,
                    pcfg: ParallelConfig, ocfg: OptimizerConfig,
                    ctx: ParallelContext, *,
                    accum_steps: Optional[int] = None) -> Callable:
    """The ONE train-step builder — `Trainer` and the dry-run both route
    through here (via `shapes_and_shardings`), so every knob on
    `ParallelConfig` — `grad_compression` included — behaves identically
    from every entry point.

    `grad_compression != "none"` on a multi-shard data-parallel mesh wraps
    the whole grad computation in a shard_map over the batch axes: each
    shard computes grads on its local batch and the exchange itself runs
    compressed (`parallel/compression.compressed_allreduce` — shared-scale
    int8 payload psum / exact-k sparse exchange).  Without a mesh (or with
    model parallelism in play, where XLA owns the fused reduction) the same
    schemes apply as a post-reduction numerics roundtrip.  Either way the
    metrics carry per-device wire-bytes accounting for one exchange.
    """
    accum = accum_steps or pick_accum_steps(cfg, shape, ctx,
                                            xent_chunk=pcfg.xent_chunk)
    scheme = pcfg.grad_compression
    from repro.parallel import compression as COMP

    def grads_of(params, batch, gctx):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, gctx, remat=pcfg.remat,
                              xent_chunk=pcfg.xent_chunk,
                              attn_impl=pcfg.attn_impl),
            has_aux=True)(params)

    def accumulated(params, batch, gctx):
        """(grads, metrics) with gradient-accumulation microstepping."""
        if accum == 1:
            (loss, metrics), grads = grads_of(params, batch, gctx)
            return grads, dict(metrics)

        mb = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, xs):
            g_acc, loss_acc = acc
            (loss, _), g = grads_of(params, xs, gctx)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / accum, g_acc, g)
            return (g_acc, loss_acc + loss / accum), None

        (grads, loss), _ = jax.lax.scan(
            body, (g0, jnp.zeros((), jnp.float32)), mb)
        return grads, {"loss": loss}

    # compressed DP exchange: data-parallel shards only (with model
    # parallelism XLA owns the fused backward reduction, so compression
    # falls back to the post-reduction roundtrip)
    ndp = 1
    for a in ctx.batch_axes:
        ndp *= ctx.axis_size(a)
    dp_exchange = (scheme != "none" and ndp > 1
                   and ctx.model_axis_size == 1
                   and shape.global_batch % (ndp * accum) == 0)

    def dp_step(params, batch):
        axes = tuple(ctx.batch_axes)

        def body(p, b):
            g, metrics = accumulated(p, b, LOCAL)
            g = COMP.compressed_allreduce(g, scheme, axes)
            metrics = {k: _pmean(v, axes) for k, v in metrics.items()}
            return g, metrics

        return jax.shard_map(body, mesh=ctx.mesh,
                             in_specs=(P(), P(axes)),
                             out_specs=(P(), P()),
                             check_vma=False)(params, batch)

    def train_step(params, opt_state, batch):
        if dp_exchange:
            grads, metrics = dp_step(params, batch)
        else:
            grads, metrics = accumulated(params, batch, ctx)
            if scheme != "none":
                grads = COMP.compress_grads(grads, scheme)
        wb = COMP.wire_bytes(grads, scheme)
        metrics = dict(metrics,
                       wire_bytes=jnp.float32(wb["wire_bytes"]),
                       wire_bytes_full=jnp.float32(wb["wire_bytes_full"]),
                       wire_overhead_bytes=jnp.float32(
                           wb["wire_overhead_bytes"]))
        params, opt_state, om = OPT.apply(ocfg, params, grads, opt_state)
        metrics = dict(metrics, **om)
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                      ctx: ParallelContext,
                      pcfg: Optional[ParallelConfig] = None) -> Callable:
    kw = ({} if (pcfg is None or cfg.family == "audio")
          else {"attn_impl": pcfg.attn_impl})

    def prefill_step(params, batch):
        with activate(ctx):
            return api.prefill(cfg, params, batch, ctx,
                               max_len=shape.seq_len, **kw)
    return prefill_step


def make_decode_step(cfg: ModelConfig, shape: ShapeConfig,
                     ctx: ParallelContext) -> Callable:
    def decode_step(params, cache, tokens):
        with activate(ctx):
            return api.decode_step(cfg, params, cache, tokens, ctx)
    return decode_step


# ---------------------------------------------------------------------------
# Spec assembly for jit/lower
# ---------------------------------------------------------------------------

def shapes_and_shardings(cfg: ModelConfig, shape: ShapeConfig,
                         pcfg: ParallelConfig, ocfg: OptimizerConfig,
                         ctx: ParallelContext, *,
                         accum_steps: Optional[int] = None):
    """(abstract args, in_shardings, out_shardings, step_fn) for one cell."""
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda: api.init_params(cfg, key, ctx))
    pspecs = SH.param_specs(cfg, params_shape, ctx)
    batch_shape = api.batch_specs(cfg, shape)
    bspecs = SH.batch_specs_sharding(cfg, shape, batch_shape, ctx)

    if shape.kind == "train":
        opt_shape = jax.eval_shape(
            lambda: OPT.init(ocfg, _concretize(params_shape)))
        ospecs = _opt_specs(opt_shape, pspecs)
        step = make_train_step(cfg, shape, pcfg, ocfg, ctx,
                               accum_steps=accum_steps)
        args = (params_shape, opt_shape, batch_shape)
        in_sh = (pspecs, ospecs, bspecs)
        out_sh = (pspecs, ospecs, None)
        return args, in_sh, out_sh, step
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, shape, ctx, pcfg)
        args = (params_shape, batch_shape)
        cache_shape = api.cache_specs(cfg, shape)
        cspecs = SH.cache_specs_sharding(
            cfg, shape, cache_shape, ctx,
            seq_shard=pcfg.sequence_parallel)
        in_sh = (pspecs, bspecs)
        out_sh = (None, cspecs)
        return args, in_sh, out_sh, step
    # decode
    step = make_decode_step(cfg, shape, ctx)
    batch_shape = api.batch_specs(cfg, shape)
    cache_shape = api.cache_specs(cfg, shape)
    cspecs = SH.cache_specs_sharding(cfg, shape, cache_shape, ctx)
    tokens_shape = batch_shape["tokens"]
    bsz = 1
    for a in (ctx.batch_axes or ()):
        bsz *= ctx.axis_size(a)
    ok = ctx.has_mesh and bsz > 1 and tokens_shape.shape[0] % bsz == 0
    tspec = P(tuple(ctx.batch_axes)) if ok else P(None)
    args = (params_shape, cache_shape, tokens_shape)
    in_sh = (pspecs, cspecs, tspec)
    out_sh = (None, cspecs)
    return args, in_sh, out_sh, step


def _concretize(shape_tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), shape_tree)


def _opt_specs(opt_shape, pspecs):
    """Optimizer state inherits parameter specs (ZeRO via FSDP storage)."""
    def assign(path, leaf):
        # walk the matching param spec by stripping mu/nu prefixes
        return _lookup_like(path, leaf, pspecs)
    return jax.tree_util.tree_map_with_path(assign, opt_shape)


def _lookup_like(path, leaf, pspecs):
    # OptState(step, mu, nu): mu/nu mirror params; adafactor nests dicts
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(k.idx)
    if not parts:
        return P()
    head = parts[0]
    if head == "step":
        return P()
    node = pspecs
    for k in parts[1:]:
        if isinstance(node, dict) and k in node:
            node = node[k]
        elif isinstance(node, (list, tuple)) and isinstance(k, int) \
                and k < len(node):
            node = node[k]
        elif isinstance(k, str) and k in ("vr", "vc", "v"):
            # adafactor factored dims: reduce the param spec
            if isinstance(node, P):
                if k == "vr":
                    return P(*node[:-1])
                if k == "vc":
                    return P(*(list(node[:-2]) + [node[-1]])) \
                        if len(node) >= 2 else P()
                return node
            return P()
        else:
            return P()
    return node if isinstance(node, P) else P()
