"""Chip smoke test: drive the main path once on a TPU, at olmo-1b's published
width, through the entry points a user calls.

    python chip_smoke.py             # one chip: device, serve, train
    python chip_smoke.py --chips 4   # four chips: the sharded embedding
                                     # all-to-all and the sharded train step,
                                     # each against its one-device result

Phases, each printing one JSON line (what ran, seconds spent lowering and
compiling vs. the rest, and what was checked):

* ``device`` - JAX must report a TPU.  Anything else exits non-zero, naming
  the device it found.  There is no CPU mode.
* ``serve`` - olmo-1b as published (16 layers, d_model 2048, vocab 50304;
  random weights from ``--seed``) through ``Supercomputer().allocate`` ->
  ``Slice.serve`` -> ``ServeEngine``: 8 requests of 32 new tokens on 8 slots
  over a 2048-row KV cache.  Checks every request returns exactly its budget
  of in-vocab tokens, the compiled decode program holds the Pallas kernel
  (``tpu_custom_call``), the first tokens equal those of an engine whose
  decode attention is XLA's (``decode_attn="dense"``), and the decode
  kernels (plain, int8 KV, block table) agree with their references at these
  shapes.  Then the pooled prefix-shared cache (128-row blocks) serves three
  requests with a common 256-token header: the later two must reuse the
  header's blocks, and closing the pool must find no leaked block.
* ``train`` - ``Slice.train`` on olmo-1b at published width with the depth
  cut from 16 to 4 layers (f32 weights and Adam state for all 16 layers, plus
  activations, do not fit one v5e's 16 GB; 4 layers compile to about 9.4 GiB),
  batch 4 x 512 tokens, 4 steps.  Every loss must be finite.

The last line printed is ``{"ok": true, "device": {...}}``.  A failed check
exits non-zero before it.  The phase functions are importable, so tests run
them at a reduced size on a CPU host.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster import SliceSpec, Supercomputer  # noqa: E402
from repro.configs import (OptimizerConfig, ParallelConfig,  # noqa: E402
                           RunConfig, ShapeConfig, registry)
from repro.kernels import ops as OPS  # noqa: E402
from repro.kernels import ref as REF  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models import quant as QUANT  # noqa: E402
from repro.parallel.context import LOCAL, ParallelContext  # noqa: E402

SERVE_SPEC = SliceSpec(slots=8, max_len=2048, prompt_len=128, chunk=8)
# 4 slots for the 3 pooled requests; 96 blocks cover every slot's 16-block
# table with room for the published header
POOLED_SPEC = SliceSpec(slots=4, max_len=2048, prompt_len=384, chunk=8,
                        kv_block=128, kv_share=True, kv_blocks=96)
TRAIN_LAYERS = 4                       # of olmo-1b's 16; see the docstring
TRAIN_SHAPE = ShapeConfig("chip_smoke", "train", 512, 4)
SLICE = (4, 4, 4)                      # one block of the modelled machine
# bf16 q/k/v; the kernel rounds probabilities to bf16 before P @ V
KERNEL_TOL = 3e-2

# lowering to StableHLO and the XLA compile (or its cache read); tracing is
# left out, since a trace event nests those of the functions it calls
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def timed():
    """Yields a dict that holds, on exit, the wall seconds of the block and
    the part of them JAX reported lowering and compiling."""
    t = {"compile_s": 0.0}

    def on(event, duration, **_):
        if event in _COMPILE_EVENTS:
            t["compile_s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on)
    t0 = time.perf_counter()
    try:
        yield t
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        t["wall_s"] = time.perf_counter() - t0
        t["run_s"] = t["wall_s"] - t["compile_s"]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(chips: int = 1) -> dict:
    """The device JAX reports; refuses anything but ``chips`` TPUs."""
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"needs a TPU, but JAX found {len(devs)} {d.platform} device(s) "
          f"({d.device_kind})")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPUs, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _serve(sl, cfg, params, spec, ctx, prompts, new_tokens):
    """Submit ``prompts`` to a new session and serve them to completion."""
    session = sl.serve(cfg, params, spec, ctx=ctx)
    reqs = [session.submit(p, max_new_tokens=new_tokens) for p in prompts]
    session.run()
    return session, _outputs(cfg, reqs, new_tokens)


def _outputs(cfg, reqs, new_tokens):
    toks = [list(r.out_tokens) for r in reqs]
    for i, tk in enumerate(toks):
        check(len(tk) == new_tokens,
              f"request {i} returned {len(tk)} tokens, budget {new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in tk),
              f"request {i} returned a token outside the vocabulary")
    return toks


def _release(session) -> None:
    """Drop the session's KV cache so the next engine has the memory."""
    session.engine.cache = None
    session.close()


def decode_program_text(engine) -> str:
    """The engine's jitted decode program, compiled at its live state."""
    budgets = jnp.zeros((engine.slots,), jnp.int32)
    return engine._decode_fn.lower(
        engine.params, engine.cache, engine.last_tokens, engine.seq_lens,
        budgets, engine._sample_key, engine.sample_salt,
        engine.spec.chunk).compile().as_text()


def kernel_check(cfg, spec: SliceSpec, ctx: ParallelContext, seed: int = 0
                 ) -> dict:
    """Max |kernel - reference| of the three decode kernels at the serving
    shapes (slots, max_len, the model's heads), on random bf16 data."""
    a = cfg.attention
    B, S, H, KH, d = spec.slots, spec.max_len, a.num_heads, a.num_kv_heads, \
        a.head_dim
    bk = ctx.decode_kv_block
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KH, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KH, d), jnp.bfloat16)
    lens = jax.random.randint(ks[3], (B,), 1, S + 1, jnp.int32)

    def err(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))

    out = {"plain": err(
        OPS.paged_decode_attention(q, k, v, lens, bk=bk, impl="pallas"),
        REF.paged_decode_attention_ref(q, k, v, lens))}
    kq, kscale = QUANT.quantize_kv(k)
    vq, vscale = QUANT.quantize_kv(v)
    out["int8"] = err(
        OPS.paged_decode_attention(q, kq, vq, lens, k_scale=kscale,
                                   v_scale=vscale, bk=bk, impl="pallas"),
        REF.paged_decode_attention_ref(
            q, QUANT.dequantize_kv(kq, kscale),
            QUANT.dequantize_kv(vq, vscale), lens))
    # block table: the same rows as a pool of bk-row blocks, slots' blocks
    # in reverse pool order
    nb = S // bk
    pk, pv = (x.reshape(B * nb, bk, KH, d) for x in (k, v))
    tables = (B * nb - 1 - jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    pk, pv = pk[::-1], pv[::-1]
    out["block_table"] = err(
        OPS.paged_decode_attention_bt(q, pk, pv, lens, tables, impl="pallas"),
        REF.paged_decode_attention_bt_ref(q, pk, pv, lens, tables))
    for name, e in out.items():
        check(e <= KERNEL_TOL, f"{name} decode kernel differs from its "
                               f"reference by {e} (limit {KERNEL_TOL})")
    return out


def serve_phase(cfg, params, *, spec: SliceSpec = SERVE_SPEC,
                pooled: SliceSpec = POOLED_SPEC,
                ctx: ParallelContext = LOCAL, requests: int = 8,
                new_tokens: int = 32, seed: int = 0) -> dict:
    """Serve through the cluster API with the decode-attention selection of
    ``ctx`` and again with XLA attention; then serve a shared-prefix trio
    from the pooled cache.  Returns what was measured and checked."""
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    prompts = [rng.integers(0, V, size=spec.prompt_len)
               for _ in range(requests)]
    res = {"model": cfg.name, "layers": cfg.num_layers,
           "d_model": cfg.d_model, "vocab": V,
           "spec": dataclasses.asdict(spec)}
    with Supercomputer().allocate(SLICE) as sl:
        with timed() as t:
            session, toks = _serve(sl, cfg, params, spec, ctx, prompts,
                                   new_tokens)
        res["serve_s"] = t
        res["decode_has_kernel"] = ("tpu_custom_call"
                                    in decode_program_text(session.engine))
        _release(session)

        xla_ctx = dataclasses.replace(ctx, decode_attn="dense")
        with timed() as t:
            session, ref_toks = _serve(sl, cfg, params, spec, xla_ctx,
                                       prompts, new_tokens)
        res["xla_attention_serve_s"] = t
        _release(session)
        check([tk[0] for tk in toks] == [tk[0] for tk in ref_toks],
              "first tokens differ from the XLA-attention engine's")
        # the first token comes from prefill; the second is the first one
        # whose attention ran through the decode kernel
        res["first_decoded_token_matches"] = sum(
            a[1] == b[1] for a, b in zip(toks, ref_toks))
        res["identical_streams"] = sum(a == b for a, b in zip(toks, ref_toks))
        res["requests"] = requests
        res["new_tokens"] = new_tokens

        with timed() as t:
            res["kernel_max_abs_err"] = kernel_check(cfg, spec, ctx, seed)
        res["kernel_check_s"] = t

        # pooled prefix-shared cache: a header of whole blocks, then a
        # half-block tail per request; the first request publishes the
        # header, the next two must map it instead of prefilling it
        bs = pooled.kv_block
        header = rng.integers(0, V, size=(pooled.prompt_len // bs - 1) * bs)
        trio = [np.concatenate([header, rng.integers(0, V, size=bs // 2)])
                for _ in range(3)]
        with timed() as t:
            session = sl.serve(cfg, params, pooled, ctx=ctx)
            reqs = [session.submit(trio[0], max_new_tokens=new_tokens)]
            session.run()
            reqs += [session.submit(p, max_new_tokens=new_tokens)
                     for p in trio[1:]]
            session.run()
        _outputs(cfg, reqs, new_tokens)
        engine = session.engine
        shared = engine.kv_stats()["kv_shared_tokens"]
        check(shared == 2 * len(header),
              f"pooled cache shared {shared} prompt tokens, expected "
              f"{2 * len(header)}")
        engine.kv_close()
        leaked = engine.kvpool.stats()["allocated_blocks"]
        check(leaked == 0, f"pooled cache leaked {leaked} blocks")
        _release(session)
        res["pooled"] = {"spec": dataclasses.asdict(pooled),
                         "shared_prompt_tokens": shared,
                         "leaked_blocks": leaked, "serve_s": t}
    return res


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, *, layers: int = TRAIN_LAYERS,
                shape: ShapeConfig = TRAIN_SHAPE, steps: int = 4,
                seed: int = 0) -> dict:
    """``steps`` steps of ``Slice.train`` on ``cfg`` cut to ``layers``."""
    run = RunConfig(model=cfg.replace(num_layers=layers), shape=shape,
                    parallel=ParallelConfig(remat="block"),
                    optimizer=OptimizerConfig(), seed=seed)
    with Supercomputer().allocate(SLICE) as sl:
        with timed() as t:
            session = sl.train(run, steps, log_every=1)
        losses = [m["loss"] for m in session.metrics_log if "loss" in m]
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite training loss: {losses}")
    return {"model": cfg.name, "layers": layers,
            "published_layers": cfg.num_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab_size, "batch": shape.global_batch,
            "seq_len": shape.seq_len, "steps": steps, "losses": losses,
            "train_s": t}


# ---------------------------------------------------------------------------
# four chips: sharded embedding all-to-all and sharded train step
# ---------------------------------------------------------------------------

def _spans(x, n: int) -> bool:
    """Every leaf of ``x`` lives on ``n`` devices."""
    return all(len(leaf.devices()) == n for leaf in jax.tree.leaves(x))


def _is_sharded(x) -> bool:
    """Some leaf of ``x`` holds only a shard of its rows per device."""
    return any(leaf.addressable_shards[0].data.shape != leaf.shape
               for leaf in jax.tree.leaves(x))


def _worst(a, b, rtol, atol) -> float:
    """Largest ``|a - b| / (atol + rtol |b|)`` over the leaves: at most 1
    exactly where ``np.allclose(a, b, rtol, atol)`` holds for every leaf."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        worst = max(worst, float(np.max(np.abs(x - y)
                                        / (atol + rtol * np.abs(y)))))
    return worst


def embedding_tables(num: int = 8, max_rows: int = 500_000):
    """dlrm0's ``num`` largest tables at their published widths and
    valencies, rows capped at ``max_rows`` to fit the one-device reference
    beside the sharded copy."""
    return [dataclasses.replace(t, vocab_size=min(t.vocab_size, max_rows))
            for t in registry.get_config("dlrm0").dlrm.tables[:num]]


def embedding_features(tables, batch: int, seed: int):
    """(batch, max_valency) ids per table; each row's valency is drawn
    around the table's average, the rest padded with -1."""
    rng = np.random.default_rng(seed)
    out = {}
    for t in tables:
        ids = rng.integers(0, t.vocab_size, size=(batch, t.max_valency))
        n = np.clip(rng.poisson(t.avg_valency, size=batch), 1, t.max_valency)
        ids[np.arange(t.max_valency)[None, :] >= n[:, None]] = -1
        out[t.name] = jnp.asarray(ids, jnp.int32)
    return out


def sharded_embedding_phase(tables, *, batch: int = 1024, seed: int = 0,
                            chips: int = 4) -> dict:
    """``EmbeddingCollection`` lookup and its gradient with the tables
    sharded over a ``chips``-way model axis (the all-to-all path), against
    the same lookup and gradient on one device."""
    from repro.embeddings.engine import (EmbeddingCollection,
                                         lookup_reference,
                                         materialize_tables)
    from repro.launch.mesh import make_mesh
    P = jax.sharding.PartitionSpec
    mesh = make_mesh((1, chips), ("data", "model"))
    check(mesh.devices.size == chips, f"mesh holds {mesh.devices.size} "
                                      f"devices, not {chips}")
    ctx = ParallelContext(mesh=mesh, data_axis="data", model_axis="model")
    coll = EmbeddingCollection(tables, num_shards=chips)
    params = coll.init(jax.random.PRNGKey(seed))
    feats = embedding_features(tables, batch, seed)

    def sq(outs):
        return sum(jnp.sum(o ** 2) for o in outs.values())

    with timed() as t_local:
        want = jax.jit(lambda p, f: lookup_reference(
            materialize_tables(coll, p), tables, f))(params, feats)
        g_want = jax.jit(jax.grad(lambda p, f: sq(coll.lookup(p, f))))(
            params, feats)
    shard = {k: jax.sharding.NamedSharding(
        mesh, P("model", None) if k in {g.name for g in coll.groups.values()}
        else P()) for k in params}
    ps = {k: jax.device_put(v, shard[k]) for k, v in params.items()}
    check(_spans(ps, chips) and _is_sharded(ps),
          f"embedding tables are not sharded over {chips} devices")
    with jax.set_mesh(mesh), timed() as t_sharded:
        got = jax.jit(lambda p, f: coll.lookup(p, f, ctx, method="a2a"))(
            ps, feats)
        g_got = jax.jit(jax.grad(lambda p, f: sq(
            coll.lookup(p, f, ctx, method="a2a"))))(ps, feats)
    check(_spans(g_got, chips), "embedding gradients do not span the mesh")
    # in units of the tolerance: at most 1 passes
    worst = {"lookup": _worst(got, want, 1e-5, 1e-6),
             "grad": _worst(g_got, g_want, 1e-4, 1e-6)}
    for k, w in worst.items():
        check(w <= 1, f"sharded embedding {k} differs from the one-device "
                      f"{k} by {w:.3g} times the tolerance")
    return {"tables": len(tables),
            "rows": sum(t.vocab_size for t in tables),
            "dims": sorted({t.dim for t in tables}),
            "placement": {k: v.strategy for k, v in coll.plan.items()},
            "batch": batch, "mesh": dict(mesh.shape),
            "diff_over_tolerance": worst,
            "local_s": t_local, "sharded_s": t_sharded}


def sharded_train_phase(cfg, *, layers: int = TRAIN_LAYERS,
                        shape: ShapeConfig = TRAIN_SHAPE, seed: int = 0,
                        mesh_shape=(2, 2)) -> dict:
    """One ``make_train_step`` step sharded over a (data, model) mesh of
    real devices, against the same step on one device."""
    from repro.launch import steps as STEPS
    from repro.launch.mesh import make_mesh
    from repro.optim import adam as OPT
    from repro.parallel import sharding as SH
    cfg = cfg.replace(num_layers=layers)
    pcfg, ocfg = ParallelConfig(remat="block"), OptimizerConfig()
    n = int(np.prod(mesh_shape))
    mesh = make_mesh(mesh_shape, ("data", "model"))
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: api.init_params(cfg, k))(key)
    opt = jax.jit(lambda p: OPT.init(ocfg, p))(params)
    batch = api.make_batch(cfg, shape, key)

    with timed() as t_local:
        step_l = STEPS.make_train_step(cfg, shape, pcfg, ocfg, LOCAL,
                                       accum_steps=2)
        m_l = jax.jit(step_l)(params, opt, batch)[2]
        m_l = {k: float(v) for k, v in m_l.items()}
    sctx = SH.make_context(mesh, pcfg)
    with jax.set_mesh(mesh), timed() as t_sharded:
        _, in_sh, out_sh, step_s = STEPS.shapes_and_shardings(
            cfg, shape, pcfg, ocfg, sctx, accum_steps=2)

        def named(tree):
            return jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s)
                if s is not None else None, tree,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
                or x is None)

        ps = jax.device_put(params, named(in_sh[0]))
        os_ = jax.device_put(opt, named(in_sh[1]))
        bs = jax.device_put(batch, named(in_sh[2]))
        del params, opt
        new_p, _, m_s = jax.jit(step_s, in_shardings=named(in_sh),
                                out_shardings=named(out_sh))(ps, os_, bs)
        m_s = {k: float(v) for k, v in m_s.items()}
    check(_spans(new_p, n) and _is_sharded(new_p),
          f"updated parameters are not sharded over {n} devices")
    for k, rtol in (("loss", 2e-2), ("grad_norm", 5e-2)):
        check(math.isclose(m_l[k], m_s[k], rel_tol=rtol),
              f"sharded step {k} {m_s[k]} differs from the one-device "
              f"step's {m_l[k]}")
    return {"model": cfg.name, "layers": layers, "d_model": cfg.d_model,
            "batch": shape.global_batch, "seq_len": shape.seq_len,
            "mesh": dict(mesh.shape), "local": m_l, "sharded": m_s,
            "local_s": t_local, "sharded_s": t_sharded}


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phases, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    device = device_phase(args.chips)
    report("device", compile_cache=cache, **device)
    olmo = registry.get_config("olmo-1b")
    if args.chips == 4:
        report("sharded_embedding", **sharded_embedding_phase(
            embedding_tables(), seed=args.seed, chips=4))
        report("sharded_train", **sharded_train_phase(olmo, seed=args.seed))
    else:
        with timed() as t:
            params = jax.jit(lambda k: api.init_params(olmo, k))(
                jax.random.PRNGKey(args.seed))
        serve = serve_phase(olmo, params, seed=args.seed)
        serve["init_params_s"] = t
        check(serve["decode_has_kernel"],
              "the decode program holds no Pallas kernel (tpu_custom_call)")
        report("serve", **serve)
        del params
        report("train", **train_phase(olmo, seed=args.seed))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: check failed: {e}")
