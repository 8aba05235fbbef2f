"""SparseCore embedding pipeline v2 benchmark -> BENCH_sparsecore.json.

Measures the pipelined multi-group executor against the legacy dataflow:

  * ``lookup``  — wall-clock µs of the fused descriptor-stream lookup (ONE
    launch covering every table) vs the per-group baseline (one dispatch per
    table, the pre-v2 "one Pallas call per width-group" model).  The paper's
    CISC-issue-per-table-batch overhead (§3.5) is exactly what fusion
    amortises; the acceptance gate is fused >= 1.3x.
  * ``train``   — end-to-end DLRM train-step steps/s with the pipelined
    executor on vs off (same model, same data).
  * ``cache``   — distributed (8 fake devices) a2a lookup µs with and
    without the hot-id LFU cache; cache hits skip the id/vector all-to-all
    and the exchange buffers shrink by the cache's ``capacity_scale``.
    Runs in a subprocess so the main process keeps its single-device view.

Standalone:  PYTHONPATH=src python benchmarks/sparsecore_pipeline.py
Harness:     benchmarks/run.py imports ``run()``.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_sparsecore.json"

N_TABLES = 24
BATCH = 128


def _demo_collection():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import EmbeddingTableConfig
    from repro.embeddings.engine import EmbeddingCollection

    dims = [16, 8, 32]
    specs = [EmbeddingTableConfig(
        name=f"t{i:02d}", vocab_size=4000 * (1 + i % 3), dim=dims[i % 3],
        avg_valency=[1.0, 4.0, 8.0][i % 3],
        max_valency=[1, 8, 16][i % 3],
        combiner="sum" if i % 2 == 0 else "mean")
        for i in range(N_TABLES)]
    # v2 layout for the fused path; a legacy per-table collection for the
    # baseline (same RNG draws, so per-table values are identical)
    coll = EmbeddingCollection(specs, num_shards=1, fused_storage=True)
    params = coll.init(jax.random.PRNGKey(0))
    legacy = EmbeddingCollection(specs, num_shards=1)
    params_legacy = legacy.init(jax.random.PRNGKey(0))
    feats = {}
    for i, t in enumerate(specs):
        key = jax.random.PRNGKey(100 + i)
        u = jax.random.uniform(key, (BATCH, t.max_valency),
                               minval=1e-6, maxval=1.0)
        ids = jnp.minimum((u ** 2.0) * t.vocab_size,
                          t.vocab_size - 1).astype(jnp.int32)
        drop = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.25,
                                    (BATCH, t.max_valency))
        feats[t.name] = jnp.where(drop, -1, ids)
    return specs, coll, params, feats, params_legacy


def _time_pair(fa, fb, reps=10, rounds=6):
    """Interleaved best-of-rounds for a fair A/B on a jittery box: each
    round times A then B back to back, so scheduler noise hits both."""
    import jax
    jax.block_until_ready(fa())        # compile
    jax.block_until_ready(fb())
    best_a = best_b = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fa())
        best_a = min(best_a, (time.perf_counter() - t0) / reps * 1e6)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fb())
        best_b = min(best_b, (time.perf_counter() - t0) / reps * 1e6)
    return best_a, best_b


def bench_lookup():
    """Fused one-launch lookup vs one dispatch per table."""
    import jax
    import numpy as np
    from repro.embeddings.engine import _combine, _gather_rows

    specs, coll, params, feats, params_legacy = _demo_collection()
    fused = jax.jit(lambda p, f: coll.lookup(p, f, method="local",
                                             fused=True))
    per_table = {
        t.name: jax.jit(lambda tbl, ids, c=t.combiner:
                        _combine(_gather_rows(tbl, ids), ids, c))
        for t in specs}

    def run_pergroup():
        return {n: fn(params_legacy[n], feats[n])
                for n, fn in per_table.items()}

    a, b = fused(params, feats), run_pergroup()
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-5, atol=1e-6)
    fused_us, pergroup_us = _time_pair(lambda: fused(params, feats),
                                       run_pergroup, rounds=10)
    speedup = pergroup_us / fused_us
    return {"fused_us": round(fused_us, 1),
            "pergroup_us": round(pergroup_us, 1),
            "tables": N_TABLES, "batch": BATCH,
            "speedup": round(speedup, 2), "ok": bool(speedup >= 1.3)}


def bench_train(steps=25):
    """DLRM train steps/s: pipelined executor on vs off."""
    import jax
    from repro.configs import (OptimizerConfig, ParallelConfig, RunConfig,
                               ShapeConfig)
    from repro.launch.mesh import make_local_mesh
    from repro.train.trainer import Trainer
    sys.path.insert(0, str(ROOT / "examples"))
    from train_dlrm import demo_config

    cfg = demo_config()
    mesh = make_local_mesh()
    out = {}
    for label, pipeline in (("pipelined", True), ("pergroup", False)):
        run_cfg = RunConfig(
            model=cfg, shape=ShapeConfig("d", "train", 1, BATCH),
            parallel=ParallelConfig(remat="none", emb_pipeline=pipeline),
            optimizer=OptimizerConfig(lr=3e-4))
        trainer = Trainer(run_cfg, mesh)
        state = trainer.train(5)          # warm up + compile
        t0 = time.perf_counter()
        trainer.train(5 + steps, state=state)
        out[f"{label}_steps_per_s"] = round(
            steps / (time.perf_counter() - t0), 2)
    return out


def bench_cached():
    """Distributed a2a lookup, hot-id cache on vs off (8 fake devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.embeddings.sharding as ESH
    from repro.configs.base import EmbeddingTableConfig
    from repro.embeddings.cache import HotIdCache
    from repro.embeddings.engine import EmbeddingCollection
    from repro.launch.mesh import make_mesh
    from repro.parallel.context import ParallelContext

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, data_axis="data", model_axis="model")
    specs = [EmbeddingTableConfig("a", 65536, 64, 16.0, 16, "sum"),
             EmbeddingTableConfig("b", 32768, 64, 8.0, 8, "mean")]
    ESH.REPLICATE_BYTES = 0
    ESH.TABLE_SHARD_BYTES = 0
    coll = EmbeddingCollection(specs, num_shards=4)
    params = coll.init(jax.random.PRNGKey(0))
    feats = {}
    for i, t in enumerate(specs):
        key = jax.random.PRNGKey(i)
        u = jax.random.uniform(key, (512, t.max_valency),
                               minval=1e-6, maxval=1.0)
        feats[t.name] = jnp.minimum(             # heavy zipf skew: hot head
            (u ** 6.0) * t.vocab_size, t.vocab_size - 1).astype(jnp.int32)

    cache = HotIdCache(capacity=2048, capacity_scale=0.5)
    for dim, g in sorted(coll.groups.items()):
        for s in g.slots:
            cache.observe(g.name,
                          np.asarray(feats[s.spec.name]) + s.offset)
    cache.refresh_all(coll, params)
    for dim, g in sorted(coll.groups.items()):       # measure the hit rate
        for s in g.slots:
            cache.observe(g.name,
                          np.asarray(feats[s.spec.name]) + s.offset)

    with jax.set_mesh(mesh):
        un = jax.jit(lambda p, f: coll.lookup(p, f, ctx, method="a2a"))
        ca = jax.jit(lambda p, f, c: coll.lookup(p, f, ctx, method="a2a",
                                                 cache=c))
        arrays = cache.arrays()
        # fresh cache: cached must be bitwise-identical to uncached (misses
        # must fit the scaled exchange buffers, hits are exact row copies)
        a, b = un(params, feats), ca(params, feats, arrays)
        exact = all(bool((a[k] == b[k]).all()) for k in a)
        uncached_us, cached_us = _time_pair(
            lambda: un(params, feats),
            lambda: ca(params, feats, arrays), reps=4, rounds=16)
    return {"uncached_us": round(uncached_us, 1),
            "cached_us": round(cached_us, 1),
            "hit_rate": round(cache.hit_rate, 3),
            "capacity_scale": cache.capacity_scale,
            "exact": exact,
            "speedup": round(uncached_us / cached_us, 2)}


def _cached_subprocess():
    """Run bench_cached in its own process with 8 fake devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = (str(ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).resolve()),
         "--cached-json"],
        capture_output=True, text=True, env=env, timeout=900)
    if proc.returncode != 0:
        return {"error": proc.stderr[-500:]}
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_model(hit_rate: float):
    """Analytic SC step time (dlrm0 on a 4x4x8 v4 slice): what the fused
    issue stream and the measured cache hit rate buy on real ICI, where the
    exchange is bandwidth-bound (unlike this container's memcpy a2a)."""
    from repro.configs import get_config
    from repro.core.costmodel import TPU_V4
    from repro.core.sparsecore import sc_step_time
    from repro.core.topology import SliceTopology

    dlrm = get_config("dlrm0").dlrm
    topo = SliceTopology((4, 4, 8))
    base = sc_step_time(dlrm, 4096, topo, TPU_V4)["total"]
    fused = sc_step_time(dlrm, 4096, topo, TPU_V4,
                         fused_issue=True)["total"]
    cached = sc_step_time(dlrm, 4096, topo, TPU_V4, fused_issue=True,
                          cache_hit_rate=hit_rate)["total"]
    return {"base_us": round(base * 1e6, 1),
            "fused_issue_us": round(fused * 1e6, 1),
            "fused_cached_us": round(cached * 1e6, 1),
            "hit_rate_used": hit_rate,
            "fused_gain": round(base / fused, 3),
            "cached_gain": round(base / cached, 3)}


def collect(include_cached: bool = True):
    results = {"lookup": bench_lookup(), "train": bench_train()}
    if include_cached:
        results["cache"] = _cached_subprocess()
    hit = results.get("cache", {}).get("hit_rate")
    results["model"] = bench_model(hit if hit is not None else 0.3)
    results["model"]["hit_rate_source"] = (
        "measured" if hit is not None else "assumed")
    OUT.write_text(json.dumps(results, indent=2) + "\n")
    return results


def run():
    """benchmarks/run.py entry: rows of (name, us, derived)."""
    res = collect(include_cached=True)
    lk, tr = res["lookup"], res["train"]
    rows = [
        ("sparsecore_fused_lookup", lk["fused_us"],
         f"vs_pergroup={lk['pergroup_us']:.0f}us;"
         f"speedup={lk['speedup']:.2f}x;paper>=1.3x;ok={lk['ok']}"),
        ("sparsecore_train_pipelined", 0.0,
         f"steps/s={tr['pipelined_steps_per_s']};"
         f"pergroup={tr['pergroup_steps_per_s']}"),
    ]
    ca = res.get("cache", {})
    if "cached_us" in ca:
        rows.append(("sparsecore_cached_a2a", ca["cached_us"],
                     f"uncached={ca['uncached_us']:.0f}us;"
                     f"hit_rate={ca['hit_rate']};exact={ca['exact']};"
                     f"speedup={ca['speedup']:.2f}x"))
    elif "error" in ca:
        rows.append(("sparsecore_cached_a2a", 0.0,
                     f"ERROR:{ca['error'][-120:]}"))
    mo = res["model"]
    rows.append(("sparsecore_model_v4", mo["fused_cached_us"],
                 f"base={mo['base_us']:.0f}us;"
                 f"fused_issue_gain={mo['fused_gain']}x;"
                 f"cached_gain={mo['cached_gain']}x;"
                 f"hit_rate={mo['hit_rate_source']}"))
    return rows


if __name__ == "__main__":
    if "--cached-json" in sys.argv:
        # subprocess mode: 8 fake devices were set by the parent env
        sys.path.insert(0, str(ROOT / "src"))
        print(json.dumps(bench_cached()))
    else:
        sys.path.insert(0, str(ROOT / "src"))
        sys.path.insert(0, str(ROOT))
        for name, us, derived in run():
            print(f"{name},{us:.1f},{derived}")
        print(f"wrote {OUT}")
        # the acceptance gate is real: ci.sh (set -e) fails when the fused
        # multi-group lookup loses its >= 1.3x margin over per-group
        gate = json.loads(OUT.read_text())["lookup"]
        if not gate["ok"]:
            print(f"GATE FAILED: fused speedup {gate['speedup']}x < 1.3x")
            sys.exit(1)
