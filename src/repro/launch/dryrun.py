import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init).  For every cell this driver:

  1. builds the abstract args (ShapeDtypeStructs — no allocation),
  2. jit-lowers the step function with in/out shardings on the production
     mesh ((16,16) "data","model" single-pod; (2,16,16) "pod","data","model"
     multi-pod),
  3. ``.compile()``s it,
  4. records memory_analysis / cost_analysis / per-collective HLO bytes and
     the three roofline terms into a JSON cache (results/dryrun.json).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import (OptimizerConfig, ParallelConfig, get_config,
                           registry)
from repro.launch import roofline as RL
from repro.launch import steps as STEPS
from repro.launch.mesh import make_production_mesh
from repro.parallel import sharding as SH

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results"


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             pcfg: ParallelConfig = None, ocfg: OptimizerConfig = None,
             verbose: bool = True, tag: str = "",
             pcfg_overrides: dict = None) -> dict:
    cfg = get_config(arch)
    shape = next(s for s in registry.shapes_for(arch)
                 if s.name == shape_name)
    pcfg = pcfg or ParallelConfig(
        pod_axis="pod" if mesh_kind == "multi" else None,
        **(pcfg_overrides or {}))
    ocfg = ocfg or OptimizerConfig(
        state_dtype="bfloat16" if cfg.param_count() > 2e11 else "float32")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    ctx = SH.make_context(mesh, pcfg)

    t0 = time.time()
    with jax.set_mesh(mesh):
        args, in_sh, out_sh, step = STEPS.shapes_and_shardings(
            cfg, shape, pcfg, ocfg, ctx)
        in_shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), in_sh,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        out_shardings = jax.tree.map(
            lambda s: (jax.sharding.NamedSharding(mesh, s)
                       if s is not None else None), out_sh,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
            or x is None)
        jitted = jax.jit(step, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import HloCost
    hc = HloCost(hlo).summary()
    coll = {k[5:]: v for k, v in hc.items() if k.startswith("coll_")}

    # loop-corrected per-device costs (cost_analysis counts loop bodies once)
    flops = float(hc["flops"])
    bytes_accessed = float(hc["hbm_bytes"])
    coll_bytes = float(hc["collective_bytes"])
    mf = RL.model_flops_for(cfg, shape)
    peak_mem = (getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0))
    roof = RL.Roofline(
        arch=arch, shape=shape_name,
        mesh=("2x16x16" if mesh_kind == "multi" else "16x16"),
        chips=chips, flops_per_chip=flops,
        hbm_bytes_per_chip=bytes_accessed,
        collective_bytes_per_chip=coll_bytes,
        model_flops=mf, bytes_per_chip_peak=float(peak_mem))

    rec = roof.to_dict()
    rec.update({
        "tag": tag,
        "collectives": coll,
        "raw_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "ok": True,
    })
    if verbose:
        gib = (rec["memory"]["argument_bytes"] or 0) / 2**30
        tmp = (rec["memory"]["temp_bytes"] or 0) / 2**30
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']}: "
              f"args {gib:.2f} GiB/dev, temp {tmp:.2f} GiB/dev, "
              f"flops/dev {flops:.3e}, hbm {bytes_accessed:.3e} B, "
              f"coll {coll_bytes:.3e} B -> dominant={rec['dominant']} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
              flush=True)
    return rec


def _load(path: pathlib.Path) -> dict:
    if path.exists():
        return json.loads(path.read_text())
    return {}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-dlrm", action="store_true")
    ap.add_argument("--out", default=str(RESULTS / "dryrun.json"))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--xent-chunk", type=int, default=0)
    ap.add_argument("--bf16-gather", action="store_true")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--no-seq-par", action="store_true")
    ap.add_argument("--attn-impl", default="blocked",
                    choices=["blocked", "qchunked"])
    ap.add_argument("--emb-wire-bf16", action="store_true")
    ap.add_argument("--emb-cf", type=float, default=2.0)
    ap.add_argument("--emb-method", default="auto",
                    choices=["auto", "a2a", "psum"])
    args = ap.parse_args(argv)
    overrides = dict(xent_chunk=args.xent_chunk,
                     bf16_fsdp_gather=args.bf16_gather, remat=args.remat,
                     sequence_parallel=not args.no_seq_par,
                     attn_impl=args.attn_impl,
                     emb_wire_bf16=args.emb_wire_bf16,
                     emb_capacity_factor=args.emb_cf,
                     emb_method=args.emb_method)

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    cache = _load(out)

    cells = []
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    if args.all:
        archs = list(registry.ASSIGNED_ARCHS)
        if args.include_dlrm:
            archs.append("dlrm0")
        for a in archs:
            for s in registry.shapes_for(a):
                for m in meshes:
                    cells.append((a, s.name, m))
    else:
        assert args.arch and args.shape
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    failures = 0
    for arch, shape, mesh_kind in cells:
        k = f"{args.tag}/{arch}/{shape}/{mesh_kind}"
        if k in cache and cache[k].get("ok") and not args.force:
            print(f"[dryrun] cached {k}", flush=True)
            continue
        try:
            cache[k] = run_cell(arch, shape, mesh_kind, tag=args.tag,
                                pcfg_overrides=overrides)
        except Exception as e:  # record failure for triage
            failures += 1
            cache[k] = {"ok": False, "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-3000:]}
            print(f"[dryrun] FAIL {k}: {type(e).__name__}: {e}", flush=True)
        out.write_text(json.dumps(cache, indent=1))
    print(f"[dryrun] done: {len(cells)} cells, {failures} failures",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
