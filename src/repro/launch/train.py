"""CLI training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 50 \
        --reduced --batch 8 --seq 64

Full-scale configs (--arch without --reduced) target the production mesh and
are what the dry-run lowers; on a CPU host use --reduced.
"""
import argparse
import json

from repro.configs import (OptimizerConfig, ParallelConfig, RunConfig,
                           ShapeConfig, registry)
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b",
                    choices=list(registry.ALL_ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--remat", default="none",
                    choices=["none", "block", "full"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--optimizer", default="adam",
                    choices=["adam", "sgd", "adafactor"])
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    run = RunConfig(
        model=cfg,
        shape=ShapeConfig("cli", "train", args.seq, args.batch),
        parallel=ParallelConfig(remat=args.remat,
                                grad_compression=args.grad_compression),
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr,
                                  warmup_steps=max(args.steps // 10, 1)))
    trainer = Trainer(run, mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every)
    trainer.train(args.steps, log_every=max(args.steps // 10, 1))
    for m in trainer.metrics_log:
        print(json.dumps(m))


if __name__ == "__main__":
    main()
