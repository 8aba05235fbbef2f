"""Offer a serving cell's mix at several fixed rates, on several seeds, one
window each, in one process, to find the knee: the highest rate at which
the queue does not grow over the window.  A cell's rate is then fixed in its
mix file; the benchmark's own runs never search for one.

    python3 bench/sweep.py --workload olmo1b-serve-chat \
        --rates 0.8,1.0,1.2 --seeds 7,8 --seconds 30

``--engine`` merges a JSON object into the mix's engine settings, to drive
another path of the program on the same traffic.  Prints, per rate and
seed, the run's info line and its result line.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv) -> int:
    import argparse
    import json

    import jax

    from bench import harness as H
    from bench.peaks import peaks_for
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="7")
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--engine", default="{}")
    args = ap.parse_args(argv)

    bench = H.load_benchmark()
    device = H.require_chips(H.find_cell(bench, args.workload).chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = H.find_cell(bench, args.workload)
            cell.mix["rate_per_s"] = rate
            cell.mix["engine"].update(json.loads(args.engine))
            job = H.Job(cell=cell, seed=seed, seconds=args.seconds,
                        t_start=time.perf_counter(), tracer=H.Tracer(False),
                        peaks=peaks_for(device["kind"]))
            print(json.dumps(H.execute(job, device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
