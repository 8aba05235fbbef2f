"""CLI serving driver (cluster session API, serve fast path).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --requests 8

serves the published config of ``--arch``; ``--reduced`` serves its small
test-size preset instead (what a CPU host can run).

``--chunk`` sets the multi-step decode width (tokens advanced per device
dispatch); ``--chunk 1`` is the per-token path with identical greedy output.
"""
import argparse
import json

import jax
import numpy as np

from repro.cluster import SliceSpec, Supercomputer
from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b",
                    choices=[a for a in registry.ALL_ARCHS if a != "dlrm0"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per device dispatch (1 = per-token)")
    ap.add_argument("--sample", action="store_true",
                    help="temperature sampling instead of greedy decode")
    ap.add_argument("--slice", dest="slice_chips", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small test-size preset of --arch "
                         "instead of its published config")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    params = jax.jit(lambda k: api.init_params(cfg, k))(jax.random.PRNGKey(0))
    sc = Supercomputer()
    with sc.allocate(args.slice_chips) as sl:
        session = sl.serve(cfg, params,
                           SliceSpec(slots=args.slots, max_len=args.max_len,
                                     prompt_len=args.prompt_len,
                                     greedy=not args.sample,
                                     chunk=args.chunk))
        rng = np.random.default_rng(0)
        for _ in range(args.requests):
            session.submit(rng.integers(0, cfg.vocab_size, size=8),
                           max_new_tokens=args.new_tokens)
        print(json.dumps(session.run(), indent=2))


if __name__ == "__main__":
    main()
