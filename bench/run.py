"""Benchmark entry point: run one cell of BENCHMARK.json on the chips JAX
holds and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer than
the cell asks for, or where the program is not beside the benchmark.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

try:
    import repro  # noqa: E402,F401
except ImportError as e:
    sys.exit(f"bench: the program is not in this checkout ({e})")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
