#!/usr/bin/env python3
"""The wind tunnel's benchmark: one run of one cell on the chip(s) JAX
finds, printing its result as the last line of standard output.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cells, their configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the repository root; ``PERF.md`` says why each is
there. A run sets up the cell (data from the seed, the persistent compile
cache, one warm-up request of the cell's own shape), measures for
``--seconds`` (the last request finishes), compares the answers with the
plain reference (``bench/check.py``) and prints one JSON line. With
``--trace 1`` it reports the per-layer metrics of the profiled requests
instead of the end-to-end ones. It exits 2, printing no result, where it
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        harness.run(args.workload, args.seed, args.seconds,
                    bool(args.trace), T_START)
    except (harness.DeviceError, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
