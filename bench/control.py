#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference computed one precision lower (bfloat16 per-bin arithmetic in
place of float32) takes the program's place, on the requests a run of
the cell would check, and is compared with the reference as the
program's answers are. Its readings are the upper ends the limits in
``bench/limits.json`` are set below; the benchmark's own runs never run
it.

    python3 bench/control.py --workload <name> --seeds 1 2 3 \\
        --requests <requests a window holds>
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def readings(workload: str, seed: int, requests: int, root: str = ROOT):
    import ml_dtypes
    from bench import check, generator, harness
    from bench.system import System
    spec = harness.load_json(root, "BENCHMARK.json")
    bench_dir = os.path.join(root, spec["paths"][0])
    cell = harness.find_cell(spec, workload)
    cfg = harness.load_json(bench_dir, "configs", f"{cell['config']}.json")
    mix = harness.load_json(bench_dir, "traffic", f"{cell['traffic']}.json")
    traffic = generator.traffic(cfg, mix, seed, System())
    reqs = [traffic.make(generator.WINDOW, i) for i in range(requests)]
    return check.check_requests(traffic, reqs, cfg, seed, mix,
                                dtype=ml_dtypes.bfloat16, control=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(args.workload, seed,
                                              args.requests)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
