# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness runner.

  PYTHONPATH=src python -m benchmarks.run          # all tables
  PYTHONPATH=src python -m benchmarks.run table2   # one table

Tables map to the paper: table1 (twin parameters), table2 (year
simulations), table3 (engineering comparison), table4 (retention costs),
plus the roofline table over the assigned (arch x shape) grid, a core
micro-benchmark of the wind-tunnel primitives, the twin-calibration
fit benchmark (which also writes BENCH_calibrate.json), the
grid-backend sweep ``grid-pallas`` — XLA vs the Pallas kernel at
64/256/1024 scenarios (writes BENCH_grid_pallas.json) — and the
streaming sweep ``grid-stream`` — series vs aggregate ``simulate_grid``
at 1024/8192/65536 full-year scenarios (writes BENCH_grid_stream.json) —
the sharded-engine sweep ``grid-shard`` — the policy-uniform block
engine at 65536/262144/1048576 full-year scenarios over a 1/2/4-device
scenario mesh (writes BENCH_grid_shard.json; on the CPU run with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) —
the device-resident histogram sweep ``grid-device`` — the fully
in-graph aggregate engine (f64 ``segment_sum`` histogram, no host
binning, duplicate scenario rows deduped at dispatch) at
1024/65536/1048576 full-year scenarios, single-device + 1/2/4 mesh,
plus an all-distinct control row, vs the PR 6 host-binned baseline
(writes BENCH_grid_device.json; same XLA_FLAGS note as
``grid-shard``) — and the policy-search
benchmark ``search`` — one-dispatch K-restart search vs a serial loop,
and search vs the exhaustive 4096-point grid
(writes BENCH_search.json) — plus ``search-stream`` — one
chance-constrained ``value_and_grad`` step at frontier scale (1024
lanes x 8736 bins), streamed in-carry objective vs
materialize-then-reduce, wall clock and peak temp bytes (merges a
"stream" key into BENCH_search.json).
"""
from __future__ import annotations

import sys
import time


def _micro() -> list:
    """Micro-benchmarks of wind-tunnel primitives (span overhead etc.)."""
    from repro.core.spans import SpanCollector, span
    from repro.core.loadpattern import LoadPattern
    col = SpanCollector()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("x", col):
            pass
    span_us = (time.perf_counter() - t0) / n * 1e6
    lp = LoadPattern.ramp("r", 120, 40)
    t0 = time.perf_counter()
    for i in range(200):
        lp.records_between(i % 100, i % 100 + 1)
    lp_us = (time.perf_counter() - t0) / 200 * 1e6
    return [f"micro/span_overhead,{span_us:.2f},per-span",
            f"micro/loadpattern_integral,{lp_us:.2f},per-second-window"]


TABLES = {
    "micro": _micro,
    "table1": lambda: __import__("benchmarks.table1_twins",
                                 fromlist=["main"]).main(),
    "table2": lambda: __import__("benchmarks.table2_sims",
                                 fromlist=["main"]).main(),
    "table3": lambda: __import__("benchmarks.table3_experiments",
                                 fromlist=["main"]).main(),
    "table4": lambda: __import__("benchmarks.table4_retention",
                                 fromlist=["main"]).main(),
    "grid": lambda: __import__("benchmarks.grid_bench",
                               fromlist=["main"]).main(),
    "grid-pallas": lambda: __import__("benchmarks.grid_bench",
                                      fromlist=["main_pallas"]).main_pallas(),
    "grid-stream": lambda: __import__("benchmarks.grid_bench",
                                      fromlist=["main_stream"]).main_stream(),
    "grid-shard": lambda: __import__("benchmarks.grid_bench",
                                     fromlist=["main_shard"]).main_shard(),
    "grid-device": lambda: __import__("benchmarks.grid_bench",
                                      fromlist=["main_device"]).main_device(),
    "calibrate": lambda: __import__("benchmarks.calibrate_bench",
                                    fromlist=["main"]).main(),
    "faults": lambda: __import__("benchmarks.faults_bench",
                                 fromlist=["main"]).main(),
    "search": lambda: __import__("benchmarks.search_bench",
                                 fromlist=["main"]).main(),
    "search-stream": lambda: __import__(
        "benchmarks.search_bench",
        fromlist=["main_stream"]).main_stream(),
    "roofline": lambda: __import__("benchmarks.roofline_bench",
                                   fromlist=["main"]).main(),
}


def main() -> int:
    """Run the named tables (all by default); 1 if any table failed."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    which = sys.argv[1:] or list(TABLES)
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        fn = TABLES.get(name)
        if fn is None:
            print(f"{name},0,unknown-table")
            failed.append(name)
            continue
        try:
            for line in fn():
                print(line, flush=True)
        except Exception as e:   # noqa: BLE001 — report, keep going
            print(f"{name}/error,0,{type(e).__name__}:{str(e)[:120]}")
            failed.append(name)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
