"""What-if grid microbenchmarks: looped vs vmapped, XLA vs Pallas, and
series vs streaming-aggregate.

The seed ran ``run_grid`` as a Python loop of one jitted scan per scenario;
the TwinPolicy engine stacks the whole (twin x traffic) grid and runs it as
one vmap-over-scan dispatch. ``bench`` times both on a 64-scenario grid
(8 twins spanning all five policies x 8 traffic forecasts) and emits a JSON
record with the measured speedup.

``bench_pallas`` times the two grid *backends* against each other — the
XLA vmapped ``lax.switch`` scan vs the fused Pallas scenario-grid kernel
(the Pallas interpreter on the CPU, a Mosaic kernel on a TPU) — at N in
{64, 256, 1024} scenarios, and writes ``BENCH_grid_pallas.json``.

``bench_stream`` times the two result *modes* end to end through
``simulate_grid`` — the [N, T]-series path (device series + f64 host
conversion + per-scenario numpy summaries) vs the streaming-aggregate
path (stats folded into the scan carry, chunked ``lax.map`` dispatch, one
vectorized summary pass) — at N in {1024, 8192, 65536} full-year
scenarios, and writes ``BENCH_grid_stream.json``. The series path only
runs where its five [N, 8736] f32 + f64 buffers fit comfortably
(N <= SERIES_MAX_N); the aggregate path streams every size through
scenario blocks, so 65536 scenarios complete on this CPU container.

``bench_shard`` sweeps the sharded block engine — the donated async
policy-uniform block dispatch of ``core.simulate._grid_agg_dispatch``,
single-device and over a 1/2/4-device scenario mesh — at N in
{65536, 262144, 1048576} full-year scenarios, and writes
``BENCH_grid_shard.json``. The mesh rows need four devices: on the CPU,
export ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before
running the sweep. On a 1-core CPU container the fake host
devices share the core, so the mesh rows document the sharded
*structure* (and its bit-parity with the one-device engine); the
single-device row is the wall-clock number, measured against the prior
serial ``lax.map`` engine recorded in ``BENCH_grid_stream.json``.

``bench_device_hist`` times the fully device-resident aggregate engine
(the in-graph f64 latency histogram reduction replacing the host
``np.bincount`` drain, no [B, T] latency panel staged or copied off
device, bitwise-duplicate scenario rows deduped at dispatch) — at N in
{1024, 65536, 1048576} full-year scenarios, single-device and over a
1/2/4-device scenario mesh, plus a jittered all-distinct control row
where dedup cannot fire — and writes ``BENCH_grid_device.json``, with
the speedup measured against the PR 6 host-binned devices=1 rows
recorded in ``BENCH_grid_shard.json``.

  PYTHONPATH=src python benchmarks/grid_bench.py           # looped/vmapped
  PYTHONPATH=src python benchmarks/grid_bench.py pallas    # backend sweep
  PYTHONPATH=src python benchmarks/grid_bench.py stream    # series vs agg
  PYTHONPATH=src python benchmarks/grid_bench.py shard     # sharded engine
  PYTHONPATH=src python benchmarks/grid_bench.py device    # device-res hist
  PYTHONPATH=src python -m benchmarks.run grid             # looped/vmapped
  PYTHONPATH=src python -m benchmarks.run grid-pallas      # backend sweep
  PYTHONPATH=src python -m benchmarks.run grid-stream      # series vs agg
  PYTHONPATH=src python -m benchmarks.run grid-shard       # sharded engine
  PYTHONPATH=src python -m benchmarks.run grid-device      # device-res hist
  make grid-bench-pallas / grid-bench-stream / grid-bench-shard /
       grid-bench-device

Every timing loop records through ``repro.obs`` (``obs.timed`` spans) —
the JSON rows serialize those spans' best-of numbers, and running any
sweep under ``REPRO_OBS=1`` additionally surfaces the engine's own
``grid.block`` / ``grid.round`` spans next to them (``obs.render()``).
"""
from __future__ import annotations

import json
import pathlib
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.simulate import _grid_scan, _grid_scan_xla, simulate_grid
from repro.core.slo import SLO
from repro.core.traffic import TrafficModel
from repro.core.twin import (QuickscalingTwin, SimpleTwin, make_twin,
                             policy_onehot, registry_version)
from repro.kernels.ops import interpret_mode
from repro.kernels.policy_scan import policy_grid_scan

N_TWINS = 8
N_TRAFFICS = 8
REPEATS = 5
PALLAS_SIZES = (64, 256, 1024)
STREAM_SIZES = (1024, 8192, 65536)
SHARD_SIZES = (65536, 262144, 1048576)
SHARD_MESHES = (1, 2, 4)
DEVICE_SIZES = (1024, 65536, 1048576)
SERIES_MAX_N = 1024        # five [N, 8736] f32+f64 series stay <1 GB here
STREAM_BLOCK = 4096        # aggregate-mode lax.map scenario block
BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_grid_pallas.json"
STREAM_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_grid_stream.json"
SHARD_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_grid_shard.json"
DEVICE_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_grid_device.json"


def _grid(n_twins: int = N_TWINS, n_traffics: int = N_TRAFFICS):
    twins = [
        SimpleTwin("block", 1.9512, 0.0082, 0.15),
        SimpleTwin("non-block", 6.15, 0.0703, 0.06),
        SimpleTwin("cpu-lim", 0.6612, 0.0027, 0.29),
        QuickscalingTwin("quick", 1.9512, 0.0082, 0.15),
        make_twin("auto-fast", "autoscale", max_rps=0.5, usd_per_hour=0.002,
                  base_latency_s=0.1, scale_up_hours=1),
        make_twin("auto-slow", "autoscale", max_rps=0.5, usd_per_hour=0.002,
                  base_latency_s=0.1, scale_up_hours=6),
        make_twin("shed", "shed", max_rps=1.0, usd_per_hour=0.0082,
                  base_latency_s=0.15, queue_cap_hours=2),
        make_twin("batch", "batch_window", max_rps=6.15, usd_per_hour=0.0703,
                  base_latency_s=0.06, window_hours=6),
    ][:n_twins]
    traffics = [TrafficModel.honda_default(f"g{g:.2f}", R=3.5, G=g)
                for g in np.linspace(1.0, 1.7, n_traffics)]
    grid_twins, loads = [], []
    for tr in traffics:
        hl = tr.hourly_loads()
        for tw in twins:
            grid_twins.append(tw)
            loads.append(hl)
    return grid_twins, np.stack(loads).astype(np.float32)


def _kernel_args(twins, loads):
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    return loads, params, idx, registry_version()


def bench() -> Dict:
    twins, loads = _grid()
    loads_j, params, idx, ver = _kernel_args(twins, loads)
    n = len(twins)

    # vmapped: one dispatch over the stacked batch
    def vmapped():
        out = _grid_scan(loads_j, params, idx, ver)
        jax.block_until_ready(out)

    # looped: the seed's shape — one batch-of-1 kernel call per scenario
    def looped():
        for i in range(n):
            out = _grid_scan(loads_j[i:i + 1], params[i:i + 1],
                             idx[i:i + 1], ver)
        jax.block_until_ready(out)

    vmapped(), looped()          # warm both jit caches
    t_vm, t_loop = [], []
    for _ in range(REPEATS):
        with obs.timed("bench.grid_vmapped", scenarios=n) as tm:
            vmapped()
        t_vm.append(tm.elapsed)
        with obs.timed("bench.grid_looped", scenarios=n) as tm:
            looped()
        t_loop.append(tm.elapsed)
    vm_ms = min(t_vm) * 1e3
    loop_ms = min(t_loop) * 1e3
    return {
        "scenarios": n,
        "hours": int(loads.shape[1]),
        "looped_ms": round(loop_ms, 3),
        "vmapped_ms": round(vm_ms, 3),
        "speedup": round(loop_ms / vm_ms, 2),
        "device": jax.devices()[0].platform,
    }


def _time_best(fn, repeats: int = REPEATS,
               label: str = "bench.grid") -> float:
    fn()                                  # warm the jit cache
    best = float("inf")
    for _ in range(repeats):
        with obs.timed(label) as tm:
            fn()
        best = min(best, tm.elapsed)
    return best * 1e3


def bench_pallas(sizes=PALLAS_SIZES, repeats: int = REPEATS) -> Dict:
    """XLA vmapped-switch backend vs fused Pallas scenario-grid kernel.

    On the CPU the kernel runs in the Pallas interpreter, so the numbers
    there measure the fused-scan structure (one pallas_call, scenarios on
    lanes, carry resident) rather than TPU silicon; parity with the XLA
    path is asserted on every size before timing.
    """
    rows = []
    for n in sizes:
        twins, loads = _grid(n_twins=8, n_traffics=-(-n // 8))
        twins, loads = twins[:n], loads[:n]
        loads, params, idx, ver = _kernel_args(twins, loads)
        loads_j, params_j = jnp.asarray(loads), jnp.asarray(params)
        idx_j = jnp.asarray(idx)
        onehot_j = jnp.asarray(policy_onehot(idx))

        def xla():
            jax.block_until_ready(
                _grid_scan_xla(loads_j, params_j, idx_j, ver, 1.0))

        def pallas():
            jax.block_until_ready(
                policy_grid_scan(loads_j, params_j, onehot_j, 1.0))

        # parity first (1e-5 relative on every series), then wall-clock
        _, outs_x = _grid_scan_xla(loads_j, params_j, idx_j, ver, 1.0)
        _, outs_p = policy_grid_scan(loads_j, params_j, onehot_j, 1.0)
        for a, b in zip(outs_x, outs_p):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)

        xla_ms = _time_best(xla, repeats)
        pallas_ms = _time_best(pallas, repeats)
        rows.append({"scenarios": n, "hours": int(loads.shape[1]),
                     "xla_ms": round(xla_ms, 3),
                     "pallas_ms": round(pallas_ms, 3),
                     "pallas_over_xla": round(pallas_ms / xla_ms, 3)})
    out = {"device": jax.devices()[0].platform, "repeats": repeats,
           "mode": "interpret" if interpret_mode() else "mosaic",
           "parity_rtol": 1e-5, "sizes": rows}
    BENCH_JSON.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return out


def _stream_grid(n: int, n_traffics: int = 16):
    """n scenarios as twins + a [n_traffics, 8736] load matrix + index map
    (the O(K*T + N) host encoding ``whatif.run_grid`` uses) — the 8 bench
    twins cycled over growth-swept traffic forecasts."""
    twins8, _ = _grid(n_twins=8, n_traffics=1)
    twins = [twins8[i % 8] for i in range(n)]
    matrix = np.stack([TrafficModel.honda_default(f"g{g:.3f}", R=3.5,
                                                  G=float(g)).hourly_loads()
                       for g in np.linspace(1.0, 1.7, n_traffics)]).astype(
        np.float32)
    index = (np.arange(n, dtype=np.int32) // 8) % n_traffics
    return twins, matrix, index


def bench_stream(sizes=STREAM_SIZES, repeats: int = 3) -> Dict:
    """Series vs streaming-aggregate ``simulate_grid``, end to end.

    Both modes run the same XLA switch-scan policy math over the same
    (load matrix, index) grid with a 4h latency SLO; what differs is
    everything around it — five [N, 8736] output series + f64 conversion
    + a per-scenario numpy summary loop, vs O(N) in-carry aggregates +
    one vectorized summary pass. Aggregate wall-clock must come out
    >= 2x faster at N = 1024 (the acceptance bar); scalar outputs are
    asserted bit-identical before timing wherever both modes run.
    """
    slo = SLO(limit_s=4 * 3600, met_fraction=0.95)
    rows = []
    for n in sizes:
        twins, matrix, index = _stream_grid(n)
        block = min(STREAM_BLOCK, n)

        def agg():
            return simulate_grid(twins, slo=slo, return_series=False,
                                 load_matrix=matrix, load_index=index,
                                 scenario_block=block)

        row = {"scenarios": n, "hours": int(matrix.shape[1]),
               "scenario_block": block}
        sims_a = agg()                          # warm + parity sample
        agg_ms = _time_best(agg, repeats)
        row["aggregate_ms"] = round(agg_ms, 1)
        if n <= SERIES_MAX_N:
            def series():
                return simulate_grid(twins, slo=slo, return_series=True,
                                     load_matrix=matrix, load_index=index)

            sims_s = series()
            for s, a in zip(sims_s, sims_a):
                assert s.total_cost_usd == a.total_cost_usd, s.name
                assert s.max_throughput_rph == a.max_throughput_rph
                assert s.slo_met == a.slo_met
            series_ms = _time_best(series, repeats)
            row["series_ms"] = round(series_ms, 1)
            row["agg_speedup"] = round(series_ms / agg_ms, 2)
        else:
            row["series_ms"] = None             # would not fit sensibly
            row["agg_speedup"] = None
        rows.append(row)
        del sims_a
    out = {"device": jax.devices()[0].platform, "repeats": repeats,
           "series_max_n": SERIES_MAX_N, "slo": "latency<=4h@95%",
           "parity": "scalar outputs bit-identical where both modes ran",
           "sizes": rows}
    STREAM_JSON.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return out


def _shard_grid(n: int, n_traffics: int = 16):
    """The shard sweep's raw dispatch operands — the ``_stream_grid``
    scenario mix without materializing an n-element twin list (at a
    million scenarios the engine arrays are the honest cost; a Python
    object list is not)."""
    twins8, _ = _grid(n_twins=8, n_traffics=1)
    reps = -(-n // 8)
    params = np.tile(np.stack([tw.padded_params() for tw in twins8]),
                     (reps, 1))[:n].astype(np.float32)
    idx = np.tile(np.asarray([tw.policy_index for tw in twins8], np.int32),
                  reps)[:n]
    matrix = np.stack([TrafficModel.honda_default(f"g{g:.3f}", R=3.5,
                                                  G=float(g)).hourly_loads()
                       for g in np.linspace(1.0, 1.7, n_traffics)]).astype(
        np.float32)
    index = (np.arange(n, dtype=np.int32) // 8) % n_traffics
    return matrix, index, params, idx


def bench_shard(sizes=SHARD_SIZES, meshes=SHARD_MESHES) -> Dict:
    """Sharded million-scenario aggregate engine: N x mesh sweep.

    Every (N, devices) cell runs the full streaming dispatch end to end —
    policy-uniform block plan, donated async device scans, overlapped
    host histogram binning, scatter back to grid order. devices=1 is the
    single-device engine; devices>1 shards one block per device per
    round through ``shard_map``. Bit-parity across mesh sizes is
    asserted at the smallest N before any timing is recorded.
    """
    from repro.core.simulate import _grid_agg_dispatch, agg_auto_block
    avail = jax.device_count()
    usable = [d for d in meshes if d <= avail]
    skipped = [d for d in meshes if d > avail]
    slo_limit = 4.0 * 3600.0
    block = agg_auto_block(8736)

    def dispatch(matrix, index, params, idx, d):
        return _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                  slo_limit, 0, None,
                                  devices=None if d == 1 else d)

    # warm every mesh's jit cache on a 2x-block grid (same [block] shapes
    # the big sweeps compile to), so the timed runs measure execution
    warm = _shard_grid(2 * block)
    for d in usable:
        dispatch(*warm, d)

    rows = []
    for n in sizes:
        matrix, index, params, idx = _shard_grid(n)
        row = {"scenarios": n, "hours": int(matrix.shape[1]),
               "scenario_block": block, "mesh": {}}
        base = None
        for d in usable:
            with obs.timed("bench.grid_shard", scenarios=n,
                           mesh=d) as tm:
                carry, agg = dispatch(matrix, index, params, idx, d)
            row["mesh"][str(d)] = round(tm.elapsed * 1e3, 1)
            if n == sizes[0]:
                if base is None:
                    base = (carry, agg)
                else:
                    np.testing.assert_array_equal(carry, base[0])
                    np.testing.assert_array_equal(agg, base[1])
        del carry, agg, base
        rows.append(row)
    baseline = None
    if STREAM_JSON.exists():      # the prior serial lax.map engine's time
        for r in json.loads(STREAM_JSON.read_text())["sizes"]:
            if r["scenarios"] == sizes[0] and r.get("aggregate_ms"):
                baseline = {"scenarios": sizes[0],
                            "lax_map_aggregate_ms": r["aggregate_ms"]}
    out = {"device": jax.devices()[0].platform, "device_count": avail,
           "meshes": usable, "meshes_skipped_no_devices": skipped,
           "scenario_block": block,
           "parity": "mesh results bit-identical at the smallest N",
           "note": "fake host devices share this container's one core; "
                   "mesh>1 rows document sharded structure, devices=1 is "
                   "the wall-clock number",
           "serial_baseline": baseline, "sizes": rows}
    SHARD_JSON.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return out


def bench_device_hist(sizes=DEVICE_SIZES, meshes=SHARD_MESHES) -> Dict:
    """Fully device-resident aggregate engine: N x mesh sweep vs PR 6.

    Same dispatch shape as ``bench_shard`` (policy-uniform blocks,
    donated accumulators, ``shard_map`` rounds for devices>1), but the
    engine under it no longer stages a [B, T] latency panel or drains it
    to the host for ``np.bincount`` binning — the load-weighted
    quarter-octave histogram accumulates in-graph as an exact f64
    masked reduction per time chunk, and blocks are sized by the
    panel-free footprint. The dispatch also dedups bitwise-identical
    scenario rows before simulating — this sweep's grid tiles 8 twins
    over 8 traffic ramps, so every N collapses to the same 128 distinct
    scenarios; ``unique_scenarios`` records that per row, and the
    ``distinct`` row jitters every param vector so dedup CANNOT fire
    and the raw no-dedup engine time is on record next to the tiled
    ones. The speedup rows compare end to end against the host-binned
    devices=1 times recorded in ``BENCH_grid_shard.json`` (same
    container, same tiled scenario mix — the PR 6 engine had no dedup
    and simulated every row). Bit-parity across mesh sizes is asserted
    at the smallest N before any timing is recorded.
    """
    from repro.core.simulate import (_dedup_rows, _grid_agg_dispatch,
                                     agg_auto_block)
    avail = jax.device_count()
    usable = [d for d in meshes if d <= avail]
    skipped = [d for d in meshes if d > avail]
    slo_limit = 4.0 * 3600.0
    block = agg_auto_block(8736)

    def dispatch(matrix, index, params, idx, d):
        return _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                  slo_limit, 0, None,
                                  devices=None if d == 1 else d)

    # warm every mesh's jit cache on a 2x-block grid (same [block] shapes
    # the big sweeps compile to), so the timed runs measure execution
    warm = _shard_grid(2 * block)
    for d in usable:
        dispatch(*warm, d)

    baseline = {}
    if SHARD_JSON.exists():   # PR 6 host-binned engine, same scenario mix
        for r in json.loads(SHARD_JSON.read_text())["sizes"]:
            if r.get("mesh", {}).get("1"):
                baseline[r["scenarios"]] = r["mesh"]["1"]

    rows = []
    for n in sizes:
        matrix, index, params, idx = _shard_grid(n)
        dd = _dedup_rows(index, params, idx)
        row = {"scenarios": n, "hours": int(matrix.shape[1]),
               "scenario_block": block,
               "unique_scenarios": n if dd is None else int(len(dd[0])),
               "mesh": {}}
        del dd
        base = None
        for d in usable:
            with obs.timed("bench.grid_device", scenarios=n,
                           mesh=d) as tm:
                carry, agg = dispatch(matrix, index, params, idx, d)
            row["mesh"][str(d)] = round(tm.elapsed * 1e3, 1)
            if n == sizes[0]:
                if base is None:
                    base = (carry, agg)
                else:
                    np.testing.assert_array_equal(carry, base[0])
                    np.testing.assert_array_equal(agg, base[1])
        del carry, agg, base
        if n in baseline:
            row["host_binned_d1_ms"] = baseline[n]
            row["speedup_vs_host_binned"] = round(
                baseline[n] / row["mesh"]["1"], 2)
        rows.append(row)

    # the no-dedup control: jitter every param vector so each of the
    # 1024 rows is bitwise distinct and the engine simulates all of them
    n = 1024
    matrix, index, params, idx = _shard_grid(n)
    params = (params
              * (1.0 + np.arange(n, dtype=np.float32)[:, None] * 1e-5))
    assert _dedup_rows(index, params, idx) is None
    dispatch(matrix, index, params, idx, 1)      # warm this shape
    with obs.timed("bench.grid_device", scenarios=n, mesh=1,
                   distinct=True) as tm:
        dispatch(matrix, index, params, idx, 1)
    rows.append({"scenarios": n, "hours": int(matrix.shape[1]),
                 "scenario_block": block, "distinct": True,
                 "unique_scenarios": n,
                 "mesh": {"1": round(tm.elapsed * 1e3, 1)}})

    out = {"device": jax.devices()[0].platform, "device_count": avail,
           "meshes": usable, "meshes_skipped_no_devices": skipped,
           "scenario_block": block,
           "parity": "mesh results bit-identical at the smallest N",
           "note": "device-resident f64 histogram reduction, no [B,T] "
                   "panel, no host binning; the dispatch dedups bitwise-"
                   "duplicate scenario rows, and this tiled sweep "
                   "collapses to unique_scenarios distinct years per row "
                   "(the distinct row disables that by construction); "
                   "speedup vs the PR 6 host-binned no-dedup devices=1 "
                   "rows in BENCH_grid_shard.json",
           "sizes": rows}
    DEVICE_JSON.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return out


def main() -> List[str]:
    r = bench()
    return [f"grid/looped_{r['scenarios']}x,{r['looped_ms'] * 1e3:.0f},"
            f"per-scenario-dispatch",
            f"grid/vmapped_{r['scenarios']}x,{r['vmapped_ms'] * 1e3:.0f},"
            f"speedup={r['speedup']}x;{json.dumps(r, sort_keys=True)}"]


def main_pallas() -> List[str]:
    r = bench_pallas()
    lines = []
    for row in r["sizes"]:
        n = row["scenarios"]
        lines.append(f"grid/xla_{n}x,{row['xla_ms'] * 1e3:.0f},"
                     f"vmapped-switch-scan")
        lines.append(f"grid/pallas_{n}x,{row['pallas_ms'] * 1e3:.0f},"
                     f"{r['mode']};ratio={row['pallas_over_xla']}")
    lines.append(f"grid/pallas_json,0,wrote={BENCH_JSON.name}")
    return lines


def main_stream() -> List[str]:
    r = bench_stream()
    lines = []
    for row in r["sizes"]:
        n = row["scenarios"]
        lines.append(f"grid/agg_{n}x,{row['aggregate_ms'] * 1e3:.0f},"
                     f"streaming-aggregate;block={row['scenario_block']}")
        if row["series_ms"] is not None:
            lines.append(f"grid/series_{n}x,{row['series_ms'] * 1e3:.0f},"
                         f"full-series;agg_speedup={row['agg_speedup']}x")
        else:
            lines.append(f"grid/series_{n}x,0,skipped;over-series-budget")
    lines.append(f"grid/stream_json,0,wrote={STREAM_JSON.name}")
    return lines


def main_shard() -> List[str]:
    r = bench_shard()
    lines = []
    for row in r["sizes"]:
        n = row["scenarios"]
        for d, ms in sorted(row["mesh"].items(), key=lambda kv: int(kv[0])):
            lines.append(f"grid/shard_{n}x_d{d},{ms * 1e3:.0f},"
                         f"block={row['scenario_block']}")
    if r["serial_baseline"]:
        b = r["serial_baseline"]
        lines.append(f"grid/shard_baseline_{b['scenarios']}x,"
                     f"{b['lax_map_aggregate_ms'] * 1e3:.0f},"
                     f"prior-serial-lax-map")
    lines.append(f"grid/shard_json,0,wrote={SHARD_JSON.name}")
    return lines


def main_device() -> List[str]:
    r = bench_device_hist()
    lines = []
    for row in r["sizes"]:
        n = row["scenarios"]
        tag = "_distinct" if row.get("distinct") else ""
        for d, ms in sorted(row["mesh"].items(), key=lambda kv: int(kv[0])):
            lines.append(f"grid/device_{n}x{tag}_d{d},{ms * 1e3:.0f},"
                         f"block={row['scenario_block']};"
                         f"unique={row['unique_scenarios']}")
        if row.get("host_binned_d1_ms"):
            lines.append(f"grid/device_baseline_{n}x,"
                         f"{row['host_binned_d1_ms'] * 1e3:.0f},"
                         f"host-binned;speedup="
                         f"{row['speedup_vs_host_binned']}x")
    lines.append(f"grid/device_json,0,wrote={DEVICE_JSON.name}")
    return lines


if __name__ == "__main__":
    if "device" in sys.argv[1:]:
        print(json.dumps(bench_device_hist(), indent=2, sort_keys=True))
    elif "shard" in sys.argv[1:]:
        print(json.dumps(bench_shard(), indent=2, sort_keys=True))
    elif "pallas" in sys.argv[1:]:
        print(json.dumps(bench_pallas(), indent=2, sort_keys=True))
    elif "stream" in sys.argv[1:]:
        print(json.dumps(bench_stream(), indent=2, sort_keys=True))
    else:
        print(json.dumps(bench(), indent=2, sort_keys=True))
