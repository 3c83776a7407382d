"""The paper's two what-if studies plus a beyond-paper policy sweep, all
driven by the unified TwinPolicy engine (one vmapped scan per grid):

  1. "What if increased car sales put 50% more cars on the road by the end
     of the year?"  (Table II: six twin x forecast simulations)
  2. "What would be the cost impact of doubling data retention from 3 to 6
     months?"       (Table IV: monthly cloud/network/storage costs)
  3. "Which scaling policy should the blocking-write pipeline run?" —
     fifo vs quickscale vs autoscale (slow/fast) vs shed vs batch_window,
     on the same traffic, priced per instance.
  4. The same policy sweep re-run on the fused Pallas grid backend
     (``kernels.ops.pallas_mode()``): scenarios ride the vector lanes of
     one kernel instead of the XLA vmapped lax.switch scan — the Pallas
     interpreter on the CPU, a Mosaic kernel on a TPU — and the Table II
     numbers agree to 1e-5.
  5. A 4096-scenario cost-lever sweep (autoscaling delay x instance cap x
     queue cap x batch window x growth — the levers Jablonski & Heltweg
     catalogue for cloud pipelines) through the STREAMING-AGGREGATE grid:
     ``run_grid`` holds each traffic's load row once (load matrix + index
     map), folds the Table II statistics into the scan carry, and returns
     O(N) ``GridSummary`` rows — no [N, 8736] series ever exists, so the
     same engine scales to 100k+ scenarios (see ``make
     grid-bench-stream``).
  6. The INVERSE question — "cheapest autoscale config that keeps p95
     under 2h at +40% traffic?" — answered directly by
     ``whatif.optimize_scenario`` (repro.search): multi-start projected
     AdamW on a differentiable annual-cost + SLO-hinge objective, all
     restarts as lanes of one grad-of-scan dispatch, feasibility
     re-checked bit-exactly, plus the cost-vs-SLO Pareto frontier
     ("what does tightening the SLO cost?").
  7. CHAOS: "what do outages and reconnect floods do to the Table II
     picture, and what is the cheapest config that survives 95% of
     them?" — a ``repro.faults`` schedule crosses the grid with F
     sampled fault futures per scenario (``run_grid(faults=...)``,
     fault-attribution columns in Table II), and
     ``optimize_scenario(faults=..., quantile=0.95)`` runs the
     chance-constrained search: cheapest configuration meeting the SLO
     in >= 95% of futures, achieved quantile re-checked bit-exactly.

Registered twin policies (see repro/core/twin.py):

  policy        extra params                         behaviour
  ------------  -----------------------------------  -------------------------
  fifo          -                                    fixed capacity, infinite
                                                     FIFO queue (paper)
  quickscale    -                                    ideal scaling, pay
                                                     ceil(load/cap) instances
  autoscale     min/max_instances, scale_up_hours    bounded scaling with
                                                     boot delay
  shed          queue_cap_hours                      bounded queue, overflow
                                                     dropped
  batch_window  window_hours, idle_cost_fraction     accumulate-then-flush
                                                     batching

Any new policy registered with ``register_policy`` joins ``run_grid``
automatically — the grid kernel dispatches per scenario via lax.switch.

The twins below are hand-entered from the paper's Table I; to *fit* a
twin to a measured trace by gradient descent through the simulation scan
(measure -> fit -> grid, with holdout validation), see
``examples/calibrate_twin.py`` and ``repro.calibrate``.

Run:  PYTHONPATH=src python examples/whatif_analysis.py
"""
from repro.core.cost import CostModel
from repro.core.report import render_table
from repro.core.slo import SLO
from repro.core.traffic import TrafficModel
from repro.core.twin import SimpleTwin, make_twin, policy_table_rows
from repro.core.whatif import retention_whatif, run_grid, table2_rows

# the paper's Table I twins (cents/hr -> USD/hr)
twins = [SimpleTwin("blocking-write", 1.9512, 0.0082, 0.15),
         SimpleTwin("no-blocking-write", 6.15, 0.0703, 0.06),
         SimpleTwin("cpu-limited", 0.6612, 0.0027, 0.29)]
nominal = TrafficModel.honda_default("nominal", R=3.5, G=1.0)
high = TrafficModel.honda_default("high(+50%)", R=3.5, G=1.5)
slo = SLO(limit_s=4 * 3600, met_fraction=0.95)

sims = run_grid(twins, [nominal, high], slo=slo)
print(render_table(table2_rows(sims),
                   "What-if #1: +50% car sales (paper Table II)"))
print("paper: SLO met only for {nom block, nom non-block, high non-block}\n")

tables = retention_whatif(twins[1], nominal, record_mb=0.0141,
                          retentions_days=(91, 182),
                          cost_model=CostModel())
for ret, rows in tables.items():
    total = sum(r["total_usd"] for r in rows)
    print(render_table(rows, f"What-if #2: {ret}-day retention "
                             f"(year total ${total:,.2f})"))

# ---------------------------------------------------------------------------
# What-if #3 (beyond paper): policy choice for the blocking-write pipeline.
# Price one instance at the measured blocking-write rate/cost and sweep the
# scaling policy; the whole (6 policies x 2 forecasts) grid is one dispatch.
# ---------------------------------------------------------------------------
print(render_table(policy_table_rows(), "Registered twin policies"))

RPS, USD_HR, LAT = 1.9512, 0.0082, 0.15
policy_twins = [
    SimpleTwin("fifo", RPS, USD_HR, LAT),
    make_twin("quickscale", "quickscale", max_rps=RPS, usd_per_hour=USD_HR,
              base_latency_s=LAT),
    make_twin("autoscale-1h", "autoscale", max_rps=RPS, usd_per_hour=USD_HR,
              base_latency_s=LAT, max_instances=8, scale_up_hours=1),
    make_twin("autoscale-6h", "autoscale", max_rps=RPS, usd_per_hour=USD_HR,
              base_latency_s=LAT, max_instances=8, scale_up_hours=6),
    make_twin("shed-4h", "shed", max_rps=RPS, usd_per_hour=USD_HR,
              base_latency_s=LAT, queue_cap_hours=4),
    make_twin("batch-6h", "batch_window", max_rps=RPS, usd_per_hour=USD_HR,
              base_latency_s=LAT, window_hours=6),
]
psims = run_grid(policy_twins, [nominal, high], slo=slo)
print(render_table(table2_rows(psims),
                   "What-if #3: scaling-policy sweep (blocking-write rates)"))
print("a slow autoscaler (6h boot) clears the fifo backlog for less than "
      "quickscale's\nbill while still meeting the SLO; shed trades dropped "
      "records for bounded\nlatency; batch_window is cheapest when latency "
      "may reach half a window.")

# ---------------------------------------------------------------------------
# What-if #4: the same grid on the fused Pallas backend. ``pallas_mode()``
# flips ``core.simulate._grid_scan`` from the XLA vmapped lax.switch scan
# to the one-pallas_call scenario-grid kernel (kernels/policy_scan.py);
# scenarios sit on the vector lanes and every policy runs branchless.
# ---------------------------------------------------------------------------
from repro.kernels.ops import pallas_mode  # noqa: E402

with pallas_mode():         # interpreted on the CPU, Mosaic on a TPU
    psims_pallas = run_grid(policy_twins, [nominal, high], slo=slo)
print(render_table(table2_rows(psims_pallas),
                   "What-if #4: same sweep, Pallas grid backend"))
worst = max(abs(p.total_cost_usd - x.total_cost_usd)
            / max(abs(x.total_cost_usd), 1e-9)
            for p, x in zip(psims_pallas, psims))
assert worst <= 1e-5, f"backend drift: {worst:.2e} exceeds 1e-5 vs XLA"
print(f"backends agree: worst relative cost difference vs XLA = "
      f"{worst:.2e} (tolerance 1e-5)")

# ---------------------------------------------------------------------------
# What-if #5: a 4096-scenario cost-lever sweep on the streaming-aggregate
# grid. 256 twins (64 autoscale delay x cap combos, 64 shed queue caps,
# 64 batch windows x idle fractions, 64 fifo/quickscale capacity points)
# x 16 growth forecasts = 4096 full-year scenarios; run_grid keeps ONE
# copy of each forecast's 8736-hour load row and returns scalar
# GridSummary rows straight off the in-carry aggregates. table2_rows
# consumes only scalars, so nothing about the report changes — only the
# memory (O(N) instead of O(N*8736)) and the scale ceiling.
# ---------------------------------------------------------------------------
import numpy as np  # noqa: E402

sweep_twins = []
for d, (cap, delay) in enumerate((c, dl) for c in (2, 4, 8, 16, 24, 32,
                                                   48, 64)
                                 for dl in (0.5, 1, 2, 3, 4, 6, 9, 12)):
    sweep_twins.append(make_twin(f"auto-c{cap}-d{delay:g}", "autoscale",
                                 max_rps=RPS, usd_per_hour=USD_HR,
                                 base_latency_s=LAT, max_instances=cap,
                                 scale_up_hours=delay))
for q in np.geomspace(0.25, 96.0, 64):
    sweep_twins.append(make_twin(f"shed-q{q:.2f}", "shed", max_rps=RPS,
                                 usd_per_hour=USD_HR, base_latency_s=LAT,
                                 queue_cap_hours=float(q)))
for w, f in ((w, f) for w in np.geomspace(0.5, 24.0, 16)
             for f in (0.05, 0.1, 0.2, 0.4)):
    sweep_twins.append(make_twin(f"batch-w{w:.1f}-f{f}", "batch_window",
                                 max_rps=RPS, usd_per_hour=USD_HR,
                                 base_latency_s=LAT, window_hours=float(w),
                                 idle_cost_fraction=f))
for i, r in enumerate(np.geomspace(0.5, 16.0, 64)):
    policy = "fifo" if i % 2 else "quickscale"
    sweep_twins.append(make_twin(f"{policy}-r{r:.2f}", policy,
                                 max_rps=RPS * float(r),
                                 usd_per_hour=USD_HR * float(r),
                                 base_latency_s=LAT))
growths = [TrafficModel.honda_default(f"g{g:.2f}", R=3.5, G=float(g))
           for g in np.linspace(1.0, 1.75, 16)]
sweep = run_grid(sweep_twins, growths, slo=slo)     # aggregate mode
met = [s for s in sweep if s.slo_met]
met.sort(key=lambda s: s.grand_total_usd)
print(render_table(table2_rows(met[:8]),
                   f"What-if #5: 4096-scenario cost-lever sweep — "
                   f"cheapest 8 of {len(met)} SLO-met scenarios"))
print(f"{len(sweep)} scenarios, {len(met)} meet the 4h/95% SLO; the "
      f"whole sweep held {len(growths)} load rows and O(N) aggregates — "
      f"no per-scenario hourly series were ever materialized.")

# ---------------------------------------------------------------------------
# Scaling the grid past this sweep — the same ``run_grid`` call, bigger N.
# Three levers (all bit-identical to the defaults; see the "Scaling the
# grid" section of ``simulate_grid``'s docstring and ``make
# grid-bench-shard``):
#
#  * do nothing: grids past ``agg_auto_block(t_bins)`` scenarios stream
#    through the device automatically in policy-uniform blocks sized so
#    one block's [B, T] staging panel fits a ~150 MB budget, with the
#    host's histogram binning overlapped against the device's next block
#    scan. A 1,048,576-scenario full-year sweep completes on a laptop
#    -class CPU this way (BENCH_grid_shard.json records it).
#  * ``scenario_block=``: override the block size when device memory is
#    tighter (or roomier) than the default budget assumes.
#  * ``devices=D``: shard the blocked grid over a 1-D scenario mesh —
#    one block per device per round, load matrix replicated. On real
#    accelerators each device is one shard; to try it on CPU, export
#      XLA_FLAGS=--xla_force_host_platform_device_count=4
#    BEFORE the first jax import and pass ``devices=4``. Results are
#    bit-identical to devices=None.
#
# e.g.:  run_grid(sweep_twins, growths, slo=slo,
#                 scenario_block=4096, devices=4)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# What-if #6: INVERT the simulator — "what is the cheapest autoscaler
# configuration that keeps p95 latency under 2 hours at +40% traffic?"
# ``whatif.optimize_scenario`` (repro.search) descends a differentiable
# annual-cost objective with a smooth SLO hinge: all restarts run as
# lanes of ONE grad-of-scan dispatch through the same backend selection
# the grids use, every candidate is re-checked through the bit-exact
# streaming-aggregate path, and the p95 evidence comes off the
# aggregate histogram CDF (the new Table II tail columns above).
# ---------------------------------------------------------------------------
from repro.core.twin import make_twin  # noqa: E402
from repro.core.whatif import optimize_scenario  # noqa: E402
from repro.search import pareto_frontier  # noqa: E402

surge = TrafficModel.honda_default("surge(+40%)", R=3.5, G=1.4)
p95_slo = SLO(limit_s=2 * 3600, met_fraction=0.95)
auto_base = make_twin("autoscale-base", "autoscale", max_rps=RPS,
                      usd_per_hour=USD_HR, base_latency_s=LAT,
                      max_instances=8, scale_up_hours=2)
opt = optimize_scenario(auto_base, [surge], p95_slo,
                        search=("max_instances", "scale_up_hours"),
                        restarts=6, steps=60, coarsen=4, seed=0)
print(render_table(opt.restart_table(),
                   "What-if #6: cheapest autoscale config, p95 < 2h at "
                   "+40% traffic (per-restart convergence)"))
print(f"cheapest feasible config: {opt.config()} — "
      f"${opt.cost_usd:,.2f}/yr vs ${opt.base_cost_usd:,.2f} for the "
      f"base config (p95 = {opt.p95_latency_s:.2f}s, SLO-checked "
      f"through the bit-exact aggregate path)")

# ...and the price of tightening that SLO: a cost-vs-p95 Pareto sweep,
# every target another lane of the same single search dispatch
frontier = pareto_frontier(opt.space, [surge],
                           slo_limits=[1800, 3600, 2 * 3600, 8 * 3600],
                           restarts=4, steps=60, coarsen=4, seed=0)
print(render_table(frontier.rows(),
                   "What-if #6b: the price of tightening the p95 SLO"))

# ---------------------------------------------------------------------------
# What-if #7: CHAOS. "What if the pipeline loses capacity for hours at a
# time — and what is the cheapest configuration that still meets the SLO
# in 95% of those fault futures?" A ``repro.faults`` schedule (outages,
# device disconnects with reconnect floods, brownouts) crosses every
# grid scenario with F sampled futures: ``run_grid(faults=...)`` shows
# the damage with fault-attribution columns (hours in fault windows,
# SLO-met split inside vs outside), and
# ``optimize_scenario(faults=..., quantile=0.95)`` answers the inverse —
# the CHANCE-CONSTRAINED resilience search. quantile=1.0 insures every
# sampled future (worst case); 0.95 buys the config that sacrifices the
# rarest, most expensive futures, and is strictly cheaper whenever
# insuring them costs real capacity. Feasibility and the achieved
# quantile are re-checked through the bit-exact aggregate path.
# ---------------------------------------------------------------------------
from repro import faults  # noqa: E402

chaos = faults.FaultSchedule(
    specs=(faults.outage(rate_per_year=6, duration_hours=(1, 4)),
           faults.disconnect(rate_per_year=12, disconnect_frac=(0.2, 0.5),
                             flood_hours=1.0),
           faults.brownout(rate_per_year=8, capacity_mult=(0.3, 0.7))),
    n_futures=4, seed=0)
chaos_sims = run_grid(twins[:2], [nominal], slo=slo, faults=chaos)
print(render_table(table2_rows(chaos_sims),
                   "What-if #7: chaos suite — 4 fault futures per "
                   "scenario (fault-attribution columns)"))

resilient = optimize_scenario(auto_base, [surge], p95_slo,
                              search=("max_instances", "scale_up_hours"),
                              faults=chaos, quantile=0.95,
                              restarts=4, steps=60, coarsen=4, seed=0)
print(f"chance-constrained (q=0.95): {resilient.config()} — "
      f"${resilient.cost_usd:,.2f}/yr, meets the SLO in "
      f"{resilient.achieved_quantile:.0%} of {resilient.n_futures} fault "
      f"futures (vs ${opt.cost_usd:,.2f}/yr benign-optimal)")
