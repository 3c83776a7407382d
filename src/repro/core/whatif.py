"""What-if scenario engine (paper Sec. VII): run (twin x traffic) grids,
compare retention policies, and render Table II / Table IV style results.

``run_grid`` pairs every (traffic x twin) combination and executes the
whole batch as a single scan dispatch via ``simulate_grid`` — policies may
be mixed freely in one grid. Each traffic's [8736] load row is held ONCE
in a [K, T] load matrix with an [N] index map (never duplicated per twin),
so host memory is O(traffics*T + N), and by default the grid runs in
**streaming-aggregate mode**: the Table II statistics come back as O(N)
``GridSummary`` rows with no [N, T] series ever materialized —
``table2_rows`` only consumes scalars, so 100k+-scenario sweeps of the
Jablonski & Heltweg cost levers (autoscaling delay, overprovisioning,
queue caps) cost O(N) memory. Pass ``return_series=True`` for the full
per-bin ``SimulationResult`` series (plots, ``monthly_table``), and
``scenario_block=`` to stream grids larger than device memory through in
blocks. The scan runs on whichever backend ``core.simulate`` selects: the
XLA vmapped ``lax.switch`` scan (default), or — under
``kernels.ops.pallas_mode()`` — the fused Pallas scenario-grid kernels
with scenarios on the vector lanes.

``calibrated_grid`` closes the paper's loop end to end: it gradient-fits
one twin per requested policy to a measured ``ExperimentResult`` (or a
prebuilt ``ObservedTrace``) via ``repro.calibrate`` and plays the fitted
twins through the Table II grid — measurement in, scenario table out."""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro import obs
from repro.core.cost import CostModel
from repro.core.simulate import (GridSummary, SimulationResult,
                                 monthly_table, simulate_grid, simulate_year)
from repro.core.slo import SLO
from repro.core.traffic import TrafficModel
from repro.core.twin import Twin

#: what grid runners return: per-bin series or streaming-aggregate scalars
GridResult = Union[SimulationResult, GridSummary]


@dataclass(frozen=True)
class Scenario:
    name: str
    twin: Twin
    traffic: TrafficModel


def run_grid(twins: Sequence[Twin], traffics: Sequence[TrafficModel],
             slo: Optional[SLO] = None,
             cost_model: Optional[CostModel] = None,
             record_mb: float = 0.0, *,
             return_series: bool = False,
             scenario_block: Optional[int] = None,
             devices: Optional[int] = None,
             faults=None) -> List[GridResult]:
    """Every (traffic x twin) combination — the paper's Table II grid —
    simulated in one dispatch over the (load matrix, index map) batch.

    Aggregate mode by default (``GridSummary`` rows, O(N) memory end to
    end); ``return_series=True`` restores the full ``SimulationResult``
    series, bit-identical to the pre-streaming engine. ``scenario_block``
    streams huge aggregate grids through the device in policy-uniform
    blocks, and ``devices=D`` shards those blocks over a D-device
    scenario mesh (see ``simulate_grid``'s "Scaling the grid").
    ``faults=`` (a ``repro.faults.FaultSchedule`` or ``SampledFaults``)
    crosses the grid with F fault futures — chaos-suite Table II, rows
    named ``"{traffic} {twin}/f{f}"`` (see ``simulate_grid``'s "Chaos
    suites"); ``table2_rows`` then adds the fault-attribution columns."""
    if not twins or not traffics:
        return []
    with obs.span("whatif.loads", traffics=len(traffics)):
        load_matrix = np.stack([tr.hourly_loads() for tr in traffics])
    load_index = np.repeat(np.arange(len(traffics), dtype=np.int32),
                           len(twins))
    grid_twins = [tw for _ in traffics for tw in twins]
    names = [f"{tr.name} {tw.name}" for tr in traffics for tw in twins]
    return simulate_grid(grid_twins, names=names, slo=slo,
                         cost_model=cost_model, record_mb=record_mb,
                         return_series=return_series,
                         load_matrix=load_matrix, load_index=load_index,
                         scenario_block=scenario_block, devices=devices,
                         faults=faults)


def calibrated_grid(source, policies: Sequence[str],
                    traffics: Sequence[TrafficModel],
                    slo: Optional[SLO] = None,
                    cost_model: Optional[CostModel] = None,
                    record_mb: float = 0.0,
                    bin_s: float = 1.0,
                    **fit_kwargs) -> List[GridResult]:
    """Measured pipeline -> fitted twins -> Table II grid, in one call.

    ``source`` is an ``ExperimentResult`` or an
    ``repro.calibrate.ObservedTrace``; one twin is calibrated per entry of
    ``policies`` (extra kwargs forward to ``repro.calibrate.fit`` —
    ``devices=D`` shards each fit's restarts over a device mesh), then
    the whole (traffic x fitted twin) grid runs as a single vmapped scan.
    """
    from repro.calibrate import calibrated_twin   # late: calibrate sits
    twins = [calibrated_twin(source, policy, bin_s=bin_s,  # above core
                             name=f"{policy}-cal", **fit_kwargs)
             for policy in policies]
    return run_grid(twins, traffics, slo=slo, cost_model=cost_model,
                    record_mb=record_mb)


def optimize_scenario(base: Twin, traffics, slo: Optional[SLO] = None,
                      *, search: Optional[Sequence[str]] = None,
                      bounds: Optional[Dict] = None,
                      tie: Optional[Dict] = None,
                      **search_kwargs):
    """The inverse of ``run_grid``: cheapest configuration, not a table.

    Searches ``base``'s policy for the cheapest parameter setting that
    meets ``slo`` on every traffic scenario — gradient descent on the
    smooth annual-cost objective (``repro.search``), all restarts x
    scenarios as one vmapped grad-of-scan dispatch, feasibility
    re-checked through the bit-exact streaming-aggregate grid. ``search``
    names the free parameters (default: the policy's extras, or priced
    capacity for extra-less policies); ``bounds``/``tie`` refine the
    space; remaining kwargs forward to ``repro.search.search`` (restarts,
    steps, coarsen, ..., and ``devices=D`` to shard the restart axis over
    a device mesh — see "Scaling the search" there). Returns a
    ``repro.search.SearchResult`` whose ``.twin`` drops straight into
    ``run_grid`` / ``table2_rows``.

    Pass ``faults=`` (a ``repro.faults.FaultSchedule``) and
    ``quantile=`` for the chance-constrained resilience variant: the
    cheapest configuration meeting ``slo`` in at least that fraction of
    the schedule's fault futures on every traffic scenario, with the
    achieved empirical quantile re-checked bit-exactly
    (``SearchResult.achieved_quantile``).
    """
    from repro.search import search as _search          # late: search
    from repro.search import search_space               # sits above core
    space = search_space(base, search, bounds=bounds, tie=tie)
    return _search(space, traffics, slo, **search_kwargs)


def run_scenarios(scenarios: Sequence[Scenario],
                  slo: Optional[SLO] = None,
                  cost_model: Optional[CostModel] = None,
                  record_mb: float = 0.0, *,
                  return_series: bool = False,
                  scenario_block: Optional[int] = None,
                  devices: Optional[int] = None) -> List[GridResult]:
    """Arbitrary named (twin, traffic) pairs, batched like ``run_grid``
    (aggregate mode by default; each scenario brings its own traffic, so
    the load matrix deduplicates repeated traffic objects only).
    ``scenario_block`` / ``devices`` stream and shard exactly as in
    ``run_grid``."""
    if not scenarios:
        return []
    row_of: Dict[int, int] = {}
    rows: List[np.ndarray] = []
    load_index = np.empty(len(scenarios), np.int32)
    for i, s in enumerate(scenarios):
        key = id(s.traffic)
        if key not in row_of:
            row_of[key] = len(rows)
            rows.append(s.traffic.hourly_loads())
        load_index[i] = row_of[key]
    return simulate_grid([s.twin for s in scenarios],
                         names=[s.name for s in scenarios], slo=slo,
                         cost_model=cost_model, record_mb=record_mb,
                         return_series=return_series,
                         load_matrix=np.stack(rows), load_index=load_index,
                         scenario_block=scenario_block, devices=devices)


def table2_rows(sims: Sequence[GridResult]) -> List[Dict]:
    with obs.span("whatif.table2", n=len(sims)):
        # chaos-suite grids (any row simulated through fault windows) grow
        # three attribution columns; benign tables keep the seed's exact
        # column set
        fault_cols = any(getattr(s, "fault_hours", 0.0) > 0.0 for s in sims)
        rows = []
        for s in sims:
            row = {
                "run": s.name,
                "policy": s.twin.policy,
                "cost_usd": round(s.total_cost_usd, 2),
                "latency_median_s": round(s.median_latency_s, 2),
                "latency_p95_s": round(s.p95_latency_s, 2),
                "latency_p99_s": round(s.p99_latency_s, 2),
                "latency_mean_s": round(s.mean_latency_s, 2),
                "latency_backlog_s": round(s.backlog_s, 2),
                "thruput_mean_rph": round(s.mean_throughput_rph, 2),
                "thruput_max_rph": round(s.max_throughput_rph, 2),
                "dropped": round(s.dropped_records, 1),
                "pct_latency_met": round(s.pct_latency_met, 2),
                "slo_met": s.slo_met,
            }
            if fault_cols:
                row["fault_hours"] = round(getattr(s, "fault_hours", 0.0), 1)
                row["pct_hours_met_in_fault"] = round(
                    getattr(s, "pct_hours_met_in_fault", 100.0), 2)
                row["pct_hours_met_outside_fault"] = round(
                    getattr(s, "pct_hours_met_outside_fault", 100.0), 2)
            rows.append(row)
        return rows


def retention_whatif(twin: Twin, traffic: TrafficModel, record_mb: float,
                     retentions_days: Sequence[int] = (91, 182),
                     cost_model: Optional[CostModel] = None,
                     slo: Optional[SLO] = None) -> Dict[int, List[Dict]]:
    """The paper's 3-month vs 6-month retention comparison (Table IV)."""
    cm = cost_model or CostModel()
    loads = traffic.hourly_loads()
    out = {}
    for ret in retentions_days:
        cmr = replace(cm, retention_days=ret)
        sim = simulate_year(twin, loads, slo=slo, cost_model=cmr,
                            record_mb=record_mb,
                            name=f"{traffic.name} {twin.name} ret{ret}")
        out[ret] = monthly_table(sim, cmr, record_mb)
    return out
