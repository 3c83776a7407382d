"""Multi-start projected-AdamW policy search, one dispatch per search.

``search`` inverts the what-if simulator for one policy: instead of
enumerating configurations and eyeballing the Table II grid, it descends
the differentiable annual-cost-plus-SLO-hinge objective
(``repro.search.objective``) over a declarative ``SearchSpace`` and
returns the cheapest configuration that *provably* meets the SLO — every
candidate is re-checked through the bit-exact streaming-aggregate grid
path before any number is reported.

The optimizer is structured exactly like twin calibration's multi-start
fit (``repro.calibrate.fit._fit_kernel``): all K restarts x S traffic
scenarios run as K*S *lanes* of the shared scenario-grid backend, and the
jitted ``_search_kernel`` scans

    steps  of  grad(lane-block objective)  +  vmap(AdamW)  +  z-clip

so a whole search is ONE device program — no Python loop over restarts,
ever. ``policy_index`` (and the SLO target, penalty weights, boxes and
ties) are traced operands, so one compiled kernel serves every policy of
a tournament at equal shapes; the z-space sigmoid/softplus
reparameterization (reused from ``calibrate``) is the projection of the
"projected" AdamW, plus a +-Z_CLIP clamp that keeps restarts out of the
sigmoid's dead zones.

``search_policies`` is the cross-policy tournament: every requested
policy's search in one call, ranked into a leaderboard (feasible first,
then by exact annual cost).

Feasibility failures are never silent: a search whose candidates all
miss the SLO warns with the policy, the achieved vs required compliance,
and any parameters pinned against their search box — the actionable
third of diagnosing "the SLO is simply unreachable in this box".
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import OptimizerConfig
from repro.core.simulate import GridSummary, simulate_grid
from repro.core.slo import SLO
from repro.core.traffic import TrafficModel
from repro.core.twin import (AGG_SLO_DROP_RATE, AGG_SLO_LATENCY, PARAM_DIM,
                             Twin, registry_version)
from repro.calibrate.objective import params_from_z
from repro.optim.adamw import adamw_update, init_opt_state
from repro.search.objective import (CHANCE_W, HINGE_S, annual_scale,
                                    lane_objective_t,
                                    lane_objective_vectorized)
from repro.search.space import (Z_CLIP, SearchSpace, apply_ties,
                                default_space, search_space)

#: AdamW settings for the z-space search: no decay (z=0 is mid-box, not a
#: prior), generous clip, short warmup; total_steps is overwritten with
#: the search's step count so the cosine tail anneals the final approach
#: to the SLO boundary.
DEFAULT_SEARCH_OPT = OptimizerConfig(lr=0.12, betas=(0.9, 0.95), eps=1e-8,
                                     weight_decay=0.0, grad_clip=10.0,
                                     warmup_steps=10, total_steps=200)

#: dollars of penalty per unit of hinged SLO shortfall, in multiples of
#: the base configuration's annual cost: a 1% compliance shortfall costs
#: one full base-year of spend, so feasibility dominates until met
DEFAULT_PENALTY_WEIGHT = 100.0

#: stand-in SLO operands when no SLO constrains the search (sigmoid
#: compliance saturates at 1, the hinge at met_fraction=0 is exactly 0)
_NO_SLO_LIMIT = 1e30


class SearchInfeasibleWarning(UserWarning):
    """No candidate configuration met the SLO (details in the message)."""


#: lane-bins (global K*S*F*T) above which the search kernel streams its
#: objective reductions through the scan carry instead of materializing
#: the [L, T] series. Below the threshold the vectorized hinge is faster
#: (the streamed fold pays per-bin sigmoid/softplus inside a sequential
#: scan, replayed by the checkpointed backward); above it the [L, T]
#: residuals dominate live memory and the streamed path wins wall clock
#: AND peak temp bytes (BENCH_search.json "stream" rows). Sized so the
#: multi-start bench (8 x 2184) vectorizes and the chance-constrained
#: frontier (1024 x 8736) streams.
_STREAM_MIN_ELEMS = 1 << 21


def _search_kernel_body(steps: int, n_scen: int, n_fut: int,
                        dt_hours: float, slo_mode: int, surrogate: bool,
                        version: int, ocfg: OptimizerConfig, stream: bool,
                        z0, loads_t, scen_w, lo, hi, log_mask, free_mask,
                        fixed, tie_src, tie_coeff, policy_index,
                        slo_limit_k, met_fraction, penalty_weight,
                        penalty_scale, horizon_scale, caps_t=None,
                        quantile=1.0):
    """K restarts x S scenarios (x F fault futures), one dispatch.

    z0 [K, PARAM_DIM]; loads_t [T, S*F] scenario-MINOR (columns
    scenario-major / future-minor) — with ``stream=True`` the whole
    gradient path stays scenario-minor so no [L, T] array ever exists in
    the jaxpr, forward or backward (the streamed ``lane_objective_t``
    folds its reductions into the scan carry); ``stream=False`` takes
    ``lane_objective_vectorized``'s materialized fast path, which wins
    below ``_STREAM_MIN_ELEMS`` lane-bins. The caller decides ``stream``
    from GLOBAL problem size (``_run_kernel``), never from local shapes,
    so sharded and unsharded dispatches always pick the same path and
    ``devices=D`` stays bit-identical to unsharded.
    scen_w [S] (normalized); slo_limit_k [K]
    per-restart SLO limits (a plain search broadcasts one limit; the
    Pareto frontier packs its whole target vector here). ``steps``/
    ``n_scen``/``n_fut``/``dt_hours``/``slo_mode``/``ocfg`` are static;
    ``version`` is the policy-registry version so late registrations
    retrace (same contract as the grid and fit kernels). Everything else
    — including ``policy_index`` and the box/tie arrays — is traced, so
    one compile serves a whole tournament at equal shapes.

    ``n_fut == 1`` (no faults) keeps the pre-chaos objective exactly:
    per-restart scenario-weighted sum of the per-lane cost+hinge. With
    ``n_fut > 1`` (``caps_t`` [T, S*F] riding along) the objective turns
    chance-constrained: expected cost over futures plus a penalty on the
    smoothed probability of meeting the SLO falling below ``quantile`` —
    each future votes sigmoid((frac - met)/CHANCE_W), the per-scenario
    mean of the votes is the smooth chance, its shortfall below the
    target quantile is hinged exactly like the plain path's met-fraction
    shortfall (plus the same small gated violation-depth term so deeply
    infeasible futures still pull).

    Returns (z_fin [K, D], params_fin [K, D], objective [K],
    cost_ann [K, S*F], met_frac [K, S*F], history [steps, K]); the aux
    triple rides the optimizer scan's carry from the LAST gradient
    evaluation (z at step ``steps - 1``) — diagnostics only, which saves
    the full-horizon forward the kernel used to re-dispatch at the end.
    """
    k = z0.shape[0]
    n_lanes = n_scen * n_fut
    loads_t_block = jnp.tile(loads_t, (1, k))
    caps_t_block = None if caps_t is None else jnp.tile(caps_t, (1, k))
    slo_lane = jnp.repeat(slo_limit_k, n_lanes)

    def params_of(z):
        p = jax.vmap(lambda zz: params_from_z(zz, lo, hi, log_mask,
                                              free_mask, fixed))(z)
        return jax.vmap(lambda row: apply_ties(row, tie_src, tie_coeff))(p)

    def objective(z):
        p = params_of(z)
        pb = jnp.repeat(p, n_lanes, axis=0)
        if stream:
            per_lane, (cost_ann, frac) = lane_objective_t(
                pb, loads_t_block, dt_hours, policy_index, slo_lane,
                slo_mode, met_fraction, penalty_weight, penalty_scale,
                horizon_scale, surrogate=surrogate,
                caps_t_block=caps_t_block)
        else:
            per_lane, (cost_ann, frac) = lane_objective_vectorized(
                pb, loads_t_block.T, dt_hours, policy_index, slo_lane,
                slo_mode, met_fraction, penalty_weight, penalty_scale,
                horizon_scale, surrogate=surrogate,
                caps_block=(None if caps_t_block is None
                            else caps_t_block.T))
        if n_fut == 1:
            per_restart = (per_lane.reshape(k, n_scen) * scen_w) \
                .sum(axis=1)
        else:
            cost_sf = cost_ann.reshape(k, n_scen, n_fut)
            frac_sf = frac.reshape(k, n_scen, n_fut)
            exp_cost = (cost_sf.mean(axis=2) * scen_w).sum(axis=1)
            chance = jax.nn.sigmoid((frac_sf - met_fraction)
                                    / CHANCE_W).mean(axis=2)
            # a future sitting exactly ON the met boundary votes 0.5, so
            # the reachable smooth chance tops out half a vote short of
            # the exact count — aim the hinge at that grid (a quantile
            # of 1.0 over F futures means "the worst future at the
            # boundary", i.e. chance ~ 1 - 0.5/F, not 1.0)
            q_eff = quantile - 0.5 / n_fut
            short = jax.nn.softplus((q_eff - chance) / HINGE_S) * HINGE_S
            # chance carries NO usable gradient once futures are deeply
            # infeasible (every per-bin compliance sigmoid saturates, so
            # autodiff sees only the cost slope and dives for the
            # cheapest corner) — the rescue slope is the per-lane
            # penalty lane_objective already computed, whose violation-
            # magnitude softplus stays LINEAR in the violation depth.
            # Gate its future-mean by the chance shortfall: full
            # restoring force while the quantile is missed, released
            # the moment it is met so the allowed (1 - quantile) worst
            # futures stop pulling capacity up at the boundary.
            pen_lane = (per_lane - cost_ann).reshape(k, n_scen, n_fut)
            gate = jax.nn.sigmoid((q_eff - chance) / HINGE_S)
            pen = (penalty_weight * penalty_scale * short
                   + gate * pen_lane.mean(axis=2))
            per_restart = exp_cost + (pen * scen_w).sum(axis=1)
        return per_restart.sum(), (per_restart,
                                   cost_ann.reshape(k, n_lanes),
                                   frac.reshape(k, n_lanes))

    vgrad = jax.value_and_grad(objective, has_aux=True)
    opt0 = jax.vmap(lambda z: init_opt_state({"z": z}, ocfg))(z0)
    aux0 = (jnp.zeros((k,), jnp.float32),
            jnp.zeros((k, n_lanes), jnp.float32),
            jnp.zeros((k, n_lanes), jnp.float32))

    def one_step(carry, _):
        z, opt, _ = carry
        (_, aux), g = vgrad(z)

        def upd(zk, gk, ok):
            new_p, new_o = adamw_update({"z": zk}, {"z": gk}, ok, ocfg)
            # the projection: stay on the live part of the bijection
            return jnp.clip(new_p["z"], -Z_CLIP, Z_CLIP), new_o

        z2, opt2 = jax.vmap(upd)(z, g, opt)
        # carry the aux out instead of re-running a full-horizon forward
        # on z_fin after the scan — these are diagnostics, one AdamW step
        # behind z_fin, and the exact re-check re-scores the candidates
        # anyway
        return (z2, opt2, aux), aux[0]

    (z_fin, _, (per_restart, cost_ann, frac)), history = jax.lax.scan(
        one_step, (z0, opt0, aux0), None, length=steps)
    return (z_fin, params_of(z_fin), per_restart, cost_ann, frac, history)


_search_kernel = functools.partial(
    jax.jit,
    static_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))(_search_kernel_body)


@functools.lru_cache(maxsize=16)
def _sharded_search_fn(devices: int, steps: int, n_scen: int, n_fut: int,
                       dt_hours: float, slo_mode: int, surrogate: bool,
                       version: int, ocfg: OptimizerConfig, stream: bool,
                       has_caps: bool):
    """Build (and cache) the jitted ``shard_map`` search kernel for a
    ``devices``-wide 1-D restart mesh: z0 and slo_limit_k shard over
    their restart axis, every other operand is replicated, and each
    device runs ``_search_kernel_body`` on its K/D restarts. Restarts
    are completely independent in the kernel (per-restart reductions,
    vmapped AdamW; the grad-convenience ``per_restart.sum()`` splits
    exactly), so the sharded run is bit-identical to ``devices=None`` —
    the mesh only divides wall clock and per-device live memory."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("restart",))
    shard, rep = P("restart"), P()

    def body(z0, loads_t, scen_w, lo, hi, log_mask, free_mask, fixed,
             tie_src, tie_coeff, policy_index, slo_limit_k, met_fraction,
             penalty_weight, penalty_scale, horizon_scale, caps_t,
             quantile):
        return _search_kernel_body(
            steps, n_scen, n_fut, dt_hours, slo_mode, surrogate, version,
            ocfg, stream,
            z0, loads_t, scen_w, lo, hi, log_mask, free_mask, fixed,
            tie_src, tie_coeff, policy_index, slo_limit_k, met_fraction,
            penalty_weight, penalty_scale, horizon_scale,
            caps_t if has_caps else None, quantile)

    # shard_map wants a spec per operand, so the benign path threads a
    # [T, 0] caps placeholder — one body signature serves both modes
    in_specs = (shard,) + (rep,) * 10 + (shard,) + (rep,) * 6
    out_specs = (shard, shard, shard, shard, shard, P(None, "restart"))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@dataclass
class SearchResult:
    """Cheapest SLO-feasible configuration plus the evidence trail."""
    policy: str
    space: SearchSpace
    twin: Twin                     # best candidate (feasible when any is)
    cost_usd: float                # exact annual cost of ``twin``
    feasible: bool                 # SLO met on EVERY traffic scenario
    scenario_rows: List[GridSummary]      # twin's bit-exact rows, per scen
    base_cost_usd: float
    base_feasible: bool
    best_restart: int
    restart_params: np.ndarray     # [K, PARAM_DIM]
    restart_costs: np.ndarray      # [K] exact annual cost per restart
    restart_feasible: np.ndarray   # [K] bool
    restart_pct: np.ndarray        # [K] worst-scenario exact SLO pct
    history: np.ndarray            # [steps, K] smooth objective
    slo: Optional[SLO] = None
    # chance-constrained runs (search(faults=..., quantile=q)) only:
    # the target quantile and the winner's exact empirical quantile —
    # worst-scenario fraction of fault futures meeting the SLO on the
    # bit-exact aggregate re-check. Benign searches report 1.0 / 1.0.
    quantile: float = 1.0
    achieved_quantile: float = 1.0
    n_futures: int = 1

    @property
    def saving_vs_base(self) -> float:
        """Annual dollars saved against the base configuration."""
        return self.base_cost_usd - self.cost_usd

    @property
    def p95_latency_s(self) -> float:
        """Worst-scenario p95 latency of the chosen configuration (off
        the bit-exact aggregate histogram — the p-latency SLO evidence)."""
        return max((r.p95_latency_s for r in self.scenario_rows),
                   default=0.0)

    @property
    def p99_latency_s(self) -> float:
        return max((r.p99_latency_s for r in self.scenario_rows),
                   default=0.0)

    def config(self) -> Dict[str, float]:
        """The searched parameters of the winning configuration."""
        return {n: float(self.twin.param(n))
                for n in self.space.free_names}

    def restart_table(self) -> List[Dict]:
        rows = []
        for i in range(len(self.restart_costs)):
            row = {"restart": i,
                   "cost_usd": round(float(self.restart_costs[i]), 2),
                   "feasible": bool(self.restart_feasible[i]),
                   "pct_met": round(float(self.restart_pct[i]), 3),
                   "best": i == self.best_restart}
            for j, n in enumerate(self.space.param_names):
                if self.space.free_mask[j]:
                    row[n] = round(float(self.restart_params[i, j]), 5)
            rows.append(row)
        return rows

    def leaderboard_row(self) -> Dict:
        row = {"policy": self.policy,
               "feasible": self.feasible,
               "cost_usd": round(self.cost_usd, 2),
               "saving_vs_base": round(self.saving_vs_base, 2),
               "latency_p95_s": round(self.p95_latency_s, 2),
               "config": ", ".join(f"{k}={v:g}"
                                   for k, v in self.config().items())}
        return row


def _norm_weights(scenario_weights, n_scen: int) -> np.ndarray:
    w = np.asarray(scenario_weights if scenario_weights is not None
                   else np.full(n_scen, 1.0 / n_scen), np.float32)
    if w.shape != (n_scen,):
        raise ValueError(f"scenario_weights has shape {w.shape} for "
                         f"{n_scen} traffic scenarios — one weight per "
                         f"scenario")
    return w / max(w.sum(), 1e-12)


def _run_kernel(space: SearchSpace, g_loads: np.ndarray, g_bin: float,
                scen_w: np.ndarray, z0: np.ndarray, slo_limit_k: np.ndarray,
                slo_mode: int, met: float, penalty_weight: float,
                penalty_scale: float, g_horizon: float, steps: int,
                ocfg: OptimizerConfig, *, caps: Optional[np.ndarray] = None,
                n_fut: int = 1, quantile: float = 1.0,
                devices: Optional[int] = None):
    """Marshal one ``_search_kernel`` dispatch for a space and return
    ([K, PARAM_DIM] finite candidate vectors, [steps, K] history) —
    diverged restarts fall back to the base configuration's vector.
    Shared by ``search`` (one SLO limit broadcast over K) and
    ``pareto_frontier`` (M*K lane-packed limits). The keyword-only fault
    operands (``caps`` [S*F, T] + ``n_fut``/``quantile``) switch the
    kernel to its chance-constrained objective; ``g_loads`` then has
    S*F rows, scenario-major / future-minor. ``devices=D`` shards the
    restart axis over a D-device mesh (``_sharded_search_fn``),
    bit-identical to unsharded; a restart count that doesn't divide D
    falls back with the shared warn-once replication RuntimeWarning.

    The kernel's ``stream`` static (fold reductions into the scan carry
    vs materialize the [L, T] series) is decided HERE, from the global
    K*S*F*T lane-bin count against ``_STREAM_MIN_ELEMS`` — never from
    per-device shapes — so a sharded dispatch and its unsharded twin
    always run the same objective path and stay bit-identical."""
    from repro.distributed.sharding import resolve_mesh_axis
    stream = (z0.shape[0] * g_loads.shape[0] * g_loads.shape[1]
              >= _STREAM_MIN_ELEMS)
    statics = (int(steps), g_loads.shape[0] // int(n_fut), int(n_fut),
               float(g_bin), int(slo_mode), bool(space.needs_surrogate),
               registry_version(), ocfg, stream)
    loads_t = jnp.asarray(np.ascontiguousarray(g_loads.T))
    caps_t = (None if caps is None
              else jnp.asarray(np.ascontiguousarray(caps.T), jnp.float32))
    operands = (jnp.asarray(z0), loads_t, jnp.asarray(scen_w),
                jnp.asarray(space.lo), jnp.asarray(space.hi),
                jnp.asarray(space.log_mask), jnp.asarray(space.free_mask),
                jnp.asarray(space.fixed), jnp.asarray(space.tie_src),
                jnp.asarray(space.tie_coeff),
                jnp.int32(space.policy_index),
                jnp.asarray(slo_limit_k, jnp.float32), jnp.float32(met),
                jnp.float32(penalty_weight), jnp.float32(penalty_scale),
                jnp.float32(g_horizon))
    d = resolve_mesh_axis(devices, z0.shape[0],
                          "search(devices=) restart mesh")
    obs.count("search.objective_choice",
              stream=stream, policy=space.policy)
    with obs.span("search.kernel", restarts=z0.shape[0],
                  scenarios=g_loads.shape[0] // int(n_fut),
                  futures=int(n_fut), t_bins=g_loads.shape[1],
                  steps=int(steps), stream=stream,
                  devices=int(d or 1), policy=space.policy):
        if d is None:
            (_, p_fin, _, _, _, history) = _search_kernel(
                *statics, *operands, caps_t, jnp.float32(quantile))
        else:
            fn = _sharded_search_fn(d, *statics, caps_t is not None)
            caps_in = (caps_t if caps_t is not None
                       else jnp.zeros((loads_t.shape[0], 0), jnp.float32))
            (_, p_fin, _, _, _, history) = fn(
                *operands, caps_in, jnp.float32(quantile))
        jax.block_until_ready(p_fin)
    p_fin = np.asarray(p_fin, np.float64)
    bad = ~np.isfinite(p_fin).all(axis=1)
    obs.count("search.restarts", z0.shape[0], policy=space.policy)
    if bad.any():
        obs.count("search.restarts.diverged", int(bad.sum()),
                  policy=space.policy)
        p_fin[bad] = space._resolve(space.base.padded_params())
    return p_fin, np.asarray(history, np.float64)


def _as_loads(traffics, loads, bin_hours):
    if (traffics is None) == (loads is None):
        raise ValueError("pass exactly one of traffics= (TrafficModels) "
                         "or loads= [S, T] with bin_hours=")
    if traffics is not None:
        if isinstance(traffics, TrafficModel):
            traffics = [traffics]
        loads_np = np.stack([tr.hourly_loads() for tr in traffics]) \
            .astype(np.float32)
        return loads_np, 1.0, [tr.name for tr in traffics]
    loads_np = np.asarray(loads, np.float32)
    if loads_np.ndim == 1:
        loads_np = loads_np[None]
    if bin_hours is None:
        raise ValueError("raw loads= need bin_hours=")
    return loads_np, float(bin_hours), \
        [f"scenario{i}" for i in range(len(loads_np))]


def achieved_quantile(rows: Sequence[GridSummary], n_scen: int,
                      n_fut: int) -> float:
    """Worst-scenario fraction of fault futures whose exact re-check met
    the SLO — the empirical quantile a chance-constrained candidate
    actually achieves. ``rows`` is one candidate's [S*F] GridSummary
    list, scenario-major / future-minor."""
    met = np.array([bool(r.slo_met) for r in rows], bool) \
        .reshape(n_scen, n_fut)
    return float(met.mean(axis=1).min())


def evaluate_exact(twins: Sequence[Twin], loads_np: np.ndarray,
                   bin_hours: float, slo: Optional[SLO],
                   scen_w: np.ndarray, horizon_scale: float, *,
                   faults=None, quantile: float = 1.0
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              List[List[GridSummary]]]:
    """Bit-exact candidate scoring through the streaming-aggregate grid.

    Every (candidate x scenario) pair runs as one aggregate-mode
    ``simulate_grid`` dispatch; a candidate is feasible only when the SLO
    holds on EVERY scenario (``GridSummary.slo_met`` — the exact counters,
    with the histogram p95/p99 columns riding along as the p-latency
    evidence ``SearchResult`` reports). Returns (annual_cost [C],
    feasible [C], worst_pct [C], rows [C][S]).

    ``faults=`` (keyword-only: a ``repro.faults.SampledFaults`` or
    ``FaultSchedule``) re-checks every candidate across the F fault
    futures instead: rows come back per (candidate, scenario, future) —
    [C][S*F], scenario-major / future-minor — cost becomes the
    scenario-weighted EXPECTED cost over futures, and feasibility
    becomes ``achieved_quantile(rows) >= quantile`` (the SLO must hold
    in at least that fraction of futures on every scenario).
    """
    c, s = len(twins), loads_np.shape[0]
    grid_twins = [tw for tw in twins for _ in range(s)]
    load_index = np.tile(np.arange(s, dtype=np.int32), c)
    names = [f"{tw.name}@s{j}" for tw in twins for j in range(s)]
    rows = simulate_grid(grid_twins, names=names, slo=slo,
                         bin_hours=bin_hours, return_series=False,
                         load_matrix=loads_np, load_index=load_index,
                         faults=faults)
    f = len(rows) // (c * s) if c and s else 1
    per = s * f
    rows_by_cand = [rows[i * per:(i + 1) * per] for i in range(c)]
    w_sf = np.repeat(np.asarray(scen_w, np.float64), f) / f
    cost = np.array([sum(w * r.total_cost_usd
                         for w, r in zip(w_sf, rr)) * horizon_scale
                     for rr in rows_by_cand])
    if slo is None:
        feas = np.ones(c, bool)
        pct = np.full(c, 100.0)
    elif faults is not None and f > 1:
        aq = np.array([achieved_quantile(rr, s, f) for rr in rows_by_cand])
        feas = aq >= float(quantile) - 1e-9
        pct = np.array([min(r.pct_latency_met for r in rr)
                        for rr in rows_by_cand])
    else:
        feas = np.array([all(r.slo_met for r in rr)
                         for rr in rows_by_cand])
        pct = np.array([min(r.pct_latency_met for r in rr)
                        for rr in rows_by_cand])
    return cost, feas, pct, rows_by_cand


def _coarsen(loads_np: np.ndarray, bin_hours: float, factor: int):
    """Sum groups of ``factor`` bins for the gradient loop (the policy
    steps are bin-width aware, so dt simply grows); the exact re-check
    always runs on the original bins."""
    if factor <= 1:
        return loads_np, bin_hours
    t = loads_np.shape[1] // factor * factor
    coarse = loads_np[:, :t].reshape(loads_np.shape[0], -1, factor) \
        .sum(axis=2)
    return np.ascontiguousarray(coarse, np.float32), bin_hours * factor


def _coarsen_caps(caps: np.ndarray, factor: int) -> np.ndarray:
    """Mean-coarsen a capacity-multiplier series (loads SUM per coarse
    bin, multipliers AVERAGE — an outage covering half a coarse bin is a
    50% brownout at that scale). Gradient-guide approximation only; the
    exact re-check replays the original bins."""
    if factor <= 1:
        return caps
    t = caps.shape[1] // factor * factor
    coarse = caps[:, :t].reshape(caps.shape[0], -1, factor).mean(axis=2)
    return np.ascontiguousarray(coarse, np.float32)


def _bounds_diagnosis(space: SearchSpace, params: np.ndarray) -> List[str]:
    """Names of free parameters pinned against their search box (within
    0.5% of an edge, measured in the parameter's own fit scale — log for
    log-fitted parameters) — the actionable half of an infeasibility
    report."""
    pinned = []
    for i, n in enumerate(space.param_names):
        if not space.free_mask[i]:
            continue
        lo, hi = float(space.lo[i]), float(space.hi[i])
        if not (np.isfinite(hi) and hi > lo):
            continue
        v = float(params[i])
        if space.log_mask[i] and lo > 0:
            frac = (np.log(max(v, 1e-300)) - np.log(lo)) \
                / max(np.log(hi) - np.log(lo), 1e-12)
        else:
            frac = (v - lo) / (hi - lo)
        if frac <= 0.005:
            pinned.append(f"{n}={v:g} at lower bound {lo:g}")
        elif frac >= 0.995:
            pinned.append(f"{n}={v:g} at upper bound {hi:g}")
    return pinned


def _box_pos(space: SearchSpace, p: np.ndarray) -> np.ndarray:
    """Free coords of ``p`` as normalized positions in their boxes
    (log scale where the space fits the exponent)."""
    u = np.zeros(PARAM_DIM)
    for i in np.where(space.free_mask)[0]:
        lo, hi = float(space.lo[i]), float(space.hi[i])
        if space.log_mask[i] and lo > 0:
            u[i] = (np.log(max(p[i], lo)) - np.log(lo)) \
                / max(np.log(hi) - np.log(lo), 1e-12)
        else:
            u[i] = (p[i] - lo) / max(hi - lo, 1e-12)
    return np.clip(u, 0.0, 1.0)


def _box_params(space: SearchSpace, p_base: np.ndarray,
                u: np.ndarray) -> np.ndarray:
    """Inverse of ``_box_pos``: rebuild a full parameter vector from
    normalized free coords (ties re-applied). Positions 0/1 land on the
    box edges EXACTLY — the one thing the sigmoid reparam cannot do."""
    p = p_base.astype(np.float64).copy()
    for i in np.where(space.free_mask)[0]:
        lo, hi = float(space.lo[i]), float(space.hi[i])
        if space.log_mask[i] and lo > 0:
            p[i] = lo * (hi / lo) ** u[i]
        else:
            p[i] = lo + u[i] * (hi - lo)
    return space._resolve(p)


def _polish_ladder(space: SearchSpace, p_best: np.ndarray,
                   span: float) -> np.ndarray:
    """[C, PARAM_DIM] polish candidates around the incumbent: per free
    coordinate, +-span * (1, 1/2, 1/4, 1/8) steps in normalized box
    position plus the two exact box edges; incumbent first."""
    u0 = _box_pos(space, p_best)
    offs = np.array([span, -span, span / 2, -span / 2,
                     span / 4, -span / 4, span / 8, -span / 8])
    cands = [space._resolve(p_best)]
    for j in np.where(space.free_mask)[0]:
        for target in list(np.clip(u0[j] + offs, 0.0, 1.0)) + [0.0, 1.0]:
            u = u0.copy()
            u[j] = target
            cands.append(_box_params(space, p_best, u))
    return np.stack(cands)


def search(space_or_base: Union[SearchSpace, Twin],
           traffics=None, slo: Optional[SLO] = None, *,
           loads: Optional[np.ndarray] = None,
           bin_hours: Optional[float] = None,
           restarts: int = 8, steps: int = 120, seed: int = 0,
           scenario_weights: Optional[Sequence[float]] = None,
           opt: Optional[OptimizerConfig] = None,
           penalty_weight: float = DEFAULT_PENALTY_WEIGHT,
           met_margin: float = 0.002,
           coarsen: int = 1,
           polish_rounds: int = 3,
           search_params: Optional[Sequence[str]] = None,
           faults=None, quantile: float = 1.0,
           devices: Optional[int] = None) -> SearchResult:
    """Find the cheapest configuration of one policy that meets ``slo``.

    ``space_or_base`` is a ``SearchSpace`` (full control) or a base
    ``Twin`` (the policy's ``default_space`` — or ``search_params`` —
    around it). Traffic comes as ``traffics=`` TrafficModels (hourly
    year rows) or raw ``loads=`` [S, T] with ``bin_hours=``. All K
    ``restarts`` x S scenarios run as one ``_search_kernel`` dispatch;
    ``coarsen`` sums that many bins per gradient-loop step (the exact
    re-check always uses the original bins). ``met_margin`` tightens the
    smooth objective's compliance target slightly so candidates land on
    the feasible side of the boundary the exact re-check draws;
    ``polish_rounds`` batched coordinate-ladder refinements around the
    winner (each one exact aggregate dispatch, span quartering per
    round) then walk it onto that exact boundary — the last fraction of
    a percent no smooth penalty can locate.

    ``faults=`` (a ``repro.faults.FaultSchedule`` or ``SampledFaults``)
    makes the search **chance-constrained**: every (restart x scenario)
    lane fans out over the schedule's F fault futures, the objective
    becomes expected annual cost over futures plus a smooth-quantile
    hinge (see ``_search_kernel``), and a candidate is feasible when the
    bit-exact aggregate re-check meets the SLO in at least ``quantile``
    of the futures on every scenario. ``quantile=1.0`` (the default) is
    the worst-case search — the SLO must hold in EVERY sampled future;
    ``quantile=0.95`` buys the 95%-of-futures configuration, strictly
    cheaper whenever the worst futures are expensive to insure against.
    The result's ``achieved_quantile`` reports the winner's exact
    empirical quantile.

    **Scaling the search.** At scale the gradient loop is a
    streaming-aggregate scan: every reduction the objective needs folds
    into the scan carry as compensated triples (``search.objective``),
    and the checkpointed O(√T) VJP replays √T-bin segments on the
    backward pass — live memory is O(L·√T) for L = restarts × scenarios
    × fault futures lanes, NOT O(L·T), so a chance-constrained
    year-horizon search (K=8 × S=4 × F=32, T=8736) no longer stages
    ~150 MB of series per AdamW step. Small problems (under
    ``_STREAM_MIN_ELEMS`` global lane-bins, where the fold's per-bin
    transcendentals cost more than the series they avoid) keep the
    vectorized materialized objective — the choice is a compile-time
    static made from global sizes, invisible to results.
    ``devices=D`` additionally shards the restart axis over a D-device
    mesh through ``jax.shard_map`` — restarts are
    independent, so results are **bit-identical** to ``devices=None``
    and the mesh only divides wall clock and per-device memory. On a
    multi-core CPU host export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` *before the
    first jax import*; when ``restarts`` doesn't divide D the search
    warns once (RuntimeWarning) and runs unsharded. Tournaments
    (``search_policies(devices=...)``), ``pareto_frontier(devices=...)``
    and ``whatif.optimize_scenario(devices=...)`` forward here.

    **Observing the wind tunnel** (``repro.obs``). With telemetry on
    every gradient-loop dispatch records a ``search.kernel`` span
    (attrs: restarts, scenarios, futures, t_bins, steps, the
    ``stream`` objective choice, devices, policy) and the runtime
    decisions that used to be invisible become counters:
    ``search.objective_choice{stream,policy}`` (streamed fold vs
    vectorized hinge — the ``_STREAM_MIN_ELEMS`` static),
    ``search.restarts`` / ``search.restarts.diverged`` /
    ``search.restarts.feasible`` per policy, and an infeasible search
    additionally bumps ``warn.search_infeasible{policy,pinned}`` so the
    warning stays countable after Python's warn-once dedup silences the
    repeat (the UserWarning still fires). All of it sits outside jitted
    code — enabling telemetry changes no searched number.
    """
    if isinstance(space_or_base, SearchSpace):
        space = space_or_base
    elif search_params is not None:
        space = search_space(space_or_base, search_params)
    else:
        space = default_space(space_or_base)
    loads_np, bin_hours, scen_names = _as_loads(traffics, loads, bin_hours)
    s = loads_np.shape[0]
    scen_w = _norm_weights(scenario_weights, s)
    horizon = annual_scale(loads_np.shape[1], bin_hours)

    sampled = None
    n_fut = 1
    if faults is not None:
        from repro.faults import (FaultSchedule, SampledFaults,
                                  sample_futures)
        if isinstance(faults, FaultSchedule):
            sampled = sample_futures(faults, loads_np.shape[1], bin_hours)
        elif isinstance(faults, SampledFaults):
            sampled = faults
        else:
            raise TypeError(f"faults= must be a repro.faults.FaultSchedule "
                            f"or SampledFaults, got {type(faults).__name__}")
        n_fut = sampled.n_futures
        if not 0.0 < quantile <= 1.0:
            raise ValueError(f"quantile must be in (0, 1], got {quantile}")

    # the base configuration's exact cost anchors the penalty scale and
    # the "what did the search buy us" delta
    base_cost, base_feas, _, _ = evaluate_exact(
        [space.base], loads_np, bin_hours, slo, scen_w, horizon,
        faults=sampled, quantile=quantile)

    if slo is None:
        slo_mode, slo_limit, met = AGG_SLO_LATENCY, _NO_SLO_LIMIT, 0.0
    else:
        slo_mode = (AGG_SLO_DROP_RATE if slo.metric == "drop_rate"
                    else AGG_SLO_LATENCY)
        slo_limit = float(slo.limit_s)
        met = min(float(slo.met_fraction) + met_margin, 1.0)

    if sampled is not None:
        # fan the gradient loop's lanes out over the futures: loads get
        # each scenario's F perturbed rows (reconnect floods baked in),
        # caps ride along as the matching capacity series
        grad_loads = np.stack([sampled.apply_loads(r) for r in loads_np]) \
            .reshape(s * n_fut, -1).astype(np.float32)
        grad_caps = np.tile(np.asarray(sampled.cap, np.float32), (s, 1))
    else:
        grad_loads, grad_caps = loads_np, None

    g_loads, g_bin = _coarsen(grad_loads, bin_hours, int(coarsen))
    g_caps = (None if grad_caps is None
              else _coarsen_caps(grad_caps, int(coarsen)))
    g_horizon = annual_scale(g_loads.shape[1], g_bin)
    ocfg = dataclasses.replace(opt or DEFAULT_SEARCH_OPT, total_steps=steps)
    p_fin, history = _run_kernel(
        space, g_loads, g_bin, scen_w, space.z0(restarts, seed),
        np.full((restarts,), slo_limit), slo_mode, met, penalty_weight,
        max(base_cost[0], 1.0), g_horizon, steps, ocfg,
        caps=g_caps, n_fut=n_fut, quantile=quantile, devices=devices)
    cand_twins = [space.twin(p_fin[i], f"{space.policy}-cand{i}")
                  for i in range(restarts)]
    cost, feas, pct, rows = evaluate_exact(cand_twins, loads_np, bin_hours,
                                           slo, scen_w, horizon,
                                           faults=sampled,
                                           quantile=quantile)
    cost = np.where(np.isfinite(cost), cost, np.inf)
    pct = np.nan_to_num(pct, nan=0.0)
    obs.count("search.restarts.feasible", int(feas.sum()),
              policy=space.policy)

    if feas.any():
        best = int(np.where(feas, cost, np.inf).argmin())
        feasible = True
        # polish: batched coordinate ladders around the winner (including
        # the exact box edges), scored through the SAME exact aggregate
        # path — one dispatch per round, span halving. This walks the
        # config onto the exact SLO boundary the smooth hinge can only
        # approach, and onto box-edge optima the sigmoid reparam can
        # only asymptote toward.
        p_best = p_fin[best].copy()
        best_cost = float(cost[best])
        best_twin, best_rows = cand_twins[best], rows[best]
        span = 0.5
        rounds = int(polish_rounds) if space.num_free else 0
        for _ in range(rounds):
            p_c = _polish_ladder(space, p_best, span)
            twins_c = [space.twin(p_c[i], f"{space.policy}-pol{i}")
                       for i in range(len(p_c))]
            c_c, f_c, _, r_c = evaluate_exact(
                twins_c, loads_np, bin_hours, slo, scen_w, horizon,
                faults=sampled, quantile=quantile)
            c_c = np.where(f_c & np.isfinite(c_c), c_c, np.inf)
            i_c = int(c_c.argmin())
            if c_c[i_c] < best_cost:
                best_cost = float(c_c[i_c])
                best_twin, best_rows = twins_c[i_c], r_c[i_c]
                p_best = p_c[i_c]
            span /= 4.0
        cand_twins = list(cand_twins)
        cand_twins[best] = best_twin
        rows = list(rows)
        rows[best] = best_rows
        cost = cost.copy()
        cost[best] = best_cost
        p_fin[best] = best_twin.padded_params()
    else:
        best = int(pct.argmax())       # closest to compliance
        feasible = False
        desc = (f"{slo.metric} <= {slo.limit_s:g} in "
                f"{slo.met_fraction:.0%} of records" if slo is not None
                else "unconstrained")
        if sampled is not None:
            desc += (f", in >= {quantile:.0%} of {n_fut} fault futures "
                     f"per scenario")
        pins = _bounds_diagnosis(space, p_fin[best])
        obs.event("warn.search_infeasible", policy=space.policy,
                  pinned=bool(pins))
        warnings.warn(
            f"{space.policy} search found NO feasible configuration for "
            f"SLO ({desc}): best candidate reaches "
            f"{pct[best]:.2f}% compliance (needs "
            f"{(slo.met_fraction if slo else 0) * 100:.2f}%)"
            + (f"; pinned against the search box: {'; '.join(pins)} — "
               f"widen bounds= on those parameters or relax the SLO"
               if pins else
               "; no parameter is pinned at its bound — this policy "
               "likely cannot meet the SLO on this traffic at any "
               "configuration in the space"),
            SearchInfeasibleWarning, stacklevel=2)

    aq = 1.0
    if sampled is not None and slo is not None:
        aq = achieved_quantile(rows[best], s, n_fut)
    return SearchResult(
        policy=space.policy, space=space,
        twin=dataclasses.replace(cand_twins[best],
                                 name=f"{space.policy}-opt"),
        cost_usd=float(cost[best]), feasible=feasible,
        scenario_rows=rows[best],
        base_cost_usd=float(base_cost[0]), base_feasible=bool(base_feas[0]),
        best_restart=best, restart_params=p_fin,
        restart_costs=cost, restart_feasible=feas, restart_pct=pct,
        history=np.asarray(history, np.float64), slo=slo,
        quantile=float(quantile) if sampled is not None else 1.0,
        achieved_quantile=float(aq), n_futures=n_fut)


@dataclass
class TournamentResult:
    """Ranked cross-policy search results (feasible first, then cost)."""
    results: List[SearchResult] = field(default_factory=list)

    @property
    def best(self) -> SearchResult:
        return self.ranked()[0]

    def ranked(self) -> List[SearchResult]:
        return sorted(self.results,
                      key=lambda r: (not r.feasible, r.cost_usd))

    def leaderboard_rows(self) -> List[Dict]:
        rows = []
        best_cost = self.best.cost_usd
        for i, r in enumerate(self.ranked()):
            row = {"rank": i + 1}
            row.update(r.leaderboard_row())
            row["vs_winner_usd"] = round(r.cost_usd - best_cost, 2)
            rows.append(row)
        return rows


def search_policies(bases: Sequence[Twin], traffics=None,
                    slo: Optional[SLO] = None, *,
                    search_params: Optional[Dict[str, Sequence[str]]] = None,
                    spaces: Optional[Sequence[SearchSpace]] = None,
                    **kwargs) -> TournamentResult:
    """The cross-policy tournament: one search per base twin (its
    policy's default space, a ``search_params[policy]`` override, or a
    prebuilt entry of ``spaces``), every search one kernel dispatch — and
    all of them ONE compile when shapes agree, since the policy index and
    boxes are traced operands. Returns the ranked leaderboard."""
    if spaces is None:
        spaces = []
        for base in bases:
            override = (search_params or {}).get(base.policy)
            spaces.append(search_space(base, override)
                          if override is not None else default_space(base))
    results = [search(sp, traffics, slo, **kwargs) for sp in spaces]
    return TournamentResult(results=results)
