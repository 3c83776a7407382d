"""Year-long pipeline simulation (paper Sec. V-G / Tables II & IV) on the
unified TwinPolicy engine.

``simulate_grid`` plays hourly load projections through digital twins: the
whole batch of (twin x traffic) scenarios is stacked into [N, H] load and
[N, PARAM_DIM] parameter arrays and executed as ONE ``jax.vmap`` over a
jitted ``jax.lax.scan`` of the 8736 hours. Each hour step dispatches to the
twin's registered policy with ``jax.lax.switch`` (see core/twin.py), so a
grid mixing fifo / quickscale / autoscale / shed / batch_window twins is a
single device dispatch — "no synthetic data is actually processed; only the
load shape is used, so the simulation is quite fast" (paper); here a full
64-scenario grid simulates in about the time the seed took for one.

``simulate_year`` is the batch-of-one convenience wrapper and keeps the
seed's exact semantics: legacy SimpleTwin/QuickscalingTwin results are
numerically identical to the old hard-coded scan.

The scan is generalized to arbitrary horizon and bin width: policy steps
take the bin width ``dt`` (hours), so the same kernel that plays 8736
one-hour bins for the year tables also replays a sub-hour calibration
trace (``repro.calibrate``). ``scan_trace`` is the unbatched, *unjitted*
core — differentiable w.r.t. the parameter vector, which is what twin
calibration differentiates through. The year path pins dt=1.0 (a static
jit arg) and stays bit-identical to the PR 1 kernel.

The grid runs on either of two interchangeable backends, selected by
``_grid_scan`` through the ``kernels.ops`` Pallas switch:

* **XLA** (default) — ``_grid_scan_xla``: vmap over per-scenario scans of
  the scalar ``lax.switch`` policy step. The parity anchor; hourly
  full-year results are bit-identical to the pre-Pallas kernel.
* **Pallas** (``kernels.ops.use_pallas(True)`` or the ``pallas_mode()``
  context) — the fused scenario-grid kernel of
  ``kernels/policy_scan.py``: one ``pallas_call`` scans all T bins for
  LANES scenarios at a time using the branchless lane-vectorized policy
  steps (``core.twin.lane_policy_step``), scenarios on the vector lanes,
  Mosaic-compiled on a TPU, interpreted on the CPU. Grids and K-restart
  calibration fits (restarts are just more lanes) both route through
  this selection.

Each backend additionally exists in a **streaming-aggregate** variant
(``simulate_grid(return_series=False)`` -> ``_grid_scan_agg``): the
Table II statistics — twice-compensated running sums, per-bin max,
end-of-scan queue, SLO-ok counters and a quarter-octave load-weighted
latency histogram (``core.twin`` AGG_* hooks) — come back as O(N)
aggregate rows and the five [N, T] series are never returned. Grids
beyond ``AGG_AUTO_BLOCK`` scenarios (or any grid given an explicit
``scenario_block``) stream through the device as ``lax.map`` blocks
gathered from a [K, T] load matrix + [N] index map, so 100k+-scenario
full-year sweeps complete in one call on hardware that could never hold
the series. ``GridSummary`` rows are produced by one vectorized numpy
pass (``_summarise_aggregates``); sums/max/queue/SLO percentages match
the series path's ``_summarise`` bit for bit, the histogram median to
one bucket width. ``whatif.run_grid`` uses this mode by default.

End-of-year backlog is priced the paper's way: queue_length / capacity
hours of extra pipeline time at the twin's hourly rate ("the cost of, for
example, spinning up duplicate pipelines to process the backlog"). Policies
with a bounded queue additionally report a ``dropped`` hourly series
(records shed), which SLOs can target via ``metric="drop_rate"``.

``storage_costs`` runs the daily rolling-retention accumulation (Table IV):
data builds up day by day and ages out after the retention window.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.cost import CostModel
from repro.core.slo import SLO
from repro.core.traffic import DAYS_PER_YEAR, HOURS_PER_YEAR, MONTH_DAYS

from repro.core.twin import (A_COST, A_DROP, A_FLTH, A_FOKH, A_LATW, A_LOAD,
                             A_MAXP, A_OKH, A_OKW, A_PROC, AGG_DIM,
                             AGG_HIST_BINS, AGG_KDIM, AGG_SCALARS,
                             AGG_SLO_DROP_RATE, AGG_SLO_LATENCY, CARRY_DIM,
                             Twin, aggregate_hist_centers,
                             device_latency_histogram,
                             finalize_aggregate_x64, init_agg_scalars,
                             pack_agg_scalars, policy_branches,
                             registry_version, update_agg_scalars)


@dataclass
class SimulationResult:
    name: str
    twin: Twin
    # hourly arrays [8736]
    load: np.ndarray
    processed: np.ndarray
    queue: np.ndarray
    latency_s: np.ndarray
    cost_usd: np.ndarray
    # scalars
    total_cost_usd: float
    backlog_s: float
    backlog_cost_usd: float
    mean_throughput_rph: float
    max_throughput_rph: float
    median_latency_s: float
    mean_latency_s: float
    pct_latency_met: float          # record-weighted, vs slo.limit
    pct_hours_met: float            # hour-weighted
    slo_met: Optional[bool]
    network_cost_usd: float = 0.0
    storage_cost_usd: float = 0.0
    # hourly records shed by bounded-queue policies (zeros otherwise)
    dropped: np.ndarray = field(default_factory=lambda: np.zeros(0))
    dropped_records: float = 0.0
    # record-weighted tail latencies (same CDF the median is read from)
    p95_latency_s: float = 0.0
    p99_latency_s: float = 0.0

    def __post_init__(self):
        # a defaulted ``dropped`` must still match the horizon — a bare
        # shape-(0,) array silently broadcasts to nonsense (or raises)
        # against the other hourly series in elementwise use
        if self.dropped.shape != self.load.shape:
            if self.dropped.size == 0:
                self.dropped = np.zeros_like(self.load)
            else:
                raise ValueError(
                    f"dropped has shape {self.dropped.shape}, want "
                    f"{self.load.shape} to match the hourly series")

    @property
    def grand_total_usd(self) -> float:
        return self.total_cost_usd + self.network_cost_usd + self.storage_cost_usd


@dataclass
class GridSummary:
    """One scenario of an aggregate-mode grid: Table II scalars, no series.

    The streaming backend (``simulate_grid(return_series=False)``) folds
    the summary statistics into the scan carry, so this is all that comes
    back — every scalar a ``SimulationResult`` carries, plus the
    load-weighted latency histogram the median was read from
    (``latency_hist`` over ``core.twin.aggregate_hist_centers()`` buckets).
    Sums, maxima, end-of-scan queue and the SLO percentages match the
    series-path ``_summarise`` exactly; ``median_latency_s`` is the
    histogram-CDF quantile, exact to one log-spaced bucket width
    (``core.twin.AGG_HIST_W`` decades).
    """
    name: str
    twin: Twin
    # scalars (same meanings as SimulationResult)
    total_cost_usd: float
    backlog_s: float
    backlog_cost_usd: float
    mean_throughput_rph: float
    max_throughput_rph: float
    median_latency_s: float
    mean_latency_s: float
    pct_latency_met: float
    pct_hours_met: float
    slo_met: Optional[bool]
    network_cost_usd: float = 0.0
    storage_cost_usd: float = 0.0
    dropped_records: float = 0.0
    # load-weighted tail latencies read off the histogram CDF, exact to
    # one quarter-octave bucket like the median
    p95_latency_s: float = 0.0
    p99_latency_s: float = 0.0
    # aggregate extras the series path derives from the full arrays
    processed_records: float = 0.0
    arrived_records: float = 0.0
    queue_end: float = 0.0
    latency_hist: np.ndarray = field(default_factory=lambda: np.zeros(0))
    # fault attribution (``simulate_grid(faults=...)``), read from the
    # in-carry A_FLTH/A_FOKH counters — zero / 100% on benign grids
    fault_hours: float = 0.0
    pct_hours_met_in_fault: float = 100.0
    pct_hours_met_outside_fault: float = 100.0

    @property
    def grand_total_usd(self) -> float:
        return self.total_cost_usd + self.network_cost_usd + self.storage_cost_usd


def scan_trace(load: jnp.ndarray, params: jnp.ndarray, policy_index,
               dt_hours=1.0):
    """One scenario's scan over arbitrary bins — the differentiable core.

    load [T] records/bin; params [PARAM_DIM]; ``dt_hours`` is the bin width.
    Unjitted on purpose: ``repro.calibrate`` takes ``jax.grad`` of a loss
    through this scan (wrapping it in its own jit), and ``_grid_scan`` wraps
    it in vmap+jit for the what-if grids. Returns (carry_end, (processed,
    queue, latency, cost, dropped)) with each series shaped [T].
    """
    branches = policy_branches()
    dt = jnp.asarray(dt_hours, jnp.float32)

    def bin_step(carry, arrive):
        return jax.lax.switch(policy_index, branches, carry, arrive,
                              params, dt)

    return jax.lax.scan(bin_step, jnp.zeros((CARRY_DIM,), jnp.float32), load)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _grid_scan_xla(loads: jnp.ndarray, params: jnp.ndarray,
                   policy_idx: jnp.ndarray, version: int,
                   dt_hours: float = 1.0):
    """The XLA grid backend: vmap over per-scenario ``lax.switch`` scans.

    loads [N, T] records/bin; params [N, PARAM_DIM] per twin.padded_params;
    policy_idx [N] int32 switch indices; ``version`` is the policy-registry
    version (static) so late policy registration forces a retrace;
    ``dt_hours`` (static) is the bin width — 1.0 for the year tables.
    This path is the parity anchor: the hourly full-year numbers stay
    bit-identical to the pre-Pallas kernel.
    """
    def one(load, p, idx):
        carry_end, outs = scan_trace(load, p, idx, dt_hours)
        return carry_end[0], outs

    return jax.vmap(one)(loads, params, policy_idx)


def _grid_scan(loads: jnp.ndarray, params: jnp.ndarray,
               policy_idx: jnp.ndarray, version: int, dt_hours: float = 1.0):
    """The whole grid in one dispatch — backend-selecting entry point.

    Default: the XLA vmapped switch-scan above. Under ``kernels.ops.
    use_pallas(True)`` / ``pallas_mode()``: the fused Pallas scenario-grid
    kernel (``kernels/policy_scan.py``), scenarios on the vector lanes,
    interpreted on the CPU. Same operands, same (q_end [N], five
    [N, T] series) contract either way; selection happens OUTSIDE jit, so
    flipping the switch between calls never stales a trace cache.
    """
    from repro.kernels import ops
    if ops.pallas_enabled():
        from repro.core.twin import policy_onehot
        onehot = jnp.asarray(policy_onehot(np.asarray(policy_idx)))
        carry_end, outs = ops.policy_scan(loads, params, onehot, dt_hours)
        return carry_end[:, 0], outs
    return _grid_scan_xla(loads, params, policy_idx, version, dt_hours)


def _agg_time_chunk(t_bins: int, cap: int = 1024) -> int:
    """Time-chunk width the device-resident histogram accumulates over:
    the largest divisor of ``t_bins`` at most ``cap`` (the 8736-hour
    year -> 728, 12 chunks). The chunking can never change results —
    the scan carry threads through every chunk unchanged and the f64
    per-chunk histogram adds are exact, hence order-independent — so the
    cap is purely a working-set bound on the [B, chunk] latency/load
    transients each chunk step stages."""
    t_bins = max(int(t_bins), 1)
    return next(d for d in range(min(cap, t_bins), 0, -1)
                if t_bins % d == 0)


def _branches_f32():
    """``policy_branches()`` with every step output pinned to f32.

    The aggregate XLA jits trace under ``jax.enable_x64(True)`` (the
    histogram's exactness contract), where a registered policy step that
    builds dtype-less literals (e.g. ``jnp.zeros(())``) silently emits f64 —
    breaking ``lax.switch`` branch-type agreement and flipping scan-carry
    dtypes mid-trace. Registry steps are f32-in/f32-out by contract;
    this enforces the contract at the trace boundary instead of trusting
    every (possibly user-registered) step. The cast is a no-op for
    conforming branches and exact for dtype-less zeros, so numbers never
    change."""
    def pin(step):
        def wrapped(carry, arrive, p, dt):
            carry, outs = step(carry, arrive, p, dt)
            f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
            return f32(carry), tuple(f32(o) for o in outs)
        return wrapped
    return [pin(s) for s in policy_branches()]


def _agg_scan_vmap(loads: jnp.ndarray, params: jnp.ndarray,
                   policy_idx: jnp.ndarray, dt_hours: float,
                   slo_limit: float, slo_mode: int):
    """Unjitted core of the XLA streaming-aggregate backend: an outer
    ``lax.scan`` over time chunks of vmapped per-scenario ``lax.switch``
    scans whose carry is (policy carry, scalar aggregate state). The
    policy-step op sequence is IDENTICAL to ``scan_trace`` (chaining the
    chunk scans replays the same per-bin sequence), so per-scenario
    carries (and thus the end-of-scan queue) match the series path bit
    for bit.

    The latency histogram is the one statistic not folded into the
    per-step carry on THIS backend: a per-step [BINS]-wide carry burns
    ~0.5 s per 1k scenarios in scan double-buffering on CPU. Instead
    each chunk step emits its [N, chunk] latencies and folds them
    through ``core.twin.device_latency_histogram`` — an exact f64
    dense masked reduction per (scenario, bucket) accumulated OUTSIDE
    the scan carry, entirely on device, bit-identical to host
    ``np.bincount``. No [N, T] panel is ever staged and nothing
    round-trips to the host. MUST be traced
    under ``jax.enable_x64(True)`` (``_grid_scan_agg`` wraps
    its call sites). Returns (carry_end [N, CARRY_DIM],
    agg [N, AGG_DIM] f32)."""
    branches = _branches_f32()
    dt = jnp.asarray(dt_hours, jnp.float32)
    n, t_bins = loads.shape
    chunk = _agg_time_chunk(t_bins)
    nc = t_bins // chunk

    def one(carry_i, agg_i, load_i, p, idx):
        def bin_step(state, arrive):
            carry, agg = state
            carry, outs = jax.lax.switch(idx, branches, carry, arrive, p,
                                         dt)
            agg = update_agg_scalars(agg, arrive, outs, slo_limit,
                                     slo_mode)
            return (carry, agg), outs[2]          # chunk-local latency

        (carry, agg), latency = jax.lax.scan(bin_step, (carry_i, agg_i),
                                             load_i)
        return carry, agg, latency

    def chunk_step(state, loads_c):
        carry, agg, hist = state
        carry, agg, lat = jax.vmap(one)(carry, agg, loads_c, params,
                                        policy_idx)
        hist = hist + device_latency_histogram(lat, loads_c)
        return (carry, agg, hist), None

    state0 = (jnp.zeros((n, CARRY_DIM), jnp.float32),
              init_agg_scalars((n,)),
              jnp.zeros((n, AGG_HIST_BINS), jnp.float64))
    (carry, agg, hist), _ = jax.lax.scan(
        chunk_step, state0,
        loads.reshape(n, nc, chunk).transpose(1, 0, 2))
    return carry, jnp.concatenate(
        [pack_agg_scalars(agg), hist.astype(jnp.float32)], axis=-1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _grid_scan_agg_xla(loads: jnp.ndarray, params: jnp.ndarray,
                       policy_idx: jnp.ndarray, version: int,
                       dt_hours: float, slo_limit: float, slo_mode: int):
    """The XLA aggregate backend (jitted). ``slo_limit`` / ``slo_mode``
    are static like ``dt_hours`` — a grid sweep reuses one SLO, so the
    retrace per distinct objective is paid once. Call under
    ``jax.enable_x64(True)`` (see ``_agg_scan_vmap``). Returns (carry_end
    [N, CARRY_DIM], agg [N, AGG_DIM])."""
    return _agg_scan_vmap(loads, params, policy_idx, dt_hours, slo_limit,
                          slo_mode)


def _fault_scalar_step(branches, dt):
    """Scalar (per-scenario) form of the fault perturbation layer — the
    same arithmetic, in the same order, as ``core.twin.
    fault_lane_policy_step``, over one scenario's CARRY_DIM carry."""
    def fstep(state, arrive, capmul, p, idx):
        carry, fq = state
        gate = (capmul > 0).astype(jnp.float32)
        avail = fq + arrive
        a_eff = gate * avail
        new_fq = avail - a_eff
        p_eff = p.at[0].set(p[0] * capmul)
        carry, outs = jax.lax.switch(idx, branches, carry, a_eff, p_eff,
                                     dt)
        wait = new_fq / jnp.maximum(p[0], jnp.float32(1e-9))
        outs = (outs[0], outs[1] + new_fq, outs[2] + wait, outs[3],
                outs[4])
        return (carry, new_fq), outs
    return fstep


@functools.partial(jax.jit, static_argnums=(4, 5))
def _grid_scan_fault_xla(loads: jnp.ndarray, caps: jnp.ndarray,
                         params: jnp.ndarray, policy_idx: jnp.ndarray,
                         version: int, dt_hours: float = 1.0):
    """Fault sibling of ``_grid_scan_xla`` (series mode): per-scenario
    switch-scans through the fault perturbation layer. The fault SERIES
    path is XLA-only regardless of the Pallas switch — the fused series
    kernel covers benign grids; chaos grids lean on the aggregate
    backend (``return_series=False``), where the Pallas fault kernel
    lives. Returns (q_end [N] with the fault backlog folded in, five
    [N, T] series)."""
    branches = policy_branches()
    dt = jnp.asarray(dt_hours, jnp.float32)
    fstep = _fault_scalar_step(branches, dt)

    def one(load, cap, p, idx):
        def bin_step(state, xs):
            arrive, capmul = xs
            return fstep(state, arrive, capmul, p, idx)

        (carry, fq), outs = jax.lax.scan(
            bin_step, (jnp.zeros((CARRY_DIM,), jnp.float32),
                       jnp.float32(0.0)), (load, cap))
        return carry[0] + fq, outs

    return jax.vmap(one)(loads, caps, params, policy_idx)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _grid_scan_agg_fault_xla(loads: jnp.ndarray, caps: jnp.ndarray,
                             fmask: jnp.ndarray, params: jnp.ndarray,
                             policy_idx: jnp.ndarray, version: int,
                             dt_hours: float, slo_limit: float,
                             slo_mode: int):
    """Fault sibling of ``_grid_scan_agg_xla``: the vmapped switch-scan
    steps through the fault layer (``caps``/``fmask`` [N, T] per-bin
    series), the in-carry counters gain the A_FLTH/A_FOKH attribution
    slots, and the fault backlog residue folds into ``carry_end[:, 0]``.
    Same chunked device-resident histogram contract as the benign path
    (call under ``jax.enable_x64(True)``); returns (carry_end [N, CARRY_DIM],
    agg [N, AGG_DIM])."""
    branches = _branches_f32()
    dt = jnp.asarray(dt_hours, jnp.float32)
    fstep = _fault_scalar_step(branches, dt)
    n, t_bins = loads.shape
    chunk = _agg_time_chunk(t_bins)
    nc = t_bins // chunk
    cs = lambda a: a.reshape(n, nc, chunk).transpose(1, 0, 2)  # noqa: E731

    def one(carry_i, fq_i, agg_i, load_i, cap_i, fm_i, p, idx):
        def bin_step(state, xs):
            arrive, capmul, fmk = xs
            (carry, fq), agg = state
            (carry, fq), outs = fstep((carry, fq), arrive, capmul, p, idx)
            agg = update_agg_scalars(agg, arrive, outs, slo_limit,
                                     slo_mode, fmk)
            return ((carry, fq), agg), outs[2]    # chunk-local latency

        ((carry, fq), agg), latency = jax.lax.scan(
            bin_step, ((carry_i, fq_i), agg_i), (load_i, cap_i, fm_i))
        return carry, fq, agg, latency

    def chunk_step(state, xs):
        carry, fq, agg, hist = state
        loads_c, caps_c, fmask_c = xs
        carry, fq, agg, lat = jax.vmap(one)(carry, fq, agg, loads_c,
                                            caps_c, fmask_c, params,
                                            policy_idx)
        hist = hist + device_latency_histogram(lat, loads_c)
        return (carry, fq, agg, hist), None

    state0 = (jnp.zeros((n, CARRY_DIM), jnp.float32),
              jnp.zeros((n,), jnp.float32),
              init_agg_scalars((n,)),
              jnp.zeros((n, AGG_HIST_BINS), jnp.float64))
    (carry, fq, agg, hist), _ = jax.lax.scan(
        chunk_step, state0, (cs(loads), cs(caps), cs(fmask)))
    carry = jnp.concatenate([carry[:, :1] + fq[:, None], carry[:, 1:]],
                            axis=1)
    return carry, jnp.concatenate(
        [pack_agg_scalars(agg), hist.astype(jnp.float32)], axis=-1)


def _grid_scan_agg(loads: jnp.ndarray, params: jnp.ndarray,
                   policy_idx: jnp.ndarray, version: int, dt_hours: float,
                   slo_limit: float, slo_mode: int,
                   caps=None, fmask=None):
    """Backend-selecting entry point of the streaming-aggregate scan —
    the O(N)-memory sibling of ``_grid_scan``. Same selection rule:
    XLA vmapped switch-scan by default, the fused Pallas aggregate kernel
    under ``kernels.ops.pallas_mode()`` (aggregates fully resident in
    VMEM scratch), decided OUTSIDE jit. Either way the result is O(N)
    and fully device-resident — histogram included, no host binning
    round-trip on any backend: (carry_end [N, CARRY_DIM],
    agg [N, AGG_DIM]). The XLA jits are always entered under
    ``jax.enable_x64(True)`` so their exact-f64 histogram accumulation never
    silently re-traces truncated. ``caps``/``fmask`` [N, T] (together)
    thread a fault schedule through either backend."""
    from repro.kernels import ops
    if ops.pallas_enabled():
        from repro.core.twin import policy_onehot
        onehot = jnp.asarray(policy_onehot(np.asarray(policy_idx)))
        return ops.policy_scan_agg(loads, params, onehot, dt_hours,
                                   slo_limit=slo_limit, slo_mode=slo_mode,
                                   caps=caps, fmask=fmask)
    with jax.enable_x64(True):
        if caps is not None:
            return _grid_scan_agg_fault_xla(
                loads, caps, fmask, params, policy_idx, version, dt_hours,
                slo_limit, slo_mode)
        return _grid_scan_agg_xla(
            loads, params, policy_idx, version, dt_hours, slo_limit,
            slo_mode)


def _agg_scan_uniform(load_matrix: jnp.ndarray, lidx: jnp.ndarray,
                      params: jnp.ndarray, policy_index: jnp.ndarray,
                      dt_hours: float, slo_limit: float, slo_mode: int):
    """Single-policy sibling of ``_agg_scan_vmap``: ``policy_index`` is a
    SCALAR (possibly traced), so the ``lax.switch`` hoists OUTSIDE the
    vmapped scan and the block executes exactly one policy branch — on a
    mixed five-policy grid that is ~5x less per-bin work than the vmapped
    switch (which a batched index lowers to evaluate-all-and-select).
    The per-scenario op sequence inside the selected branch is IDENTICAL
    to ``_agg_scan_vmap``'s, so results stay bit-for-bit equal; the block
    planner (``_agg_block_plan``) guarantees every chunked block is
    single-policy.

    Takes the [K, T] distinct-row matrix + the block's [B] row index and
    gathers ONE [B, chunk] slice per time chunk in-graph — the block's
    full [B, T] loads never exist, on device or host, and the histogram
    accumulates on device (``device_latency_histogram``; call under
    ``jax.enable_x64(True)``). Returns (carry_end [B, CARRY_DIM],
    agg [B, AGG_DIM])."""
    branches = _branches_f32()
    dt = jnp.asarray(dt_hours, jnp.float32)
    b = lidx.shape[0]
    k, t_bins = load_matrix.shape
    chunk = _agg_time_chunk(t_bins)
    nc = t_bins // chunk
    mx = load_matrix.reshape(k, nc, chunk).transpose(1, 0, 2)

    def uniform(j):
        def run(mx, lidx, params):
            def one(carry_i, agg_i, load_i, p):
                def bin_step(state, arrive):
                    carry, agg = state
                    carry, outs = branches[j](carry, arrive, p, dt)
                    agg = update_agg_scalars(agg, arrive, outs, slo_limit,
                                             slo_mode)
                    return (carry, agg), outs[2]

                (carry, agg), latency = jax.lax.scan(
                    bin_step, (carry_i, agg_i), load_i)
                return carry, agg, latency

            def chunk_step(state, m_c):
                carry, agg, hist = state
                loads_c = jnp.take(m_c, lidx, axis=0)
                carry, agg, lat = jax.vmap(one)(carry, agg, loads_c,
                                                params)
                hist = hist + device_latency_histogram(lat, loads_c)
                return (carry, agg, hist), None

            state0 = (jnp.zeros((b, CARRY_DIM), jnp.float32),
                      init_agg_scalars((b,)),
                      jnp.zeros((b, AGG_HIST_BINS), jnp.float64))
            (carry, agg, hist), _ = jax.lax.scan(chunk_step, state0, mx)
            return carry, jnp.concatenate(
                [pack_agg_scalars(agg), hist.astype(jnp.float32)],
                axis=-1)

        return run

    return jax.lax.switch(policy_index,
                          [uniform(j) for j in range(len(branches))],
                          mx, lidx, params)


def _agg_scan_uniform_fault(load_matrix: jnp.ndarray, lidx: jnp.ndarray,
                            cap_matrix: jnp.ndarray,
                            fmask_matrix: jnp.ndarray, fidx: jnp.ndarray,
                            params: jnp.ndarray,
                            policy_index: jnp.ndarray, dt_hours: float,
                            slo_limit: float, slo_mode: int):
    """Fault sibling of ``_agg_scan_uniform``: the single hoisted
    ``lax.switch`` picks the policy branch, every scenario of the block
    steps through the scalar fault layer, and the A_FLTH/A_FOKH counters
    ride the scalar aggregate state. The [F, T] capacity/mask matrices
    gather through ``fidx`` one [B, chunk] slice per time chunk, exactly
    like the loads through ``lidx`` — no [B, T] fault panels are staged
    either. Same returns plus the backlog folded into the carry's queue
    slot."""
    branches = _branches_f32()
    dt = jnp.asarray(dt_hours, jnp.float32)
    b = lidx.shape[0]
    k, t_bins = load_matrix.shape
    chunk = _agg_time_chunk(t_bins)
    nc = t_bins // chunk
    cs = lambda a: a.reshape(a.shape[0], nc, chunk).transpose(1, 0, 2)  # noqa: E731

    def uniform(j):
        def run(mx, cx, fx, lidx, fidx, params):
            def one(carry_i, fq_i, agg_i, load_i, cap_i, fm_i, p):
                def bin_step(state, xs):
                    arrive, capmul, fmk = xs
                    (carry, fq), agg = state
                    gate = (capmul > 0).astype(jnp.float32)
                    avail = fq + arrive
                    a_eff = gate * avail
                    new_fq = avail - a_eff
                    p_eff = p.at[0].set(p[0] * capmul)
                    carry, outs = branches[j](carry, a_eff, p_eff, dt)
                    wait = new_fq / jnp.maximum(p[0], jnp.float32(1e-9))
                    outs = (outs[0], outs[1] + new_fq, outs[2] + wait,
                            outs[3], outs[4])
                    agg = update_agg_scalars(agg, arrive, outs, slo_limit,
                                             slo_mode, fmk)
                    return ((carry, new_fq), agg), outs[2]

                ((carry, fq), agg), latency = jax.lax.scan(
                    bin_step, ((carry_i, fq_i), agg_i),
                    (load_i, cap_i, fm_i))
                return carry, fq, agg, latency

            def chunk_step(state, xs):
                carry, fq, agg, hist = state
                m_c, c_c, f_c = xs
                loads_c = jnp.take(m_c, lidx, axis=0)
                caps_c = jnp.take(c_c, fidx, axis=0)
                fmask_c = jnp.take(f_c, fidx, axis=0)
                carry, fq, agg, lat = jax.vmap(one)(
                    carry, fq, agg, loads_c, caps_c, fmask_c, params)
                hist = hist + device_latency_histogram(lat, loads_c)
                return (carry, fq, agg, hist), None

            state0 = (jnp.zeros((b, CARRY_DIM), jnp.float32),
                      jnp.zeros((b,), jnp.float32),
                      init_agg_scalars((b,)),
                      jnp.zeros((b, AGG_HIST_BINS), jnp.float64))
            (carry, fq, agg, hist), _ = jax.lax.scan(
                chunk_step, state0, (mx, cx, fx))
            carry = jnp.concatenate(
                [carry[:, :1] + fq[:, None], carry[:, 1:]], axis=1)
            return carry, jnp.concatenate(
                [pack_agg_scalars(agg), hist.astype(jnp.float32)],
                axis=-1)

        return run

    return jax.lax.switch(policy_index,
                          [uniform(j) for j in range(len(branches))],
                          cs(load_matrix), cs(cap_matrix),
                          cs(fmask_matrix), lidx, fidx, params)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   donate_argnums=(8, 9))
def _agg_block_step_xla(version: int, dt_hours: float, slo_limit: float,
                        slo_mode: int, load_matrix: jnp.ndarray,
                        lidx: jnp.ndarray, params: jnp.ndarray,
                        policy_index: jnp.ndarray, carry_acc: jnp.ndarray,
                        agg_acc: jnp.ndarray, offset,
                        cap_matrix=None, fmask_matrix=None, fidx=None):
    """One donated block step of the device-resident XLA engine: run the
    uniform-branch aggregate scan — which gathers the block's loads one
    [B, chunk] time chunk at a time from the replicated [K, T] matrix and
    accumulates the histogram on device — and write the O(B·AGG_DIM)
    result into the donated [Npad, *] accumulators at ``offset``.
    ``donate_argnums`` hands the accumulator buffers back to XLA, so
    device memory stays at ONE chunk's gathered loads + the O(N)
    aggregates no matter how many blocks stream through; no [B, T] panel
    ever exists and nothing returns to the host until the last block.
    Traces f64 (the histogram reduction) — call under
    ``jax.enable_x64(True)``.
    Fault grids add the replicated [F, T] capacity/mask matrices + the
    block's [B] ``fidx`` gather map (appended AFTER ``offset`` so the
    donated accumulator positions never move)."""
    del version
    if cap_matrix is None:
        carry, agg = _agg_scan_uniform(
            load_matrix, lidx, params, policy_index, dt_hours,
            slo_limit, slo_mode)
    else:
        carry, agg = _agg_scan_uniform_fault(
            load_matrix, lidx, cap_matrix, fmask_matrix, fidx, params,
            policy_index, dt_hours, slo_limit, slo_mode)
    carry_acc = jax.lax.dynamic_update_slice(carry_acc, carry, (offset, 0))
    agg_acc = jax.lax.dynamic_update_slice(agg_acc, agg, (offset, 0))
    return carry_acc, agg_acc


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   donate_argnums=(8, 9))
def _agg_block_step_pallas(version: int, dt_hours: float, slo_limit: float,
                           slo_mode: int,
                           matrix_t: jnp.ndarray, lidx: jnp.ndarray,
                           params: jnp.ndarray, policy_index: jnp.ndarray,
                           carry_acc: jnp.ndarray, agg_acc: jnp.ndarray,
                           offset, cap_mt=None, fmask_mt=None, fidx=None):
    """Pallas twin of ``_agg_block_step_xla``: gathers the block directly
    in the kernel's scenario-minor layout (``matrix_t`` [T, K] staged once,
    columns gathered per block — the PR 3/4 layout follow-on: no [B, T]
    intermediate or per-block transpose copy exists anymore) and runs the
    fused aggregate kernel, histogram and all on-device. The kernel's RAW
    [B, AGG_KDIM] rows (compensated histogram triples unrecombined) are
    accumulated — the driver recombines once at the very end
    (``finalize_aggregate_x64``), keeping this jit pure f32. Accumulators
    are donated exactly as on the XLA path. Fault grids gather the [T, F]
    ``cap_mt``/``fmask_mt`` columns through ``fidx`` the same way and run
    the kernel's fault variant."""
    del version
    from repro.core.twin import num_policies
    from repro.kernels.policy_scan import policy_grid_agg
    loads_t = jnp.take(matrix_t, lidx, axis=1)
    caps_t = fmask_t = None
    if cap_mt is not None:
        caps_t = jnp.take(cap_mt, fidx, axis=1)
        fmask_t = jnp.take(fmask_mt, fidx, axis=1)
    onehot = jnp.broadcast_to(
        jax.nn.one_hot(policy_index, num_policies(), dtype=jnp.float32),
        (lidx.shape[0], num_policies()))
    carry, agg = policy_grid_agg(
        None, params, onehot, dt_hours, slo_limit=slo_limit,
        slo_mode=slo_mode, loads_t=loads_t, caps_t=caps_t,
        fmask_t=fmask_t, finalize=False)
    carry_acc = jax.lax.dynamic_update_slice(carry_acc, carry, (offset, 0))
    agg_acc = jax.lax.dynamic_update_slice(agg_acc, agg, (offset, 0))
    return carry_acc, agg_acc


#: device-memory budget a streamed block may spend on its per-block
#: working set — the block size every horizon auto-chunks to derives
#: from this, see ``agg_auto_block``
AGG_BLOCK_BUDGET_BYTES = 150 * 2**20


def agg_auto_block(t_bins: int, dtype_bytes: int = 4,
                   panels: int = 0) -> int:
    """Auto-chunk block size for a ``t_bins``-bin horizon: the largest
    lane-aligned scenario count whose per-block working set fits the
    ~150 MB ``AGG_BLOCK_BUDGET_BYTES``.

    ``panels`` counts the [B, T] (or [T, B]) full-horizon arrays the
    block actually stages — the historical under-budgeting bug was
    declaring a budget for ONE panel while fault dispatch gathered
    ``caps_t``/``fmask_t`` alongside ``loads_t`` (~3x the declared
    budget). The Pallas path still gathers per-block column panels, so
    it passes ``panels=1`` (benign) or ``panels=3`` (fault grids); the
    device-resident XLA path stages NO full-horizon panel at all
    (``panels=0``) — its footprint is the [B, chunk] time-chunk gathers
    (up to 6 buffered by the scan pipeline) plus the O(B·AGG_DIM)
    aggregate rows, so year grids get ~7k-scenario blocks instead of
    ~4k and short horizons no longer over-chunk.

    A fixed scenario count would over-chunk short calibration horizons
    (thousands of tiny dispatches) and under-chunk long sub-hour ones
    (working sets far past the budget); deriving from the horizon keeps
    every grid at the same working set. Clamped to [128, 65536] and
    rounded down to a 128-lane multiple."""
    t_bins = max(int(t_bins), 1)
    if panels:
        per_row = t_bins * dtype_bytes * panels
    else:
        per_row = (6 * _agg_time_chunk(t_bins) + 4 * AGG_DIM) * dtype_bytes
    block = AGG_BLOCK_BUDGET_BYTES // per_row
    return int(min(max(block // 128 * 128, 128), 65536))


#: aggregate YEAR grids beyond this many scenarios auto-chunk; kept as a
#: constant for back-compat — non-year horizons use ``agg_auto_block``
AGG_AUTO_BLOCK = agg_auto_block(HOURS_PER_YEAR)


def _agg_block_plan(policy_idx: np.ndarray, block: int):
    """Group scenarios into single-policy blocks of ``block``.

    Returns (positions [NB, block] int64, block_policy [NB] int32):
    ``positions[b, i]`` is the scenario index occupying slot i of block b,
    or -1 for a pad slot (each policy's run is padded up to a block
    multiple independently, so every block is policy-uniform — tail pads
    are per policy, not one global tail). Grouping is a STABLE sort by
    policy, so scenarios of one policy keep their grid order; results are
    scattered back through ``positions``, making the regrouping invisible
    to callers."""
    policy_idx = np.asarray(policy_idx)
    order = np.argsort(policy_idx, kind="stable")
    positions, block_policy = [], []
    for p in np.unique(policy_idx):
        pos = order[policy_idx[order] == p]
        nb = -(-len(pos) // block)
        padded = np.full(nb * block, -1, np.int64)
        padded[:len(pos)] = pos
        positions.append(padded.reshape(nb, block))
        block_policy.extend([int(p)] * nb)
    if positions:
        positions = np.concatenate(positions)
    else:
        positions = np.zeros((0, block), np.int64)
    return positions, np.asarray(block_policy, np.int32)


@functools.lru_cache(maxsize=16)
def _sharded_agg_fn(devices: int, version: int, dt_hours: float,
                    slo_limit: float, slo_mode: int, backend: str,
                    block: int, faulted: bool = False):
    """Build (and cache) the jitted ``shard_map`` ROUND step for a
    ``devices``-wide 1-D scenario mesh: the [K, T] load matrix is
    replicated, and one round feeds each device exactly one
    single-policy block — lidx [D, B] / params [D, B, PARAM_DIM] /
    block_policy [D] sharded on the leading axis, so every shard runs
    the same uniform-branch aggregate scan the one-device engine runs
    and results are bit-identical to unsharded by construction. Both
    backends keep the histogram INSIDE the ``shard_map`` body — the XLA
    branch accumulates it on device with ``device_latency_histogram``
    (scenarios are disjoint across shards, so a plain sharded gather
    returns the per-row histograms; no psum needed) and returns finished
    [D, B, AGG_DIM] rows; the Pallas branch returns the kernel's raw
    [D, B, AGG_KDIM] rows for one end-of-grid recombination. The old
    per-round host drain — and the pure_callback-deadlock constraint it
    was built around — is gone: the XLA round traces f64, so CALL IT
    UNDER ``jax.enable_x64(True)``. ``faulted`` builds the fault-grid
    variant: the [F, T] capacity/mask matrices replicate like the load
    matrix and a sharded [D, B] fault index gathers each block's per-bin
    fault series."""
    del version
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("scenario",))

    def body(load_matrix, lidx, params, block_policy, cap_matrix=None,
             fmask_matrix=None, fidx=None):
        lidx_b, p_b = lidx[0], params[0]          # the shard's one block
        pidx_b = block_policy[0]
        if backend == "pallas":
            from repro.core.twin import num_policies
            from repro.kernels.policy_scan import policy_grid_agg
            loads_t = jnp.take(load_matrix.T, lidx_b, axis=1)
            caps_t = fmask_t = None
            if faulted:
                caps_t = jnp.take(cap_matrix.T, fidx[0], axis=1)
                fmask_t = jnp.take(fmask_matrix.T, fidx[0], axis=1)
            onehot = jnp.broadcast_to(
                jax.nn.one_hot(pidx_b, num_policies(),
                               dtype=jnp.float32),
                (block, num_policies()))
            carry, agg = policy_grid_agg(
                None, p_b, onehot, dt_hours, slo_limit=slo_limit,
                slo_mode=slo_mode, loads_t=loads_t, caps_t=caps_t,
                fmask_t=fmask_t, finalize=False)
            return carry[None], agg[None]
        if faulted:
            carry, agg = _agg_scan_uniform_fault(
                load_matrix, lidx_b, cap_matrix, fmask_matrix, fidx[0],
                p_b, pidx_b, dt_hours, slo_limit, slo_mode)
        else:
            carry, agg = _agg_scan_uniform(
                load_matrix, lidx_b, p_b, pidx_b, dt_hours, slo_limit,
                slo_mode)
        return carry[None], agg[None]

    out_specs = (P("scenario"), P("scenario"))
    in_specs = (P(), P("scenario"), P("scenario"), P("scenario"))
    if faulted:
        in_specs = in_specs + (P(), P(), P("scenario"))
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)

    def _mesh_agg_round(*args):
        # a named function, so the profiler names the round's program
        # ``jit__mesh_agg_round`` whatever the body is called
        return sharded(*args)

    return jax.jit(_mesh_agg_round)


def _to_device(*arrays, wait: bool = False):
    """``jnp.asarray`` of each host array, counting the bytes copied in
    the ``grid.h2d_bytes`` counter. ``wait`` (the ``grid.upload`` sites,
    where no device work is queued yet, so waiting serialises nothing)
    blocks on the copies when telemetry is on, so the enclosing span
    times the transfer."""
    out = [jnp.asarray(a) for a in arrays]
    if obs.enabled():
        obs.count("grid.h2d_bytes", sum(a.nbytes for a in out))
        if wait:
            jax.block_until_ready(out)
    return out


def _run_blocks_sharded(load_matrix: np.ndarray, lidx: np.ndarray,
                        params: np.ndarray, block_policy: np.ndarray,
                        devices: int, version: int, dt_hours: float,
                        slo_limit: float, slo_mode: int, backend: str,
                        fault=None):
    """Drive the sharded round step over all blocks: rounds of one block
    per device, every round fully device-resident — the old overlap
    machinery (host binning of round r-1's panels while round r runs)
    is gone because there is no host binning left to overlap. ``lidx``
    arrives padded to a ``devices`` multiple of blocks (dummy all-pad
    blocks). ``fault`` = (cap [F, T], fmask [F, T], fidx [NB, B])
    threads a fault grid through every round. Returns host (carry
    [NB*B, CARRY_DIM], agg [NB*B, AGG_DIM]) — Pallas rounds return raw
    AGG_KDIM rows, recombined ONCE here at the end of the grid."""
    nb, block = lidx.shape
    d = devices
    rounds = nb // d
    npad = nb * block
    fn = _sharded_agg_fn(d, version, dt_hours, slo_limit, slo_mode,
                         backend, block,
                         faulted=fault is not None)
    with obs.span("grid.upload"):
        shared = _to_device(load_matrix, *(fault[:2] if fault is not None
                                           else ()), wait=True)
    agg_width = AGG_KDIM if backend == "pallas" else AGG_DIM
    carry_out = np.empty((npad, CARRY_DIM), np.float32)
    agg_out = np.empty((npad, agg_width), np.float32)
    per_round = (lidx, params, block_policy) + (
        (fault[2],) if fault is not None else ())

    # the XLA round jit traces f64 (in-graph histogram reduction) —
    # every call must sit inside enable_x64 or jit re-traces a truncated
    # f32 variant; the Pallas round jit is pure f32 and stays outside
    ctx = (contextlib.nullcontext() if backend == "pallas"
           else jax.enable_x64(True))
    with ctx:
        for r in range(rounds):
            cache0 = obs.jit_cache_size(fn) if obs.enabled() else 0
            with obs.span("grid.round", round=r, devices=d, block=block,
                          backend=backend,
                          scenarios=d * block) as sp:
                lr, pr, bpr, *fr = _to_device(
                    *(a[r * d:(r + 1) * d] for a in per_round))
                carry, agg = fn(shared[0], lr, pr, bpr, *shared[1:], *fr)
                jax.block_until_ready(agg)
            if obs.enabled():
                sp.attrs["compiled"] = float(
                    obs.jit_cache_grew(fn, cache0))
            sl = slice(r * d * block, (r + 1) * d * block)
            with obs.span("grid.drain"):
                carry_out[sl] = np.asarray(carry).reshape(-1, CARRY_DIM)
                agg_out[sl] = np.asarray(agg).reshape(-1, agg.shape[-1])
    if backend == "pallas":
        agg_out = np.asarray(finalize_aggregate_x64(agg_out))
    return carry_out, agg_out


def _run_blocks_single(load_matrix: np.ndarray, lidx: np.ndarray,
                       params: np.ndarray, block_policy: np.ndarray,
                       version: int, dt_hours: float, slo_limit: float,
                       slo_mode: int, backend: str,
                       fault=None):
    """The one-device streaming engine: every block runs fully
    device-resident — no latency panel ever crosses to the host and the
    old dispatch/bin overlap machinery is gone because there is no host
    binning left to overlap. Accumulators are donated across steps (see
    ``_agg_block_step_*``), so device memory stays at one block's
    working set + the O(N) aggregate rows; nothing copies back until
    the final ``np.asarray``. ``fault`` = (cap [F, T], fmask [F, T],
    fidx [NB, B]) threads a fault grid through every block. Returns host
    (carry [NB*B, CARRY_DIM], agg [NB*B, AGG_DIM]) — Pallas blocks
    accumulate raw AGG_KDIM rows, recombined ONCE at the end of the
    grid. With telemetry on, ``grid.upload`` times the copy of the
    shared matrices, each ``grid.block`` span the host's dispatch of its
    block (it does not wait for the device: the trace gives a block's
    device time) and ``grid.drain`` the wait for the results and their
    copy back."""
    nb, block = lidx.shape
    npad = nb * block
    pallas = backend == "pallas"
    step = _agg_block_step_pallas if pallas else _agg_block_step_xla
    shared = (load_matrix,) + (tuple(fault[:2]) if fault is not None
                               else ())
    with obs.span("grid.upload"):
        # the Pallas kernel reads [T, *] column panels
        shared = _to_device(*(np.asarray(a).T if pallas else a
                              for a in shared), wait=True)
    per_block = (lidx, params, block_policy) + (
        (fault[2],) if fault is not None else ())
    carry_acc = jnp.zeros((npad, CARRY_DIM), jnp.float32)
    agg_acc = jnp.zeros((npad, AGG_KDIM if pallas else AGG_DIM),
                        jnp.float32)
    # the XLA block step traces f64 (docstring); the Pallas one is f32
    ctx = contextlib.nullcontext() if pallas else jax.enable_x64(True)
    with ctx:
        for b in range(nb):
            cache0 = obs.jit_cache_size(step) if obs.enabled() else 0
            with obs.span("grid.block", block=b, size=block,
                          policy=int(block_policy[b]),
                          backend=backend) as sp:
                lb, pb, bpb, *fb = _to_device(*(a[b] for a in per_block))
                carry_acc, agg_acc = step(
                    version, dt_hours, slo_limit, slo_mode, shared[0], lb,
                    pb, bpb, carry_acc, agg_acc, b * block, *shared[1:],
                    *fb)
                if obs.enabled():
                    sp.attrs["compiled"] = float(obs.jit_cache_grew(
                        step, cache0))
        with obs.span("grid.drain"):
            if pallas:
                return (np.asarray(carry_acc),
                        np.asarray(finalize_aggregate_x64(agg_acc)))
            return np.asarray(carry_acc), np.asarray(agg_acc)


def _dedup_rows(load_index: np.ndarray, params: np.ndarray,
                policy_idx: np.ndarray, fault=None):
    """Exact duplicate-scenario detection for the aggregate dispatch.

    Two scenario rows are duplicates when their (load row, param vector,
    policy index, fault row) are BITWISE identical — they play the same
    deterministic year, so one simulation serves all of them. Fault rows
    are canonicalized first (bitwise-equal [F, T] cap+fmask rows map to
    one id), which is what collapses benign futures: ``expand_grid``
    aliases their load rows to the originals and every benign future's
    cap/fmask row is the same all-ones/all-zeros pair, so the N*F chaos
    grid keeps one benign row per base scenario. Tiled grids (policy
    tournaments re-running a baseline, twin x traffic sweeps cycling a
    twin list) collapse the same way. Returns (keep [U], inv [N],
    fidx_canon [N]) with ``keep`` the first-occurrence row of each
    distinct scenario and ``inv`` the expansion map back to grid order —
    or None when every row is already distinct. f32 bit-equality is
    conservative: NaN != NaN and -0.0 != 0.0 never merge rows that could
    differ."""
    lidx = np.ascontiguousarray(load_index, np.int32)
    n = lidx.shape[0]
    pp = np.ascontiguousarray(params, np.float32)
    key = [lidx[:, None].view(np.uint32),
           np.ascontiguousarray(policy_idx, np.int32)[:, None]
           .view(np.uint32), pp.view(np.uint32)]
    fidx_canon = None
    if fault is not None:
        frows = np.concatenate(
            [np.ascontiguousarray(fault[0], np.float32).view(np.uint32),
             np.ascontiguousarray(fault[1], np.float32).view(np.uint32)],
            axis=1)
        _, ffirst, finv = np.unique(frows, axis=0, return_index=True,
                                    return_inverse=True)
        fidx_canon = ffirst[finv.reshape(-1)][np.asarray(fault[2])] \
            .astype(np.int32)
        key.append(fidx_canon[:, None].view(np.uint32))
    keep, inv = np.unique(np.concatenate(key, axis=1), axis=0,
                          return_index=True, return_inverse=True)[1:]
    if keep.shape[0] == n:
        return None
    return keep, inv.reshape(-1), fidx_canon


def _grid_agg_dispatch(load_matrix: np.ndarray, load_index: np.ndarray,
                       params: np.ndarray, policy_idx: np.ndarray,
                       dt_hours: float, slo_limit: float, slo_mode: int,
                       scenario_block: Optional[int],
                       devices: Optional[int] = None, fault=None):
    """Run the aggregate scan over (matrix, index)-encoded scenarios,
    chunked into ``scenario_block``-sized blocks when asked — or when the
    grid exceeds the horizon's auto-chunk threshold (``agg_auto_block``).
    Chunked grids are regrouped into single-policy blocks
    (``_agg_block_plan``) and streamed through the donated async block
    engine; ``devices`` > 1 instead shards the blocked grid over a 1-D
    scenario mesh (``_sharded_agg_fn``). ``fault`` = (cap [F, T],
    fmask [F, T], fault_index [N]) threads a fault grid through every
    path — fault rows gather through ``fault_index`` exactly like load
    rows through ``load_index``, so a 65k chaos grid ships F fault rows,
    not 65k. Bitwise-duplicate scenario rows (``_dedup_rows``) are
    simulated once and their summary rows replicated on the way out —
    exact, because scenarios are independent and deterministic. All
    paths return the same host numpy (carry_end [N, CARRY_DIM], agg
    [N, AGG_DIM]), bit-identical to one another."""
    n = len(load_index)
    with obs.span("grid.dedup"):
        dd = _dedup_rows(load_index, params, policy_idx, fault)
        if dd is not None:
            keep, inv, fidx_canon = dd
            load_index = np.asarray(load_index)[keep]
            params = np.asarray(params)[keep]
            policy_idx = np.asarray(policy_idx)[keep]
            if fault is not None:
                fault = (fault[0], fault[1], fidx_canon[keep])
    if dd is None:
        return _grid_agg_distinct(load_matrix, load_index, params,
                                  policy_idx, dt_hours, slo_limit,
                                  slo_mode, scenario_block, devices, fault)
    obs.count("grid.dedup.total", n)
    obs.count("grid.dedup.kept", len(keep))
    carry_u, agg_u = _grid_agg_distinct(
        load_matrix, load_index, params, policy_idx, dt_hours, slo_limit,
        slo_mode, scenario_block, devices, fault)
    with obs.span("grid.scatter"):
        return carry_u[inv], agg_u[inv]


def _grid_agg_distinct(load_matrix: np.ndarray, load_index: np.ndarray,
                       params: np.ndarray, policy_idx: np.ndarray,
                       dt_hours: float, slo_limit: float, slo_mode: int,
                       scenario_block: Optional[int],
                       devices: Optional[int], fault):
    """``_grid_agg_dispatch`` for a grid of distinct rows: the small-grid
    scan, or the block plan, a block engine and the scatter back to grid
    order."""
    from repro.kernels import ops
    n = len(load_index)
    backend = "pallas" if ops.pallas_enabled() else "xla"
    # the Pallas path still stages per-block [T, B] column panels (one
    # for loads, +2 for a fault grid's caps/fmask); the device-resident
    # XLA path stages none — derive the auto-block from what the chosen
    # backend actually allocates
    panels = (3 if fault is not None else 1) if backend == "pallas" else 0
    auto_block = agg_auto_block(load_matrix.shape[1], panels=panels)
    if scenario_block is None and (n > auto_block
                                   or (devices or 1) > 1):
        scenario_block = auto_block
    version = registry_version()
    if scenario_block is None or (scenario_block >= n
                                  and (devices or 1) <= 1):
        with obs.span("grid.plan"):
            if (load_matrix.shape[0] == n
                    and np.array_equal(load_index, np.arange(n))):
                loads_np = load_matrix  # identity map: the rows ARE the grid
            else:
                loads_np = np.ascontiguousarray(load_matrix[load_index])
            fault_np = ()
            if fault is not None:
                cap_m, fmask_m, fidx = fault
                fault_np = (np.asarray(cap_m)[fidx],
                            np.asarray(fmask_m)[fidx])
        with obs.span("grid.upload"):
            loads, params_d, pidx, *fault_d = _to_device(
                loads_np, params, policy_idx, *fault_np, wait=True)
        caps, fmask = fault_d if fault_d else (None, None)
        with obs.span("grid.scan"):
            carry_end, agg = _grid_scan_agg(loads, params_d, pidx, version,
                                            dt_hours, slo_limit, slo_mode,
                                            caps=caps, fmask=fmask)
        with obs.span("grid.drain"):
            return (np.asarray(carry_end, np.float64),
                    np.asarray(agg, np.float64))

    block = int(min(scenario_block, max(n, 1)))
    d = int(devices or 1)
    with obs.span("grid.plan"):
        positions, block_policy = _agg_block_plan(policy_idx, block)
        # stage the per-block host operands through the position map: pad
        # slots (-1) read row 0 with zero params — discarded on scatter
        valid = positions >= 0
        safe = np.where(valid, positions, 0)
        lidx = np.where(valid, np.asarray(load_index)[safe], 0) \
            .astype(np.int32)
        params_b = np.where(valid[..., None], np.asarray(params)[safe],
                            0).astype(np.float32)
        block_fault = None
        if fault is not None:
            cap_m, fmask_m, fidx_all = fault
            fidx_b = np.where(valid, np.asarray(fidx_all)[safe], 0) \
                .astype(np.int32)
            block_fault = (np.asarray(cap_m, np.float32),
                           np.asarray(fmask_m, np.float32), fidx_b)
        nb = positions.shape[0]
        pad_blocks = (-nb) % d
        if pad_blocks:      # dummy all-pad blocks so every round is full
            lidx = np.concatenate(
                [lidx, np.zeros((pad_blocks, block), np.int32)])
            params_b = np.concatenate(
                [params_b,
                 np.zeros((pad_blocks, block) + params_b.shape[2:],
                          np.float32)])
            block_policy = np.concatenate(
                [block_policy, np.zeros(pad_blocks, np.int32)])
            if block_fault is not None:
                block_fault = (block_fault[0], block_fault[1],
                               np.concatenate(
                                   [block_fault[2],
                                    np.zeros((pad_blocks, block),
                                             np.int32)]))
    obs.gauge("grid.block_size", block)
    obs.count("grid.blocks", nb, backend=backend, devices=d)

    if d > 1:
        carry, agg = _run_blocks_sharded(
            np.asarray(load_matrix), lidx, params_b, block_policy, d,
            version, float(dt_hours), float(slo_limit), int(slo_mode),
            backend, fault=block_fault)
        carry = carry[:nb * block]
        agg = agg[:nb * block]
    else:
        carry, agg = _run_blocks_single(
            np.asarray(load_matrix), lidx, params_b, block_policy,
            version, float(dt_hours), float(slo_limit), int(slo_mode),
            backend, fault=block_fault)

    with obs.span("grid.scatter"):
        # block results back to grid order through the position map
        flat_pos = positions.reshape(-1)
        vmask = flat_pos >= 0
        carry_end = np.zeros((n, carry.shape[-1]), np.float64)
        out_agg = np.zeros((n, agg.shape[-1]), np.float64)
        carry_end[flat_pos[vmask]] = carry[vmask]
        out_agg[flat_pos[vmask]] = agg[vmask]
    return carry_end, out_agg


# the jit-cache introspection the tests (and benchmarks) use lives on the
# XLA paths (series + aggregate); expose it on the selector so callers
# keep one import — "compiled exactly once" holds whichever mode ran
def _clear_grid_caches():
    _grid_scan_xla.clear_cache()
    _grid_scan_agg_xla.clear_cache()
    _agg_block_step_xla.clear_cache()
    _agg_block_step_pallas.clear_cache()
    _sharded_agg_fn.cache_clear()


def _grid_cache_size():
    return (_grid_scan_xla._cache_size() + _grid_scan_agg_xla._cache_size()
            + _agg_block_step_xla._cache_size()
            + _agg_block_step_pallas._cache_size())


_grid_scan.clear_cache = _clear_grid_caches
_grid_scan._cache_size = _grid_cache_size


def simulate_grid(twins: Sequence[Twin], loads: Optional[np.ndarray] = None,
                  names: Optional[Sequence[str]] = None,
                  slo: Optional[SLO] = None,
                  cost_model: Optional[CostModel] = None,
                  record_mb: float = 0.0,
                  bin_hours: Optional[float] = None, *,
                  return_series: bool = True,
                  load_matrix: Optional[np.ndarray] = None,
                  load_index: Optional[np.ndarray] = None,
                  scenario_block: Optional[int] = None,
                  devices: Optional[int] = None,
                  faults=None):
    """Simulate N scenarios — twins[i] against loads[i] — in one vmapped
    scan. ``loads`` is [N, T] records per bin of ``bin_hours`` (the year
    tables use [N, HOURS_PER_YEAR] hourly bins).

    Two result modes:

    * ``return_series=True`` (default) — the seed contract, bit-identical:
      five [N, T] hourly series come back from the device and each
      scenario is summarised into a full ``SimulationResult``. Plots,
      ``monthly_table`` and calibration traces need this mode.
    * ``return_series=False`` — the streaming-aggregate backend: the
      Table II statistics (compensated sums, per-bin max, end-of-scan
      queue, SLO-ok counters and a load-weighted latency histogram) are
      folded into the scan carry, NO [N, T] output series is ever
      materialized, and one vectorized numpy pass over the O(N)
      aggregates returns ``GridSummary`` rows. Sums / maxima / queue /
      SLO percentages match the series path exactly; the median is
      histogram-exact (one log bucket). This is the mode 100k+-scenario
      what-if sweeps should use (and ``whatif.run_grid`` defaults to).

    Instead of a stacked ``loads`` grid, pass ``load_matrix`` [K, T] (each
    distinct load row once) + ``load_index`` [N] (scenario i plays row
    ``load_matrix[load_index[i]]``) so host memory stays O(K*T + N);
    ``whatif.run_grid`` builds its (traffic x twin) grids this way.
    ``scenario_block`` (aggregate mode only) streams the grid through
    the device in blocks of that many scenarios via ``lax.map`` — with
    the matrix+index encoding, grids larger than device memory complete
    in one call (a stacked ``loads=`` grid still lands on the device
    whole as the gather source; chunking then bounds only the
    per-block panel and outputs).

    Omitting ``bin_hours`` keeps the seed contract: hourly bins over the
    full year, any other horizon rejected. Passing it (any value,
    including an explicit 1.0) unlocks arbitrary horizons — but storage/
    network accounting (Table IV) is daily-rolling over the year, so a
    cost model + record_mb on a non-year grid is an error, not a silent
    zero.

    **Scaling the grid** (aggregate mode). The whole engine is
    device-resident: the quarter-octave latency histogram accumulates
    on device next to the scan (an exact f64 masked reduction per time
    chunk on the XLA path, compensated in-kernel triples on Pallas), so
    no ``[B, T]`` latency panel is ever staged, copied to the host, or
    binned there — only O(N·AGG_DIM) aggregate rows leave the device,
    once, at the end of the grid. Three independent levers:

    * ``scenario_block`` — scenarios per streamed device block. The
      default (``agg_auto_block(t_bins, panels=...)``) sizes blocks so
      one block's working set fits a ~150 MB budget, derived from what
      the chosen backend actually allocates: the XLA path stages only
      [B, chunk] time-chunk gathers plus the aggregate rows (so year
      grids get ~7.6k-scenario blocks), while the Pallas path still
      gathers one [T, B] column panel per block (three on chaos grids —
      counted, not under-budgeted). Shrink it if a block plus the O(N)
      aggregates exceeds device memory; growing it buys little —
      per-block overhead is one dispatch.
    * Chunked blocks are regrouped to be *policy-uniform* (stable order,
      results scattered back), so each block runs exactly one policy
      branch instead of an evaluate-all-branches select — on a mixed
      five-policy grid that alone is most of the engine's speedup, at
      identical bits.
    * ``devices=D`` — shard the blocked grid over a 1-D ``D``-device
      scenario mesh (load matrix replicated, scenario blocks sharded).
      The histogram stays inside the ``shard_map`` body, so rounds no
      longer serialize on a host drain. Results are bit-identical to
      ``devices=None``. On a multi-core CPU host, export
      ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
      *before the first jax import* to expose D host devices; on real
      accelerators each device is one shard. Million-scenario full-year
      sweeps complete either way — memory stays at one block per device
      — sharding just divides the wall clock.

    **Chaos suites** (``faults=``). Pass a ``repro.faults.FaultSchedule``
    (sampled here, seeded and deterministic) or a pre-sampled
    ``repro.faults.SampledFaults`` to play every scenario against F
    fault futures: outage windows zero the twin's capacity, brownouts
    scale it down, correlated device disconnects strip a load fraction
    and replay it as a reconnect flood right after the window, bursts
    multiply the load. The grid expands in place to N*F scenarios named
    ``"{name}/f{f}"``, ordered scenario-major / future-minor (row
    ``i*F + f``), with ``twins[i]`` repeated across its futures. Load
    perturbations are baked into extra load-matrix rows (futures that
    don't touch the load alias the ORIGINAL rows — an empty or benign
    schedule is bit-identical to the fault-free grid on both backends,
    including under ``devices=D``); capacity perturbations stream
    through the scan as [F, T] fault rows gathered per scenario, so a
    65k-scenario full-year chaos grid ships F rows, not 65k. Aggregate
    mode additionally reports fault attribution per scenario
    (``GridSummary.fault_hours`` / ``pct_hours_met_in_fault`` /
    ``pct_hours_met_outside_fault``) from in-carry counters — no [N, T]
    series materialized. Sampled series are validated before any device
    work: a negative or non-finite capacity/load multiplier raises
    ``ValueError`` naming the fault spec and bin index. Chance-
    constrained search over the same futures lives in
    ``repro.search.search(faults=..., quantile=...)``.

    **Observing the wind tunnel** (``repro.obs``). With telemetry on
    (``REPRO_OBS=1`` or inside ``obs.capture()``) every grid emits a
    ``grid.simulate`` root span (attrs: ``n``, ``t_bins``, ``mode``,
    ``devices``, ``faulted``); the blocked aggregate engine nests a
    ``grid.block`` span per device block (``grid.round`` per sharded
    round) tagged with block index, size, policy, backend and a
    ``compiled`` flag read off the jit trace cache at dispatch, so
    re-trace storms are visible per block. A ``grid.block`` span times
    the host's dispatch of its block and does not wait for the device
    (a ``grid.round`` does); a block's device time is read off a
    profiler trace. The host work around the scan has spans of its own:
    ``grid.params`` (the twins' parameter rows, before the root opens),
    ``grid.dedup``, ``grid.plan`` (block plan and staging, or the
    small-grid gather), ``grid.upload`` (the host-to-device copies; with
    telemetry on it waits for them, before any device work is queued),
    ``grid.scan`` (the small-grid dispatch), ``grid.drain`` (the wait
    for the results and their copy back), ``grid.scatter`` (back to
    grid order) and ``grid.summarise`` (``GridSummary`` rows, after the
    root closes). Every span also opens a profiler annotation of its
    name, so under a running ``jax.profiler`` trace the spans lie on the
    host plane beside the device's operations. Counters:
    ``grid.scenarios``, ``grid.blocks{backend,devices}``,
    ``grid.dedup.total`` / ``grid.dedup.kept`` (how much of the grid
    bitwise-dedup collapsed), ``grid.h2d_bytes`` (bytes copied to the
    device: the shared matrices and every block's operands). All
    instrumentation sits at dispatch boundaries — never inside
    jitted code — so simulated numbers are bit-identical with telemetry
    on or off, and the disabled path costs one attribute check per
    site. ``obs.render()`` prints the consolidated table;
    ``obs.prometheus_exposition(rows)`` serves the returned
    ``GridSummary`` rows as a scrape-able exposition.
    """
    if (loads is None) == (load_matrix is None):
        raise ValueError("pass exactly one of loads= (stacked [N, T] grid) "
                         "or load_matrix= [K, T] + load_index= [N]")
    if load_matrix is not None:
        load_matrix = np.asarray(load_matrix, np.float32)
        if load_matrix.ndim != 2:
            raise ValueError(f"load_matrix must be [K, T], got shape "
                             f"{load_matrix.shape}")
        if load_index is None:
            raise ValueError("load_matrix= needs load_index= mapping each "
                             "scenario to a matrix row")
        load_index = np.asarray(load_index, np.int32)
        if load_index.ndim != 1:
            raise ValueError(f"load_index must be [N], got shape "
                             f"{load_index.shape}")
        if load_index.size and (load_index.min() < 0
                                or load_index.max() >= load_matrix.shape[0]):
            raise ValueError(f"load_index out of range for "
                             f"{load_matrix.shape[0]} load_matrix rows")
        n, t_bins = len(load_index), load_matrix.shape[1]
    else:
        loads = np.asarray(loads, np.float32)
        if loads.ndim != 2:
            raise ValueError(f"loads must be a [N, T] scenario grid, got "
                             f"shape {loads.shape}")
        n, t_bins = loads.shape
    if bin_hours is None:
        if t_bins != HOURS_PER_YEAR:
            raise ValueError(
                f"hourly grids must cover the {HOURS_PER_YEAR}-hour year, "
                f"got {t_bins} bins; pass bin_hours= for sub-hour "
                f"or short-horizon traces")
        bin_hours = 1.0
    year_grid = t_bins == HOURS_PER_YEAR and bin_hours == 1.0
    if cost_model is not None and record_mb > 0.0 and not year_grid:
        raise ValueError("storage/network costs need the hourly full-year "
                         "grid (daily rolling retention); drop the cost "
                         "model or simulate the full year")
    if len(twins) != n:
        raise ValueError(f"{len(twins)} twins for {n} load "
                         f"rows — the grid pairs twins[i] with loads[i]")
    if scenario_block is not None and scenario_block <= 0:
        raise ValueError(f"scenario_block must be a positive block size, "
                         f"got {scenario_block}")
    if scenario_block is not None and return_series:
        raise ValueError("scenario_block chunks the streaming-aggregate "
                         "backend only; series mode materializes all "
                         "[N, T] series regardless, so the memory bound "
                         "you asked for cannot be honored — drop "
                         "scenario_block or pass return_series=False")
    if devices is not None:
        if return_series:
            raise ValueError("devices= shards the streaming-aggregate "
                             "backend only; pass return_series=False")
        if devices <= 0:
            raise ValueError(f"devices must be a positive mesh size, "
                             f"got {devices}")
        if devices > jax.device_count():
            raise ValueError(
                f"devices={devices} but only {jax.device_count()} "
                f"JAX device(s) are visible; on CPU export "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{devices} before the first jax import")
    with obs.span("grid.params", n=len(twins)):
        params = np.stack([tw.padded_params() for tw in twins])
        idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    names = list(names) if names is not None else [tw.name for tw in twins]

    fault = None
    if faults is not None:
        from repro.faults import (FaultSchedule, SampledFaults,
                                  expand_grid, sample_futures,
                                  validate_sampled)
        if isinstance(faults, FaultSchedule):
            sampled = sample_futures(faults, t_bins, float(bin_hours))
        elif isinstance(faults, SampledFaults):
            if faults.t_bins != t_bins:
                raise ValueError(
                    f"SampledFaults covers {faults.t_bins} bins but the "
                    f"grid has {t_bins}; resample with sample_futures("
                    f"schedule, {t_bins}, bin_hours={bin_hours})")
            sampled = faults
        else:
            raise TypeError(
                f"faults= must be a repro.faults.FaultSchedule or "
                f"SampledFaults, got {type(faults).__name__}")
        validate_sampled(sampled)
        if load_matrix is None:    # expansion needs the matrix encoding
            load_matrix = loads
            load_index = np.arange(n, dtype=np.int32)
            loads = None
        fg = expand_grid(sampled, load_matrix, load_index)
        nf = fg.n_futures
        load_matrix, load_index = fg.load_matrix, fg.load_index
        params = np.repeat(params, nf, axis=0)
        idx = np.repeat(idx, nf)
        twins = [tw for tw in twins for _ in range(nf)]
        names = [f"{nm}/f{f}" for nm in names for f in range(nf)]
        n = n * nf
        fault = (fg.cap, fg.fmask, fg.fault_index)

    if not return_series:
        slo_mode = (AGG_SLO_DROP_RATE
                    if slo is not None and slo.metric == "drop_rate"
                    else AGG_SLO_LATENCY)
        slo_limit = float(slo.limit_s) if slo is not None else float("inf")
        if load_matrix is None:        # chunk/gather via an identity map
            load_matrix, load_index = loads, np.arange(n, dtype=np.int32)
        # duplicate-scenario dedup (benign futures, tiled tournaments)
        # happens inside the dispatch — see _dedup_rows
        obs.count("grid.scenarios", n)
        with obs.span("grid.simulate", n=n, t_bins=t_bins, mode="agg",
                      devices=int(devices or 1),
                      faulted=fault is not None):
            carry_end, agg = _grid_agg_dispatch(
                load_matrix, load_index, params, idx, float(bin_hours),
                slo_limit, slo_mode, scenario_block, devices=devices,
                fault=fault)
        with obs.span("grid.summarise", n=n):
            return _summarise_aggregates(
                names, twins, carry_end[:, 0], agg, slo, cost_model,
                record_mb, float(bin_hours), t_bins, load_matrix,
                load_index)

    if loads is None:
        # series mode needs the full grid — the O(N*T) stack is the cost
        # of asking for per-bin series; aggregate mode never builds it
        loads = load_matrix[load_index]
    obs.count("grid.scenarios", n)
    with obs.span("grid.simulate", n=n, t_bins=t_bins, mode="series",
                  faulted=fault is not None):
        if fault is not None:
            caps_np = np.asarray(fault[0])[fault[2]]
            q_end, (processed, queue, latency, cost, dropped) = \
                _grid_scan_fault_xla(
                    jnp.asarray(loads), jnp.asarray(caps_np),
                    jnp.asarray(params), jnp.asarray(idx),
                    registry_version(), float(bin_hours))
        else:
            q_end, (processed, queue, latency, cost, dropped) = _grid_scan(
                jnp.asarray(loads), jnp.asarray(params), jnp.asarray(idx),
                registry_version(), float(bin_hours))
        jax.block_until_ready(q_end)
    q_end = np.asarray(q_end, np.float64)
    processed = np.asarray(processed, np.float64)
    queue = np.asarray(queue, np.float64)
    latency = np.asarray(latency, np.float64)
    cost = np.asarray(cost, np.float64)
    dropped = np.asarray(dropped, np.float64)
    return [
        _summarise(names[i], twins[i], np.asarray(loads[i], np.float64),
                   processed[i], queue[i], latency[i], cost[i], dropped[i],
                   float(q_end[i]), slo, cost_model, record_mb, bin_hours)
        for i in range(len(twins))
    ]


def simulate_year(twin: Twin, hourly_load: np.ndarray,
                  slo: Optional[SLO] = None,
                  cost_model: Optional[CostModel] = None,
                  record_mb: float = 0.0,
                  name: Optional[str] = None) -> SimulationResult:
    """Batch-of-one wrapper over ``simulate_grid`` (the seed's API)."""
    load = np.asarray(hourly_load, np.float32)
    if load.shape != (HOURS_PER_YEAR,):
        raise ValueError(f"hourly_load must cover the {HOURS_PER_YEAR}-hour "
                         f"year, got shape {load.shape}; use simulate_grid "
                         f"with bin_hours= for other horizons")
    return simulate_grid([twin], load[None], names=[name or twin.name],
                         slo=slo, cost_model=cost_model,
                         record_mb=record_mb)[0]


def _summarise(name: str, twin: Twin, load_np: np.ndarray,
               processed: np.ndarray, queue: np.ndarray, lat_np: np.ndarray,
               cost_np: np.ndarray, dropped: np.ndarray, q_end: float,
               slo: Optional[SLO], cost_model: Optional[CostModel],
               record_mb: float, bin_hours: float = 1.0) -> SimulationResult:
    backlog_s = q_end / max(twin.max_rps, 1e-9)
    backlog_cost = backlog_s / 3600.0 * twin.usd_per_hour

    # record-weighted latency stats (records arriving each hour share the
    # hour's latency estimate); p95/p99 read off the same CDF as the
    # median — the tail targets p-latency SLOs constrain
    w = load_np / max(load_np.sum(), 1e-9)
    order = np.argsort(lat_np)
    sorted_lat = lat_np[order]
    cdf = np.cumsum(w[order])
    qidx = np.minimum(np.searchsorted(cdf, (0.5, 0.95, 0.99)),
                      len(sorted_lat) - 1)
    median_lat, p95_lat, p99_lat = (float(v) for v in sorted_lat[qidx])
    mean_lat = float((lat_np * w).sum())

    pct_rec_met = pct_hours_met = 100.0
    slo_met = None
    if slo is not None:
        if slo.metric == "drop_rate":
            # hourly shed fraction vs the allowed fraction
            vals = dropped / np.maximum(load_np, 1e-9)
        else:
            vals = lat_np
        pct_rec_met, slo_met = slo.evaluate(vals, weights=load_np)
        pct_hours_met = slo.evaluate(vals)[0]

    net_cost = stor_cost = 0.0
    if cost_model is not None and record_mb > 0.0:
        # simulate_grid guarantees the hourly full-year grid here
        daily = storage_costs(load_np, cost_model, record_mb)
        net_cost = float(daily["network_usd"].sum())
        stor_cost = float(daily["storage_usd"].sum())

    return SimulationResult(
        name=name, twin=twin, load=load_np,
        processed=processed, queue=queue, latency_s=lat_np, cost_usd=cost_np,
        total_cost_usd=float(cost_np.sum() + backlog_cost),
        backlog_s=backlog_s, backlog_cost_usd=backlog_cost,
        mean_throughput_rph=float(processed.mean() / bin_hours),
        max_throughput_rph=float(processed.max() / bin_hours),
        median_latency_s=median_lat, mean_latency_s=mean_lat,
        pct_latency_met=pct_rec_met, pct_hours_met=pct_hours_met,
        slo_met=slo_met, network_cost_usd=net_cost,
        storage_cost_usd=stor_cost, dropped=dropped,
        dropped_records=float(dropped.sum()),
        p95_latency_s=p95_lat, p99_latency_s=p99_lat)


def _summarise_aggregates(names: Sequence[str], twins: Sequence[Twin],
                          q_end: np.ndarray, agg: np.ndarray,
                          slo: Optional[SLO],
                          cost_model: Optional[CostModel], record_mb: float,
                          bin_hours: float, t_bins: int,
                          load_matrix: np.ndarray,
                          load_index: np.ndarray) -> List["GridSummary"]:
    """ONE vectorized numpy pass over the [N, AGG_DIM] aggregate rows —
    the streaming replacement for the per-scenario ``_summarise`` loop.

    Twice-compensated (sum, comp, comp2) triples are recombined in f64,
    which reproduces the series path's f64 sums bit for bit at year-grid
    magnitudes; the median is read off the load-weighted latency
    histogram CDF (bucket-center representative, exact to one
    ``AGG_HIST_W``-decade bucket)."""
    n = agg.shape[0]
    tri = lambda i: agg[:, i] + agg[:, i + 1] + agg[:, i + 2]  # noqa: E731
    sum_proc, sum_cost = tri(A_PROC), tri(A_COST)
    sum_drop, sum_latw = tri(A_DROP), tri(A_LATW)
    sum_load, sum_okw = tri(A_LOAD), tri(A_OKW)
    okh, maxp = agg[:, A_OKH], agg[:, A_MAXP]
    flth, fokh = agg[:, A_FLTH], agg[:, A_FOKH]

    max_rps = np.array([tw.max_rps for tw in twins], np.float64)
    usd_hr = np.array([tw.usd_per_hour for tw in twins], np.float64)
    backlog_s = q_end / np.maximum(max_rps, 1e-9)
    backlog_cost = backlog_s / 3600.0 * usd_hr

    # device-side quantiles: first histogram bucket whose load-weighted
    # CDF crosses each target (the sort/cumsum quantiles of
    # ``_summarise``, exact to one log-spaced bucket). p95/p99 feed
    # p-latency SLO checks (repro.search) and the Table II tail columns.
    hist = agg[:, AGG_SCALARS:]
    cdf = np.cumsum(hist, axis=1)
    centers = aggregate_hist_centers()
    median, p95, p99 = (
        centers[np.argmax(cdf >= q * cdf[:, -1:], axis=1)]
        for q in (0.5, 0.95, 0.99))
    mean_lat = sum_latw / np.maximum(sum_load, 1e-9)

    if slo is not None:
        pct_rec = sum_okw / np.maximum(sum_load, 1e-12) * 100.0
        pct_hours = okh / t_bins * 100.0
        met = pct_rec >= slo.met_fraction * 100.0
    else:
        pct_rec = pct_hours = np.full(n, 100.0)
        met = None

    # fault attribution (repro.faults): in-carry counters split the
    # SLO-ok bins inside vs outside fault windows — no [N, T] series.
    # Benign grids carry flth == 0 everywhere, so both splits read 100.
    fault_hours = flth * bin_hours
    pct_in = np.where(flth > 0, fokh / np.maximum(flth, 1.0) * 100.0,
                      100.0)
    out_bins = t_bins - flth
    pct_out = np.where(out_bins > 0,
                       (okh - fokh) / np.maximum(out_bins, 1.0) * 100.0,
                       100.0)

    net = stor = np.zeros(n)
    if cost_model is not None and record_mb > 0.0:
        # per distinct load row (simulate_grid guarantees the hourly
        # full-year grid here), then spread by the index map
        daily = np.asarray(load_matrix, np.float64).reshape(
            -1, DAYS_PER_YEAR, 24).sum(axis=2)
        ingest_mb = daily * record_mb
        ret = cost_model.retention_days
        csum = np.concatenate(
            [np.zeros((len(ingest_mb), 1)), np.cumsum(ingest_mb, axis=1)],
            axis=1)
        lo = np.maximum(np.arange(DAYS_PER_YEAR) + 1 - ret, 0)
        stored_mb = csum[:, 1:] - csum[:, lo]
        net_k = ingest_mb.sum(axis=1) * cost_model.network_usd_per_mb
        stor_k = (stored_mb / 1024.0).sum(axis=1) \
            * cost_model.storage_usd_per_gb_day
        net, stor = net_k[load_index], stor_k[load_index]

    return [
        GridSummary(
            name=names[i], twin=twins[i],
            total_cost_usd=float(sum_cost[i] + backlog_cost[i]),
            backlog_s=float(backlog_s[i]),
            backlog_cost_usd=float(backlog_cost[i]),
            mean_throughput_rph=float(sum_proc[i] / t_bins / bin_hours),
            max_throughput_rph=float(maxp[i] / bin_hours),
            median_latency_s=float(median[i]),
            mean_latency_s=float(mean_lat[i]),
            pct_latency_met=float(pct_rec[i]),
            pct_hours_met=float(pct_hours[i]),
            slo_met=None if met is None else bool(met[i]),
            network_cost_usd=float(net[i]),
            storage_cost_usd=float(stor[i]),
            dropped_records=float(sum_drop[i]),
            p95_latency_s=float(p95[i]),
            p99_latency_s=float(p99[i]),
            processed_records=float(sum_proc[i]),
            arrived_records=float(sum_load[i]),
            queue_end=float(q_end[i]),
            latency_hist=hist[i],
            fault_hours=float(fault_hours[i]),
            pct_hours_met_in_fault=float(pct_in[i]),
            pct_hours_met_outside_fault=float(pct_out[i]))
        for i in range(n)
    ]


def storage_costs(hourly_load: np.ndarray, cost_model: CostModel,
                  record_mb: float) -> Dict[str, np.ndarray]:
    """Daily rolling-retention storage + network costs (Table IV)."""
    daily_records = hourly_load.reshape(DAYS_PER_YEAR, 24).sum(axis=1)
    ingest_mb = daily_records * record_mb
    ret = cost_model.retention_days
    # stored_mb[d] = sum of ingest over the trailing retention window
    csum = np.concatenate([[0.0], np.cumsum(ingest_mb)])
    lo = np.maximum(np.arange(DAYS_PER_YEAR) + 1 - ret, 0)
    stored_mb = csum[1:] - csum[lo]
    return {
        "ingest_mb": ingest_mb,
        "stored_gb": stored_mb / 1024.0,
        "network_usd": ingest_mb * cost_model.network_usd_per_mb,
        "storage_usd": stored_mb / 1024.0 * cost_model.storage_usd_per_gb_day,
    }


def monthly_table(sim: SimulationResult, cost_model: CostModel,
                  record_mb: float) -> List[Dict[str, float]]:
    """Monthly cloud/network/storage breakdown (Table IV rows)."""
    daily = storage_costs(sim.load, cost_model, record_mb)
    rows = []
    day0 = 0
    hourly_cost = sim.cost_usd
    for m, nd in enumerate(MONTH_DAYS):
        days = slice(day0, day0 + nd)
        hours = slice(day0 * 24, (day0 + nd) * 24)
        cloud = float(hourly_cost[hours].sum())
        net = float(daily["network_usd"][days].sum())
        stor = float(daily["storage_usd"][days].sum())
        rows.append({"month": m + 1, "cloud_usd": cloud, "network_usd": net,
                     "storage_usd": stor, "total_usd": cloud + net + stor})
        day0 += nd
    return rows
