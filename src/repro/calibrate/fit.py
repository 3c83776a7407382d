"""Multi-start gradient calibration of digital twins.

``fit`` matches a registered TwinPolicy's parameter vector to an
``ObservedTrace`` by differentiating through the simulation scan. All K
random restarts run as ONE dispatch — and as K *lanes* of the same
scenario-grid backend the what-if engine uses: the jitted ``_fit_kernel``
takes the [K, PARAM_DIM] stack of unconstrained starts and runs

    lax.scan over steps of  grad(lane-block loss-of-scan)  +  vmap(AdamW)

where the lane-block loss STREAMS: the running flow sums and compensated
residual accumulators ride the simulation scan's carry
(``objective.lane_series_loss`` -> ``kernels.ops.policy_scan_fold``), so
neither direction of the gradient ever materializes a [K, T] series —
the backward is the checkpointed O(sqrt(T)) custom VJP. A 32-restart
fit costs one compile and one device program, the same grid trick
``core.simulate`` plays for what-if scenarios. The optimizer is the
existing ``repro.optim`` AdamW (warmup + cosine, global-norm clip),
vmapped so each restart clips and schedules independently.
``fit(devices=D)`` shards the restart axis over a D-device mesh,
matching the single-device dispatch to a few ulps (see
``_sharded_fit_fn``).

The public surface:

* ``fit(trace, policy, ...) -> FitResult`` — best twin + per-start
  convergence table + loss history.
* ``fit_with_holdout(train, holdout, ...)`` — fit on one trace (say a
  ramp pattern), score the fitted twin on another (steady), report the
  generalization gap.
* ``calibrated_twin(result, policy=...) -> Twin`` — the measure -> fit
  entry point: an ``ExperimentResult`` (or a prebuilt trace) straight to
  a simulation-ready Twin for Table II grids.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.calibrate.objective import (DEFAULT_WEIGHTS, FitSpec, fit_spec,
                                       lane_trace_loss, params_from_z,
                                       series_loss, twin_from_z,
                                       z_from_params)
from repro.calibrate.trace import ObservedTrace, SERIES_KEYS
from repro.config import OptimizerConfig
from repro.core.twin import (PARAM_DIM, Twin, fit_twin, policy_spec,
                             registry_version)
from repro.distributed.sharding import resolve_mesh_axis
from repro.optim.adamw import adamw_update, init_opt_state

#: AdamW settings tuned for the z-space objective: no weight decay (z=0 is
#: mid-box, not a prior), generous clip, short warmup; total_steps is
#: overwritten with the fit's step count so the cosine tail anneals the
#: last iterations for tight parameter recovery.
DEFAULT_FIT_OPT = OptimizerConfig(lr=0.08, betas=(0.9, 0.95), eps=1e-8,
                                  weight_decay=0.0, grad_clip=10.0,
                                  warmup_steps=25, total_steps=400)


@dataclass
class FitResult:
    """Best fit plus the evidence: per-start convergence + loss history."""
    twin: Twin
    policy: str
    loss: float
    params: np.ndarray            # [PARAM_DIM] best-fit full vector
    spec: FitSpec
    best_start: int
    start_losses: np.ndarray      # [K] final loss per restart
    start_params: np.ndarray      # [K, PARAM_DIM] fitted params per restart
    loss_history: np.ndarray      # [steps, K]
    trace_name: str
    holdout_loss: Optional[float] = None
    holdout_name: Optional[str] = None

    @property
    def generalization_gap(self) -> Optional[float]:
        """holdout loss / train loss (1.0 = generalizes perfectly)."""
        if self.holdout_loss is None:
            return None
        return float(self.holdout_loss / max(self.loss, 1e-12))

    def restart_table(self) -> List[Dict]:
        """Per-start convergence rows for report.render_table."""
        first = self.loss_history[0] if len(self.loss_history) else \
            self.start_losses
        rows = []
        for k in range(len(self.start_losses)):
            row = {"start": k,
                   "loss0": float(first[k]),
                   "loss": float(self.start_losses[k]),
                   "converged": bool(self.start_losses[k]
                                     <= 2.0 * self.loss + 1e-9),
                   "best": k == self.best_start}
            for i, pname in enumerate(self.spec.param_names):
                if self.spec.free_mask[i]:
                    row[pname] = round(float(self.start_params[k, i]), 6)
            rows.append(row)
        return rows


def _fit_kernel_body(steps: int, dt_hours: float, version: int,
                     ocfg: OptimizerConfig, z0, arrivals, targets, scales,
                     weights, lo, hi, log_mask, free_mask, fixed,
                     policy_index):
    """K restarts, one dispatch: scan(grad(lane-block loss) + vmap(AdamW)).

    The restarts are K lanes of the shared grid backend: the loss plays
    the whole [K, PARAM_DIM] stack through ONE lane-vectorized streaming
    scan (``objective.lane_trace_loss`` -> ``kernels.ops.
    policy_scan_fold``; the traced ``policy_index`` switches in a single
    lane branch, so one jit trace serves every policy without paying the
    P-way blend), and grad of the summed per-lane losses recovers each
    restart's gradient exactly (the lanes are independent). AdamW stays
    vmapped so every restart clips and schedules on its own.

    ``steps``/``dt_hours``/``ocfg`` are static; ``version`` is the policy
    registry version so late registrations retrace (same contract as the
    grid kernel). Returns (z_best [K,D], best_loss [K], history
    [steps,K]) — the BEST-SEEN iterate per restart, not the endpoint:
    each step's in-loop loss evaluation (which the gradient needs
    anyway, so tracking it is free — the kernel never pays a separate
    full-horizon forward) updates a running per-restart argmin in the
    scan carry. Descent through the near-degenerate valleys these
    objectives develop (a fast-``max_rps``/slow-``scale_up_hours`` twin
    imitates its transpose) is not monotone, so the lowest-loss z along
    the trajectory beats wherever the cosine tail happened to freeze.
    """
    def losses(z):
        return lane_trace_loss(z, arrivals, targets, scales, weights,
                               policy_index, dt_hours, lo, hi, log_mask,
                               free_mask, fixed)

    def summed(z):
        per_lane = losses(z)
        return per_lane.sum(), per_lane

    vgrad = jax.value_and_grad(summed, has_aux=True)
    opt0 = jax.vmap(lambda z: init_opt_state({"z": z}, ocfg))(z0)
    best0 = jnp.full((z0.shape[0],), jnp.inf, jnp.float32)

    def one_step(carry, _):
        z, opt, z_best, best = carry
        (_, loss), g = vgrad(z)
        better = loss < best
        z_best = jnp.where(better[:, None], z, z_best)
        best = jnp.where(better, loss, best)

        def upd(zk, gk, ok):
            new_p, new_o = adamw_update({"z": zk}, {"z": gk}, ok, ocfg)
            return new_p["z"], new_o

        z2, opt2 = jax.vmap(upd)(z, g, opt)
        return (z2, opt2, z_best, best), loss

    (_, _, z_best, best_loss), history = jax.lax.scan(
        one_step, (z0, opt0, z0, best0), None, length=steps)
    return z_best, best_loss, history


_fit_kernel = functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))(
    _fit_kernel_body)


@functools.lru_cache(maxsize=16)
def _sharded_fit_fn(devices: int, steps: int, dt_hours: float, version: int,
                    ocfg: OptimizerConfig):
    """Build (and cache) the restart-sharded fit kernel for a D-device
    mesh. Restarts are fully independent lanes — per-restart losses,
    per-restart AdamW — so sharding the leading axis changes nothing
    about any lane's arithmetic. On CPU the results may still drift a
    few ulps from the unsharded kernel: XLA's SPMD recompilation can
    contract the loss backward's fused log-residual mul+add chains
    differently at narrow shards (baking the replicated trace operands
    in as jaxpr constants restores bitwise equality, but would force a
    recompile per trace). Parity is pinned at rtol=2e-6 in
    tests/test_stream_objectives.py."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("restart",))
    shard, rep = P("restart"), P()

    def body(z0, arrivals, targets, scales, weights, lo, hi, log_mask,
             free_mask, fixed, policy_index):
        return _fit_kernel_body(steps, dt_hours, version, ocfg, z0,
                                arrivals, targets, scales, weights, lo, hi,
                                log_mask, free_mask, fixed, policy_index)

    in_specs = (shard,) + (rep,) * 10
    out_specs = (shard, shard, P(None, "restart"))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _as_operands(trace: ObservedTrace, weights: Optional[Dict[str, float]]):
    arrivals = jnp.asarray(np.asarray(trace.arrivals, np.float32))
    targets = {k: jnp.asarray(np.asarray(v, np.float32))
               for k, v in trace.series().items()}
    scales = {k: jnp.float32(v) for k, v in trace.scales().items()}
    w = dict(DEFAULT_WEIGHTS)
    w.update(weights or {})
    w_j = {k: jnp.float32(w[k]) for k in SERIES_KEYS}
    return arrivals, targets, scales, w_j


def fit(trace: ObservedTrace, policy: str = "fifo", *,
        restarts: int = 16, steps: int = 400, seed: int = 0,
        init: Optional[Twin] = None,
        freeze: Sequence[str] = (), unfreeze: Sequence[str] = (),
        fixed_values: Optional[Dict[str, float]] = None,
        weights: Optional[Dict[str, float]] = None,
        opt: Optional[OptimizerConfig] = None,
        name: Optional[str] = None,
        devices: Optional[int] = None) -> FitResult:
    """Fit ``policy``'s parameter vector to ``trace`` by gradient descent
    through the simulation scan, from ``restarts`` random starts at once.

    Start 0 is deterministic: the ``init`` twin's parameters if given,
    else the middle of every parameter box; the rest are Gaussian in
    z-space (i.e. spread across the boxes through the sigmoid bijection).

    Scaling the fit
    ---------------
    The loss streams: its flow cumsums and residual accumulators ride the
    simulation scan's carry and the backward pass is the checkpointed
    O(sqrt(T)) VJP, so fitting a long trace holds O(K * sqrt(T)) live
    values instead of O(K * T) series. ``devices=D`` additionally shards
    the K restarts over a D-device mesh (restarts are independent lanes;
    the sharded fit matches the single-device one to a few ulps — see
    ``_sharded_fit_fn`` on why CPU SPMD recompilation keeps it from
    being bitwise); when
    K doesn't divide D the fit warns once and falls back to replication.
    On CPU, export ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
    before the first jax import to get D devices.

    **Observing the wind tunnel** (``repro.obs``). With telemetry on
    the gradient dispatch records a ``calibrate.fit`` span (attrs:
    policy, restarts, steps, t_bins, devices) and counters
    ``calibrate.fits{policy}`` / ``calibrate.restarts``; the two warn
    sites stay countable as ``warn.fit_warm_start_outside{policy}`` and
    ``warn.fit_pinned{policy}`` (the Python warnings still fire). The
    round-trip the exporters close lands here: an instrumented
    windtunnel experiment's ``stage.*`` spans export via
    ``obs.to_otel_spans`` and re-import through
    ``ObservedTrace.from_otel_spans`` as the very trace this function
    fits — the tool calibrating from its own telemetry.
    """
    spec = fit_spec(policy, freeze=freeze, unfreeze=unfreeze,
                    fixed_values=fixed_values, init=init)
    arrivals, targets, scales, w = _as_operands(trace, weights)

    rng = np.random.default_rng(seed)
    z0 = rng.normal(0.0, 1.5, (restarts, PARAM_DIM)).astype(np.float32)
    if init is not None:
        ip = init.padded_params()
        outside = [
            f"{n}={ip[i]:g} vs box ({spec.lo[i]:g}, {spec.hi[i]:g})"
            for i, n in enumerate(spec.param_names)
            if spec.free_mask[i] and not spec.lo[i] <= ip[i] <= spec.hi[i]]
        if outside:
            obs.event("warn.fit_warm_start_outside", policy=policy)
            warnings.warn(
                f"{policy} fit on trace {trace.name!r}: warm start lies "
                f"outside the calibration bounds — {'; '.join(outside)}. "
                f"The sigmoid bijection cannot reach it; widen that "
                f"parameter's bounds (register_policy(bounds=...)) or "
                f"freeze it", stacklevel=2)
        z0[0] = z_from_params(ip, spec.lo, spec.hi, spec.log_mask)
    else:
        z0[0] = 0.0          # mid-box start

    ocfg = dataclasses.replace(opt or DEFAULT_FIT_OPT, total_steps=steps)
    statics = (int(steps), float(trace.bin_hours), registry_version(), ocfg)
    operands = (jnp.asarray(z0), arrivals, targets, scales, w,
                jnp.asarray(spec.lo), jnp.asarray(spec.hi),
                jnp.asarray(spec.log_mask), jnp.asarray(spec.free_mask),
                jnp.asarray(spec.fixed),
                jnp.int32(policy_spec(policy).index))
    d = resolve_mesh_axis(devices, int(restarts),
                          "fit(devices=) restart mesh")
    obs.count("calibrate.fits", policy=policy)
    obs.count("calibrate.restarts", restarts)
    with obs.span("calibrate.fit", policy=policy, restarts=restarts,
                  steps=int(steps), t_bins=int(arrivals.shape[0]),
                  devices=int(d or 1)):
        if d is None:
            z_fin, final_loss, history = _fit_kernel(*statics, *operands)
        else:
            z_fin, final_loss, history = _sharded_fit_fn(
                d, *statics)(*operands)
        jax.block_until_ready(final_loss)

    z_fin = np.asarray(z_fin)
    final_loss = np.asarray(final_loss, np.float64)
    best = int(np.nanargmin(final_loss))
    start_params = np.stack([
        np.asarray(params_from_z(jnp.asarray(z_fin[k]), spec.lo, spec.hi,
                                 spec.log_mask, spec.free_mask, spec.fixed))
        for k in range(restarts)])
    pinned = [
        f"{n}={start_params[best, i]:g} at the "
        f"{'upper' if z_fin[best, i] > 0 else 'lower'} edge of "
        f"({spec.lo[i]:g}, {spec.hi[i]:g})"
        for i, n in enumerate(spec.param_names)
        if spec.free_mask[i] and np.isfinite(spec.hi[i])
        and abs(z_fin[best, i]) > 7.0]    # sigmoid(7) ~ 0.999
    if pinned:
        obs.event("warn.fit_pinned", policy=policy)
        warnings.warn(
            f"{policy} fit on trace {trace.name!r} pinned "
            f"{'; '.join(pinned)} — the measured pipeline likely lies "
            f"outside that parameter's box; widen the policy's bounds "
            f"(register_policy(bounds=...)) or treat the fit as a "
            f"lower/upper bound", stacklevel=2)
    twin = twin_from_z(z_fin[best], spec,
                       name or f"{trace.name}-{policy}-cal")
    return FitResult(twin=twin, policy=policy,
                     loss=float(final_loss[best]),
                     params=start_params[best], spec=spec, best_start=best,
                     start_losses=final_loss, start_params=start_params,
                     loss_history=np.asarray(history, np.float64),
                     trace_name=trace.name)


def evaluate(twin: Twin, trace: ObservedTrace,
             weights: Optional[Dict[str, float]] = None) -> float:
    """Score an existing twin against a trace with the calibration loss
    (no fitting) — the holdout metric."""
    arrivals, targets, scales, w = _as_operands(trace, weights)
    loss = series_loss(jnp.asarray(twin.padded_params()), arrivals, targets,
                       scales, w, jnp.int32(twin.policy_index),
                       float(trace.bin_hours))
    return float(loss)


def fit_with_holdout(train: ObservedTrace, holdout: ObservedTrace,
                     policy: str = "fifo", **fit_kwargs) -> FitResult:
    """Fit on one trace, validate on another (the measure-on-ramp /
    validate-on-steady workflow): the returned FitResult carries the
    holdout loss and the generalization gap. Extra kwargs — ``devices=D``
    included — forward to ``fit``."""
    result = fit(train, policy, **fit_kwargs)
    result.holdout_loss = evaluate(
        result.twin, holdout, weights=fit_kwargs.get("weights"))
    result.holdout_name = holdout.name
    return result


def calibrated_twin(source: Union[ObservedTrace, "ExperimentResult"],
                    policy: str = "fifo", *, bin_s: float = 1.0,
                    name: Optional[str] = None,
                    **fit_kwargs) -> Twin:
    """Measured pipeline -> simulation-ready Twin, in one call.

    ``source`` is an ``ExperimentResult`` (binned into a trace at
    ``bin_s``-second resolution, with the paper's closed-form fit as the
    warm start) or a prebuilt ``ObservedTrace``. Extra kwargs forward to
    ``fit`` (``devices=D`` shards the restarts over a device mesh). Use
    ``fit()`` directly when you want the convergence table.
    """
    if isinstance(source, ObservedTrace):
        trace = source
    else:
        trace = ObservedTrace.from_experiment(source, bin_s=bin_s)
        if "init" not in fit_kwargs:
            try:
                fit_kwargs["init"] = fit_twin(source, policy)
            except (KeyError, AttributeError):
                fit_kwargs["init"] = None
    result = fit(trace, policy, name=name, **fit_kwargs)
    return result.twin
