"""Digital twins (paper Sec. V-G) as a unified TwinPolicy architecture.

A twin is explainable pipeline model fit from wind-tunnel experiments and
applied to traffic projections by the simulator. Where the paper ships two
hard-coded models (fixed-capacity FIFO and optimal quickscaling), here a
twin is a ``Twin`` record carrying a *policy name* plus a *flat parameter
vector*, and every policy is a pure hour-step function

    step(carry, arrive, params) -> (carry, (processed, queue, latency,
                                            cost, dropped))

registered in a module-level table. The simulator selects the step inside
its ``jax.lax.scan`` with ``jax.lax.switch``, so every (twin x traffic)
scenario of a what-if grid — regardless of policy mix — runs through ONE
vmapped scan kernel (see core/simulate.py). New scaling/queueing policies
are added by registering a step function; the kernel never changes.

Steps are *bin-width aware*: the canonical signature is

    step(carry, arrive, params, dt) -> (carry, (processed, queue, latency,
                                                cost, dropped))

where ``dt`` is the bin width in hours (1.0 for the year simulation;
sub-hour for calibration traces). Legacy three-argument steps registered
before the dt generalization are wrapped automatically and simply ignore
``dt`` — at dt=1.0 every built-in reduces bit-identically to its PR 1 form.

Every policy exists in TWO interchangeable step forms:

* the scalar form above — dispatched per scenario with ``jax.lax.switch``
  inside the XLA grid kernel (``core/simulate.py``), the parity anchor;
* a *branchless, lane-vectorized* form

      lane_step(carry [LANES, CARRY_DIM], arrive [LANES],
                params [LANES, PARAM_DIM], dt) -> (carry, outs)

  — pure masked ``jnp`` math over a block of LANES scenarios at once,
  with each of the five outputs shaped [LANES]. The built-ins hand-write
  this form (so it lowers to straight-line VPU vector code inside the
  Pallas scenario-grid kernel, ``kernels/policy_scan.py``); policies
  registered without one get it derived automatically via ``jax.vmap`` of
  their scalar step. At registration the registry *asserts both forms
  agree* on a random block, so the two backends cannot drift.

``lane_policy_step(carry, arrive, params, policy_onehot, dt)`` is the
combined branchless step over a mixed-policy lane block: every registered
policy is evaluated on every lane and the results blended with the
[LANES, P] one-hot policy mask — exactly what ``vmap`` of ``lax.switch``
lowers to, and the form the Pallas kernel scans over all T bins with
scenarios on the vector lanes.

Next to the policy steps live the *streaming-aggregate hooks* (AGG_*
constants, ``update_agg_scalars`` / ``lane_update_aggregate`` /
``np_latency_histogram``): the carry extension that lets the grid
backends fold the Table II summary statistics into the scan instead of
materializing [N, T] series — see the section comment below and
``core/simulate.py``.

Each registered policy also declares *calibration metadata*: a per-parameter
``bounds`` box, the subset optimized in log-space (``log_params``), and the
params ``frozen`` by default during gradient fitting (operator-chosen knobs
like instance bounds). ``repro.calibrate`` uses this to reparameterize the
flat vector onto the bounds with a sigmoid/softplus bijection and fit it to
an observed trace by differentiating through the simulation scan.

Shared convention: ``params[0:3] = (max_rps, usd_per_hour, base_latency_s)``
for every policy; extra parameters follow, zero-padded to ``PARAM_DIM``.
The scan carry is a ``CARRY_DIM``-vector: slot 0 holds queued/accumulated
records, slot 1 holds policy state (autoscale's live instance count,
batch_window's hours-since-flush).

Built-in policies
-----------------
fifo          — fixed capacity, fixed $/hr, FIFO infinite queue (the
                paper's proof-of-concept model, Table I).
quickscale    — optimal horizontal scaling: no queueing; cost scales with
                ceil(load / capacity) instances.
autoscale     — beyond-paper: horizontal scaling with a scale-up delay and
                min/max instance bounds — the autoscaling-delay /
                overprovisioning cost levers of Jablonski & Heltweg.
shed          — beyond-paper: bounded queue with load shedding; excess
                records are dropped and reported per hour.
batch_window  — beyond-paper: accumulate-then-flush batching; pay mostly
                for compute actually used (plus a keep-warm fraction) at
                the price of half-a-window average latency.

``SimpleTwin`` / ``QuickscalingTwin`` remain as constructor aliases that
build the equivalent ``Twin``, and ``roofline_twin`` still derives capacity
analytically from compiled dry-run roofline terms (launch/roofline.py), so
cost/performance can be forecast before a pipeline is ever run at scale.
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.experiment import ExperimentResult

CARRY_DIM = 2     # [queued/accumulated records, policy state]
PARAM_DIM = 6     # flat parameter vector, zero-padded per policy

# calibration boxes for the shared triple; extras declare their own via
# register_policy(bounds=...) or inherit the generic positive box below
SHARED_BOUNDS: Dict[str, Tuple[float, float]] = {
    "max_rps": (1e-2, 1e3),
    "usd_per_hour": (1e-4, 10.0),
    "base_latency_s": (1e-2, 100.0),
}
GENERIC_BOUNDS: Tuple[float, float] = (1e-3, 1e3)
SHARED_LOG = ("max_rps", "usd_per_hour", "base_latency_s")


@dataclass(frozen=True)
class PolicySpec:
    """One registered scaling/queueing policy."""
    name: str
    index: int                       # lax.switch branch index (stable)
    step: Callable                   # (carry, arrive, params, dt) -> (carry, out)
    param_names: Tuple[str, ...]     # layout of the flat param vector
    defaults: Dict[str, float]
    doc: str
    # calibration metadata (repro.calibrate)
    bounds: Dict[str, Tuple[float, float]] = None
    log_params: Tuple[str, ...] = ()
    frozen: Tuple[str, ...] = ()
    # branchless lane-vectorized form of ``step`` (see module docstring):
    # (carry [L, CARRY_DIM], arrive [L], params [L, PARAM_DIM], dt)
    lane_step: Callable = None
    # --- differentiability audit (repro.search) ---------------------------
    # parameters the EXACT step hard-gates on (ceil / >= comparisons whose
    # gradient is zero or undefined): a gradient-based policy search cannot
    # move these through ``lane_step``. Policies flagging any must supply a
    # ``surrogate_lane_step`` — same signature and lane semantics as
    # ``lane_step`` but with the hard gates smoothed (fluid instance
    # counts, sigmoid flush gates), so ``d(output)/d(param)`` is nonzero.
    # The surrogate is ONLY used for gradients (repro.search's inner loop);
    # every reported number still comes from the exact step.
    nondiff_params: Tuple[str, ...] = ()
    surrogate_lane_step: Callable = None

    def bound(self, pname: str) -> Tuple[float, float]:
        return (self.bounds or {}).get(pname, GENERIC_BOUNDS)


_REGISTRY: Dict[str, PolicySpec] = {}
_VERSION = 0    # bumped on registration; a static jit arg, so the grid
                # kernel retraces when a new policy is registered late


def _accepts_dt(fn: Callable) -> bool:
    """True if ``fn`` already takes the (carry, arrive, params, dt) form."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):       # builtins etc. — assume modern
        return True
    kinds = [p.kind for p in sig.parameters.values()]
    if any(k == inspect.Parameter.VAR_POSITIONAL for k in kinds):
        return True
    pos = [k for k in kinds if k in (inspect.Parameter.POSITIONAL_ONLY,
                                     inspect.Parameter.POSITIONAL_OR_KEYWORD)]
    return len(pos) >= 4


def _derived_lane_step(step: Callable) -> Callable:
    """Lane-vectorize a scalar step with ``jax.vmap`` (the fallback for
    policies registered without a hand-written lane form)."""
    import jax
    return jax.vmap(step, in_axes=(0, 0, 0, None))


def _assert_lane_parity(name: str, step: Callable, lane_step: Callable,
                        lanes: int = 4, seed: int = 0):
    """Registry invariant: the scalar and lane-vectorized forms of a policy
    agree on a random block of scenarios. Runs eagerly at registration so a
    hand-written lane step cannot drift from the ``lax.switch`` form."""
    rng = np.random.default_rng(seed)
    carry = jnp.asarray(rng.uniform(0.0, 50.0, (lanes, CARRY_DIM)),
                        jnp.float32)
    arrive = jnp.asarray(rng.uniform(0.0, 2e4, (lanes,)), jnp.float32)
    params = jnp.asarray(rng.uniform(0.05, 8.0, (lanes, PARAM_DIM)),
                         jnp.float32)
    for dt in (1.0, 1.0 / 60.0):
        dt = jnp.float32(dt)
        c_lane, o_lane = lane_step(carry, arrive, params, dt)
        for lane in range(lanes):
            c_s, o_s = step(carry[lane], arrive[lane], params[lane], dt)
            np.testing.assert_allclose(
                np.asarray(c_lane[lane]), np.asarray(c_s), rtol=1e-5,
                atol=1e-5, err_msg=f"{name}: lane/scalar carry mismatch")
            for k, (ol, os_) in enumerate(zip(o_lane, o_s)):
                np.testing.assert_allclose(
                    np.asarray(ol[lane]), np.asarray(os_), rtol=1e-5,
                    atol=1e-5,
                    err_msg=f"{name}: lane/scalar output {k} mismatch")


def _assert_surrogate_sane(name: str, surrogate: Callable, lanes: int = 4,
                           seed: int = 1):
    """Registry invariant for surrogate steps: finite outputs and finite
    parameter gradients on a random lane block (both bin widths). The
    surrogate is a gradient guide, not a parity target, so closeness to
    the exact step is NOT asserted — only that grads exist to follow."""
    rng = np.random.default_rng(seed)
    carry = jnp.asarray(rng.uniform(0.0, 50.0, (lanes, CARRY_DIM)),
                        jnp.float32)
    arrive = jnp.asarray(rng.uniform(0.0, 2e4, (lanes,)), jnp.float32)
    params = jnp.asarray(rng.uniform(0.05, 8.0, (lanes, PARAM_DIM)),
                         jnp.float32)

    def total(p, dt):
        c, outs = surrogate(carry, arrive, p, dt)
        return sum(jnp.sum(o) for o in outs) + jnp.sum(c)

    for dt in (1.0, 1.0 / 60.0):
        val = total(params, jnp.float32(dt))
        g = jax.grad(total)(params, jnp.float32(dt))
        if not (np.isfinite(float(val)) and np.all(np.isfinite(g))):
            raise AssertionError(
                f"{name}: surrogate step produced non-finite output or "
                f"gradient at dt={dt}")


def register_policy(name: str, param_names: Tuple[str, ...],
                    defaults: Optional[Dict[str, float]] = None,
                    doc: str = "",
                    bounds: Optional[Dict[str, Tuple[float, float]]] = None,
                    log_params: Optional[Tuple[str, ...]] = None,
                    frozen: Tuple[str, ...] = (),
                    lane_step: Optional[Callable] = None,
                    nondiff_params: Tuple[str, ...] = (),
                    surrogate_lane_step: Optional[Callable] = None):
    """Decorator: register ``fn(carry, arrive, params, dt)`` as ``name``.

    ``param_names`` must start with the shared triple
    (max_rps, usd_per_hour, base_latency_s) and fit within PARAM_DIM.
    Legacy ``fn(carry, arrive, params)`` steps are wrapped to ignore the
    bin width ``dt`` (they then only simulate correctly at dt=1 hour).

    ``bounds`` / ``log_params`` / ``frozen`` declare calibration metadata:
    the fit box per parameter (shared-triple boxes are filled in), which
    parameters are fit in log-space, and which are held fixed by default.

    ``lane_step`` optionally supplies the branchless lane-vectorized form
    (see module docstring); omitted, it is derived with ``jax.vmap``.
    Either way the registry asserts the two forms agree on a random block
    before the policy becomes visible.

    ``nondiff_params`` flags parameters the exact step hard-gates on
    (zero-gradient through ceil / comparisons); flagging any requires a
    ``surrogate_lane_step`` whose gates are smoothed so ``repro.search``
    can take gradients w.r.t. them. Policies with no hard gates leave both
    unset and the exact lane step doubles as its own surrogate.
    """
    if len(param_names) > PARAM_DIM:
        raise ValueError(f"{name}: {len(param_names)} params > {PARAM_DIM}")
    if tuple(param_names[:3]) != ("max_rps", "usd_per_hour",
                                  "base_latency_s"):
        raise ValueError(f"{name}: params must start with the shared triple")
    full_bounds = dict(SHARED_BOUNDS)
    full_bounds.update(bounds or {})
    logp = tuple(log_params) if log_params is not None else tuple(
        p for p in param_names if p in SHARED_LOG)

    def deco(fn):
        global _VERSION
        step = fn if _accepts_dt(fn) else (
            lambda carry, arrive, p, dt, _fn=fn: _fn(carry, arrive, p))
        lstep = lane_step or _derived_lane_step(step)
        _assert_lane_parity(name, step, lstep)
        unknown_nd = set(nondiff_params) - set(param_names)
        if unknown_nd:
            raise ValueError(f"{name}: nondiff_params {sorted(unknown_nd)} "
                             f"not in param_names")
        if nondiff_params and surrogate_lane_step is None:
            raise ValueError(
                f"{name}: flags hard-gated params {list(nondiff_params)} "
                f"but supplies no surrogate_lane_step — gradient search "
                f"over them would silently see zero gradients")
        sstep = surrogate_lane_step or lstep
        _assert_surrogate_sane(name, sstep)
        # overriding an existing policy keeps its switch index so twins
        # built earlier still dispatch to the right branch slot
        prev = _REGISTRY.get(name)
        spec = PolicySpec(name=name,
                          index=prev.index if prev else len(_REGISTRY),
                          step=step,
                          param_names=tuple(param_names),
                          defaults=dict(defaults or {}),
                          doc=doc or (fn.__doc__ or "").strip(),
                          bounds=full_bounds,
                          log_params=logp,
                          frozen=tuple(frozen),
                          lane_step=lstep,
                          nondiff_params=tuple(nondiff_params),
                          surrogate_lane_step=sstep)
        _REGISTRY[name] = spec
        _VERSION += 1
        return fn
    return deco


def policy_spec(name: str) -> PolicySpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown twin policy {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def policy_names() -> List[str]:
    return [s.name for s in sorted(_REGISTRY.values(), key=lambda s: s.index)]


def policy_branches() -> Tuple[Callable, ...]:
    """Step functions ordered by switch index (the kernel's branch table)."""
    return tuple(s.step for s in
                 sorted(_REGISTRY.values(), key=lambda s: s.index))


def lane_branches() -> Tuple[Callable, ...]:
    """Lane-vectorized step functions ordered by switch index."""
    return tuple(s.lane_step for s in
                 sorted(_REGISTRY.values(), key=lambda s: s.index))


def surrogate_lane_branches() -> Tuple[Callable, ...]:
    """Smooth-surrogate lane steps ordered by switch index — the branch
    table gradient-based policy search scans (``repro.search``). Policies
    without hard gates reuse their exact lane step here."""
    return tuple(s.surrogate_lane_step for s in
                 sorted(_REGISTRY.values(), key=lambda s: s.index))


def num_policies() -> int:
    return len(_REGISTRY)


def policy_onehot(policy_idx) -> np.ndarray:
    """[N, P] f32 one-hot mask from [N] switch indices — the lane form's
    branch selector (P = number of registered policies)."""
    idx = np.asarray(policy_idx, np.int32)
    return (idx[:, None] == np.arange(num_policies())[None, :]).astype(
        np.float32)


def lane_policy_step(carry, arrive, params, onehot, dt, branches=None):
    """The combined branchless bin-step over a mixed-policy lane block.

    carry [L, CARRY_DIM]; arrive [L]; params [L, PARAM_DIM];
    onehot [L, P] selects each lane's policy. Every registered policy is
    evaluated on every lane (pure vector math, no control flow) and the
    results blended with the one-hot mask — a masked sum is exact in f32
    (1*x + 0*y == x), so this matches the ``lax.switch`` form bit for bit
    as long as every branch stays finite on foreign parameter vectors
    (a registry invariant checked at registration). This is the step the
    Pallas scenario-grid kernel scans over all T bins with scenarios on
    the vector lanes (``kernels/policy_scan.py``).

    ``branches`` overrides the branch table (default: the exact lane
    steps) — ``repro.search`` passes ``surrogate_lane_branches()``.
    """
    new_carry = jnp.zeros_like(carry)
    outs = [jnp.zeros_like(arrive) for _ in range(5)]
    for j, lstep in enumerate(branches or lane_branches()):
        c_j, o_j = lstep(carry, arrive, params, dt)
        m = onehot[:, j]
        new_carry = new_carry + m[:, None] * c_j
        outs = [acc + m * o for acc, o in zip(outs, o_j)]
    return new_carry, tuple(outs)


def fault_lane_policy_step(state, arrive, capmul, params, onehot, dt,
                           branches=None):
    """``lane_policy_step`` wrapped in the fault perturbation layer.

    ``state`` = (policy carry [L, CARRY_DIM], fault backlog ``fq`` [L]);
    ``capmul`` [L] is this bin's capacity multiplier from the fault
    schedule. The layer sits *outside* the policy step, so every
    registered policy composes with every fault kind unchanged:

    * capacity scales: the policy sees ``max_rps * capmul`` (brownouts);
    * hard outage (``capmul == 0``) gates arrivals into a fault-layer
      backlog queue instead of the policy — the policy drains its own
      queue and then idles, rather than autoscaling against a dead
      pipeline. When capacity returns the whole backlog re-enters in one
      bin: the reconnect flood, conserving every record;
    * backlog waiting time is priced into reported latency at NOMINAL
      capacity (``fq / max_rps``) — a deliberate lower bound that keeps
      outage latencies finite instead of dividing by a zeroed rate.

    The benign bin (``capmul == 1``, ``fq == 0``) is IEEE-exact identity:
    ``1 * (0 + arrive) == arrive``, ``max_rps * 1.0 == max_rps``, and
    ``lat + 0.0 == lat``, so an all-ones capacity series is bit-identical
    to the unwrapped step — the structural guarantee behind the
    empty-schedule parity tests.
    """
    carry, fq = state
    gate = (capmul > 0).astype(jnp.float32)
    avail = fq + arrive
    a_eff = gate * avail
    new_fq = avail - a_eff
    p_eff = jnp.concatenate([(params[:, 0] * capmul)[:, None],
                             params[:, 1:]], axis=1)
    carry, outs = lane_policy_step(carry, a_eff, p_eff, onehot, dt,
                                   branches=branches)
    wait = new_fq / jnp.maximum(params[:, 0], jnp.float32(1e-9))
    outs = (outs[0], outs[1] + new_fq, outs[2] + wait, outs[3], outs[4])
    return (carry, new_fq), outs


def registry_version() -> int:
    return _VERSION


# ---------------------------------------------------------------------------
# Streaming aggregates (the O(N)-memory grid backend's carry extension)
# ---------------------------------------------------------------------------
#
# The what-if tables (core/whatif.table2_rows) consume only per-scenario
# *scalars*, so the streaming grid backend folds the Table II statistics
# into the scan carry instead of materializing five [N, T] series:
#
# * running sums of processed / cost / dropped / latency*load / load and
#   the load-weighted SLO-ok mass, each carried as a twice-compensated
#   (sum, comp, comp2) f32 triple (cascaded Neumaier: the exact two-sum
#   residual stream is itself compensated) — recombined in f64 on the
#   host the triple reproduces numpy's f64 series sum bit for bit at
#   year-grid magnitudes, so aggregate totals match the series-path
#   ``_summarise`` exactly;
# * the per-bin max throughput and the count of SLO-ok bins (exact in f32);
# * a fixed-width load-weighted latency histogram over AGG_HIST_BINS
#   quarter-octave buckets — the device-side replacement for the numpy
#   sort/cumsum median in ``_summarise`` (quantiles read off the bucket
#   CDF are exact to one bucket width, ``AGG_HIST_W`` decades). Bucket
#   keys come straight from the f32 exponent + top mantissa bits
#   (``_hist_bucket`` / ``np_hist_bucket``: bitcast, shift, clip — no
#   transcendentals), so every backend computes the identical integer
#   bucket for every latency value.
#
# In the scan the aggregate state is an UNPACKED pytree (a tuple of
# per-statistic arrays, ``init_aggregate``) rather than one packed
# [AGG_DIM] vector: per-bin updates are then pure elementwise arithmetic
# with no gather/stack/update-slice in the hot loop (~5x on the CPU
# backend). ``pack_aggregate`` flattens the state into the [.., AGG_DIM]
# slot layout once per scan (or per Pallas time chunk, where the packed
# form is what persists in VMEM scratch — ``unpack_aggregate`` restores
# the pytree at chunk entry).
#
# The histogram has two backend-appropriate DEVICE-RESIDENT
# realizations, both bit-identical to the host reference
# (``np_latency_histogram``, kept as the parity oracle):
#
# * ``lane_update_aggregate`` — the branchless lane form the Pallas
#   kernel (and the jnp lane oracle) runs: a masked compare-add over the
#   bucket axis, resident in VMEM scratch, O(N) end to end. Each bucket
#   column is a twice-compensated (sum, comp, comp2) triple — the same
#   scheme the scalar sums use — recombined in f64 once per scan
#   (``finalize_aggregate``), which reproduces numpy's per-row f64
#   ``np.bincount`` bit for bit;
# * the XLA switch-scan backend keeps only the scalar statistics in the
#   scan carry and folds each staged time-chunk of latencies through
#   ``device_latency_histogram`` — a flat f64 ``segment_sum`` over
#   (scenario, bucket) ids *outside* the scan carry (a per-step
#   [N, BINS] carry costs ~0.5 s per 1k scenarios in scan
#   double-buffering alone). The f64 adds are exact at year-grid
#   magnitudes, so the chunked accumulation is order-independent and
#   matches ``np.bincount`` bitwise with no host round-trip.

AGG_HIST_BINS = 152            # quarter-octave latency buckets
#: smallest resolvable latency: 2^-10 s ~ 0.98 ms (bucket 0 clips below)
AGG_HIST_MIN_EXP = -10
AGG_HIST_MIN = float(2.0 ** AGG_HIST_MIN_EXP)
#: (biased exponent | 2-bit mantissa) key of AGG_HIST_MIN — bucket 0
_AGG_HIST_KEY0 = (127 + AGG_HIST_MIN_EXP) << 2
#: bucket width in decades: a quarter octave (top edge 2^28 s ~ 8.5 yr)
AGG_HIST_W = float(np.log10(2.0) / 4.0)

# scalar slot layout: (sum, comp, comp2) triples first, then exact slots
A_PROC = 0                     # sum of processed records
A_COST = 3                     # sum of cost_usd
A_DROP = 6                     # sum of dropped records
A_LATW = 9                     # sum of latency * load (record-weighted)
A_LOAD = 12                    # sum of load
A_OKW = 15                     # sum of load in SLO-ok bins
A_OKH = 18                     # count of SLO-ok bins
A_MAXP = 19                    # max processed per bin
A_FLTH = 20                    # count of bins inside a fault window
A_FOKH = 21                    # count of SLO-ok bins inside fault windows
AGG_SCALARS = 22
AGG_DIM = AGG_SCALARS + AGG_HIST_BINS
#: kernel-internal packed width: each histogram bucket is a
#: twice-compensated (sum, comp, comp2) triple until ``finalize_aggregate``
AGG_KDIM = AGG_SCALARS + 3 * AGG_HIST_BINS

#: SLO metric selector for the aggregate scan (a static trace argument)
AGG_SLO_LATENCY, AGG_SLO_DROP_RATE = 0, 1


def aggregate_hist_edges() -> np.ndarray:
    """[AGG_HIST_BINS + 1] bucket edges in seconds (quarter-octave)."""
    return np.power(2.0, AGG_HIST_MIN_EXP
                    + np.arange(AGG_HIST_BINS + 1) / 4.0)


def aggregate_hist_centers() -> np.ndarray:
    """[AGG_HIST_BINS] geometric bucket centers in seconds — the
    representative values quantiles read off the histogram CDF."""
    return np.power(2.0, AGG_HIST_MIN_EXP
                    + (np.arange(AGG_HIST_BINS) + 0.5) / 4.0)


def _two_sum(a, b):
    """Branch-free Knuth two-sum: (fl(a+b), exact residual)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _neumaier2(s, c, cc, x):
    """One twice-compensated summation step: (sum, comp, comp2) += x.
    The per-step two-sum residual is EXACT; its running sum is itself
    compensated into (c, cc), so ``s + c + cc`` recombined in f64 on the
    host matches numpy's f64 sum of the same f32 terms bit for bit at
    the magnitudes the year grids produce (verified against the series
    path in tests/test_grid_aggregate.py)."""
    s, e = _two_sum(s, x)
    c, ee = _two_sum(c, e)
    return s, c, cc + ee


def fold_triple_init(shape) -> tuple:
    """Fresh twice-compensated accumulator triple ``(sum, comp, comp2)``
    of f32 zeros — the differentiable AGG hook the streaming gradient
    objectives fold into their scan carry (search cost / compliance /
    violation sums, calibrate residual sums)."""
    z = jnp.zeros(shape, jnp.float32)
    return z, z, z


@jax.custom_jvp
def fold_triple_add(triple: tuple, x) -> tuple:
    """One differentiable compensated accumulation step: triple += x.

    Every two-sum residual channel is *symbolically* zero in exact
    arithmetic (``e = (a - (s - bb)) + (b - bb)`` with ``s = a + b``,
    ``bb = s - a`` has ``de/da = de/db = 0``), and because autodiff's
    chain coefficients through those wires are exact 0/1 constants, the
    gradient of a compensated fold is BITWISE the gradient of the plain
    sum. The custom JVP states that directly — tangents ride the plain
    ``s + x`` channel — so the O(sqrt(T)) segment replays of the
    streaming objectives don't drag three dead two-sum transposes per
    accumulator per bin through the backward (measurably faster at the
    search kernel's small lane counts, identical numbers)."""
    s, c, cc = triple
    return _neumaier2(s, c, cc, x)


@fold_triple_add.defjvp
def _fold_triple_add_jvp(primals, tangents):
    triple, x = primals
    (ds, dc, dcc), dx = tangents
    return fold_triple_add(triple, x), (ds + dx, dc, dcc)


def fold_triple_finalize(triple: tuple) -> jnp.ndarray:
    """Recombine ``(sum, comp, comp2) -> sum + comp + comp2`` in f64,
    cast back to f32 — the PR 4 trick that makes the streamed value
    match an f64 accumulation of the same f32 terms. Under a plain-f32
    trace (the search/fit kernels) the f64 cast is a no-op and the
    recombination is a deterministic pair of f32 adds; either way the
    result is bit-identical between any two paths that share this code."""
    s, c, cc = triple
    # canonicalize: f64 only when x64 is enabled (avoids the truncation
    # UserWarning on every plain-f32 trace; the numbers are identical)
    acc_t = jax.dtypes.canonicalize_dtype(jnp.float64)
    return (s.astype(acc_t) + c + cc).astype(jnp.float32)


def _hist_bucket(latency):
    """Bucket index on the fixed quarter-octave grid, from the f32 bit
    pattern: (exponent | top 2 mantissa bits) rebased to AGG_HIST_MIN.
    Integer-exact and backend-independent (``np_hist_bucket`` is the
    bit-identical numpy twin)."""
    lat = jnp.maximum(latency, jnp.float32(AGG_HIST_MIN))
    bits = jax.lax.bitcast_convert_type(lat, jnp.int32)
    return jnp.clip((bits >> 21) - _AGG_HIST_KEY0, 0, AGG_HIST_BINS - 1)


def np_hist_bucket(latency: np.ndarray) -> np.ndarray:
    """Numpy twin of ``_hist_bucket`` — same bits, same buckets (one
    temporary, then in-place int ops: this sits on the streaming grid's
    per-block hot path)."""
    buf = np.maximum(np.ascontiguousarray(latency, np.float32),
                     np.float32(AGG_HIST_MIN))
    bits = buf.view(np.int32)
    np.right_shift(bits, 21, out=bits)
    bits -= _AGG_HIST_KEY0
    np.clip(bits, 0, AGG_HIST_BINS - 1, out=bits)
    return bits


def np_latency_histogram(latency: np.ndarray, weights: np.ndarray,
                         weight_rows: np.ndarray = None) -> np.ndarray:
    """[N, T] latencies + [N, T] weights -> [N, AGG_HIST_BINS] f32
    load-weighted histogram (one ``np.bincount`` per scenario, f64
    accumulation per row). The host half of the XLA aggregate backend.

    With ``weight_rows`` [N], ``weights`` is instead the [K, T] distinct
    load matrix and row i weighs by ``weights[weight_rows[i]]`` — the
    grid engine's blocks repeat a few matrix rows thousands of times, so
    this form skips the [N, T] gather AND hands bincount pre-converted
    f64 row views instead of a fresh f32->f64 copy per scenario.
    Bit-identical to the gathered form (the f64 conversion is exact and
    the accumulation order is unchanged)."""
    buckets = np_hist_bucket(latency)
    n = buckets.shape[0]
    out = np.empty((n, AGG_HIST_BINS), np.float32)
    if weight_rows is None:
        for i in range(n):
            out[i] = np.bincount(buckets[i], weights=weights[i],
                                 minlength=AGG_HIST_BINS)
    else:
        w64 = np.ascontiguousarray(weights, np.float64)
        for i in range(n):
            out[i] = np.bincount(buckets[i],
                                 weights=w64[weight_rows[i]],
                                 minlength=AGG_HIST_BINS)
    return out


def device_latency_histogram(latency, weights):
    """[N, C] latencies + [N, C] weights -> [N, AGG_HIST_BINS] f64
    load-weighted histogram, entirely on device: bucket ids from the f32
    bit pattern (``_hist_bucket``), then ONE flat ``segment_sum`` over
    (scenario * AGG_HIST_BINS + bucket) ids in f64.

    MUST be traced under ``jax.enable_x64(True)`` — outside it
    the f64 cast silently truncates to f32 and bit-parity with
    ``np_latency_histogram`` is lost. The f64 adds are exact at the
    magnitudes year grids produce (bucket sums need ~35-51 bits < 53),
    so the result is order-independent: accumulating per time chunk and
    adding the chunk histograms reproduces numpy's per-row f64
    ``np.bincount`` of the full series bit for bit."""
    n = latency.shape[0]
    seg = (jax.lax.broadcasted_iota(jnp.int32, latency.shape, 0)
           * AGG_HIST_BINS + _hist_bucket(latency))
    return jax.ops.segment_sum(
        weights.astype(jnp.float64).reshape(-1), seg.reshape(-1),
        num_segments=n * AGG_HIST_BINS).reshape(n, AGG_HIST_BINS)


def init_agg_scalars(shape=()):
    """Zeroed scalar-statistic state: (sums tuple[18], okh, maxp, flth,
    fokh), every leaf ``shape``-shaped (scalar under the vmapped switch
    path, [L] for a lane block)."""
    z = jnp.zeros(shape, jnp.float32)
    return ((z,) * 18, z, z, z, z)


def update_agg_scalars(state, arrive, outs, slo_limit, slo_mode,
                       fmask=None):
    """Fold one bin's step outputs into the scalar statistics (shared by
    every backend; elementwise, shape-polymorphic). ``slo_limit`` (float)
    and ``slo_mode`` (AGG_SLO_*) are static trace constants — pass
    ``inf`` / latency when no SLO applies.

    ``fmask`` (0/1, same shape as ``arrive``) marks bins inside a fault
    window; it drives the exact fault-attribution counters (A_FLTH /
    A_FOKH — integer counts, exact in f32). ``None`` (the benign path)
    leaves both counters untouched, so faulted and benign traces share
    one code path bit for bit."""
    sums, okh, maxp, flth, fokh = state
    processed, _queue, latency, cost, dropped = outs
    if slo_mode == AGG_SLO_DROP_RATE:
        val = dropped / jnp.maximum(arrive, jnp.float32(1e-9))
    else:
        val = latency
    ok = (val <= jnp.float32(slo_limit)).astype(jnp.float32)
    new = []
    # term order IS the slot order: A_PROC, A_COST, A_DROP, A_LATW,
    # A_LOAD, A_OKW (each a (sum, comp, comp2) triple)
    for j, x in enumerate((processed, cost, dropped, latency * arrive,
                           arrive, arrive * ok)):
        new += _neumaier2(sums[3 * j], sums[3 * j + 1], sums[3 * j + 2], x)
    if fmask is not None:
        flth = flth + fmask
        fokh = fokh + fmask * ok
    return (tuple(new), okh + ok, jnp.maximum(maxp, processed), flth, fokh)


def pack_agg_scalars(state) -> jnp.ndarray:
    """[..., AGG_SCALARS] slot layout of a scalar-statistic state."""
    sums, okh, maxp, flth, fokh = state
    return jnp.stack(tuple(sums) + (okh, maxp, flth, fokh), axis=-1)


def init_aggregate(shape=()):
    """Zeroed FULL aggregate state (scalars + histogram) for the lane
    backends: (scalar state, hist triple of [*shape, AGG_HIST_BINS] —
    per-bucket (sum, comp, comp2) compensated columns)."""
    z = jnp.zeros(tuple(shape) + (AGG_HIST_BINS,), jnp.float32)
    return (init_agg_scalars(shape), (z, z, z))


def lane_update_aggregate(state, arrive, outs, slo_limit, slo_mode,
                          fmask=None):
    """Fold one bin into the full aggregate state — branchless lane form.

    ``state`` = (scalar state with [L] leaves, hist triple of
    [L, AGG_HIST_BINS]); arrive [L]; outs five [L] vectors. Scalars via
    the shared ``update_agg_scalars``; the histogram is a masked
    compare-add over the bucket axis (no scatter) folded through the
    same twice-compensated ``_neumaier2`` step the scalar sums use, so
    the Pallas kernel runs it as straight-line VPU vector math with
    everything resident in VMEM and ``finalize_aggregate`` recovers the
    exact f64 bucket sums. ``fmask`` [L] (optional) feeds the
    fault-attribution counters."""
    scal, (hs, hc, hcc) = state
    scal = update_agg_scalars(scal, arrive, outs, slo_limit, slo_mode,
                              fmask)
    bucket = _hist_bucket(outs[2])
    lanes = bucket.shape[0]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (lanes, AGG_HIST_BINS), 1)
    x = jnp.where(bucket[:, None] == buckets, arrive[:, None],
                  jnp.float32(0.0))
    return (scal, _neumaier2(hs, hc, hcc, x))


def pack_aggregate(state) -> jnp.ndarray:
    """Flatten a full aggregate state into the [..., AGG_KDIM] slot
    layout (scalars, then the three histogram planes; done once per scan
    / per Pallas time chunk, never in the bin loop)."""
    scal, hist = state
    return jnp.concatenate([pack_agg_scalars(scal)] + list(hist), axis=-1)


def unpack_aggregate(packed: jnp.ndarray):
    """Inverse of ``pack_aggregate`` — restores the pytree a Pallas
    kernel's VMEM-resident [L, AGG_KDIM] block carries between chunks."""
    b = AGG_HIST_BINS
    return ((tuple(packed[..., i] for i in range(18)),
             packed[..., A_OKH], packed[..., A_MAXP],
             packed[..., A_FLTH], packed[..., A_FOKH]),
            (packed[..., AGG_SCALARS:AGG_SCALARS + b],
             packed[..., AGG_SCALARS + b:AGG_SCALARS + 2 * b],
             packed[..., AGG_SCALARS + 2 * b:]))


def finalize_aggregate(packed: jnp.ndarray) -> jnp.ndarray:
    """[..., AGG_KDIM] kernel rows -> [..., AGG_DIM] public rows: each
    bucket's (sum, comp, comp2) triple recombined in f64 then cast f32.

    MUST be traced under ``jax.enable_x64(True)`` (like
    ``device_latency_histogram``): the f64 recombination of the
    twice-compensated triple is exact, so the result equals numpy's f64
    ``np.bincount`` rounded once — an f32-only recombination double-
    rounds at tie boundaries and loses bit-parity."""
    b = AGG_HIST_BINS
    hs = packed[..., AGG_SCALARS:AGG_SCALARS + b].astype(jnp.float64)
    hc = packed[..., AGG_SCALARS + b:AGG_SCALARS + 2 * b]
    hcc = packed[..., AGG_SCALARS + 2 * b:]
    hist = (hs + hc + hcc).astype(jnp.float32)
    return jnp.concatenate([packed[..., :AGG_SCALARS], hist], axis=-1)


_finalize_aggregate_jit = jax.jit(finalize_aggregate)


def finalize_aggregate_x64(packed: jnp.ndarray) -> jnp.ndarray:
    """Eager entry point for ``finalize_aggregate``: enters
    ``enable_x64`` around a module-level jit, so the compiled cache only
    ever holds the f64-correct variant (calling the same jit outside the
    ctx would silently re-trace a truncated-f32 one)."""
    with jax.enable_x64(True):
        return _finalize_aggregate_jit(packed)


def policy_table_rows() -> List[Dict]:
    """Catalog rows for report.render_table (docs / examples)."""
    rows = []
    for s in sorted(_REGISTRY.values(), key=lambda s: s.index):
        extras = ", ".join(p for p in s.param_names[3:]) or "-"
        rows.append({"policy": s.name, "extra_params": extras,
                     "behaviour": s.doc.split("\n")[0]})
    return rows


# ---------------------------------------------------------------------------
# The Twin record
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Twin:
    """A fitted pipeline model: policy name + flat parameter vector.

    ``params`` is laid out per ``policy_spec(policy).param_names``; the
    first three entries are always (max_rps, usd_per_hour, base_latency_s).
    """
    name: str
    policy: str = "fifo"
    params: Tuple[float, ...] = ()
    kind: str = "fit"

    # shared-triple accessors (every policy's params start with these)
    @property
    def max_rps(self) -> float:
        return self.params[0]

    @property
    def usd_per_hour(self) -> float:
        return self.params[1]

    @property
    def base_latency_s(self) -> float:
        return self.params[2]

    def param(self, pname: str) -> float:
        """Named lookup into the flat vector (falls back to the default)."""
        spec = policy_spec(self.policy)
        i = spec.param_names.index(pname)
        if i < len(self.params):
            return self.params[i]
        return float(spec.defaults[pname])

    def with_params(self, **updates) -> "Twin":
        """A copy with named parameters changed."""
        spec = policy_spec(self.policy)
        vals = dict(zip(spec.param_names, self.padded_params()))
        unknown = set(updates) - set(spec.param_names)
        if unknown:
            raise KeyError(f"{self.policy} has no params {sorted(unknown)}")
        vals.update(updates)
        return replace(self, params=tuple(float(vals[p])
                                          for p in spec.param_names))

    def padded_params(self) -> np.ndarray:
        """[PARAM_DIM] f32 vector: params, then defaults, then zeros."""
        spec = policy_spec(self.policy)
        vals = [float(v) for v in self.params[:len(spec.param_names)]]
        for pname in spec.param_names[len(vals):]:
            vals.append(float(spec.defaults.get(pname, 0.0)))
        vals += [0.0] * (PARAM_DIM - len(vals))
        return np.asarray(vals, np.float32)

    @property
    def policy_index(self) -> int:
        return policy_spec(self.policy).index


def make_twin(name: str, policy: str, *, kind: str = "fit",
              **params: float) -> Twin:
    """Build a Twin by named parameters, filling registered defaults."""
    spec = policy_spec(policy)
    vals = dict(spec.defaults)
    unknown = set(params) - set(spec.param_names)
    if unknown:
        raise KeyError(f"{policy} has no params {sorted(unknown)}; "
                       f"expects {spec.param_names}")
    vals.update(params)
    missing = [p for p in spec.param_names if p not in vals]
    if missing:
        raise KeyError(f"{policy} missing params {missing}")
    return Twin(name=name, policy=policy, kind=kind,
                params=tuple(float(vals[p]) for p in spec.param_names))


# ---------------------------------------------------------------------------
# Built-in policy bin-steps. Pure f32 math, identical output avals across
# branches (lax.switch requirement): carry [CARRY_DIM] and five scalars
# (processed, queue, latency, cost, dropped). ``dt`` is the bin width in
# hours; every formula reduces bit-identically to the hour-step at dt=1
# (multiplying by a literal 1.0 is exact in IEEE f32).
#
# Each built-in also hand-writes its lane-vectorized form (``_*_lane``):
# the same formulas over [L]-vectors with carry [L, CARRY_DIM] — the op
# sequence is kept identical to the scalar step so the two forms agree to
# f32 exactness (asserted at registration). Lane forms must stay finite on
# ANY lane's parameter vector (other policies' params occupy the same
# slots), which every division below guards with ``jnp.maximum(.., 1e-9)``.
# ---------------------------------------------------------------------------

def _fifo_lane(carry, arrive, p, dt):
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    cap_bin = max_rps * 3600.0 * dt
    queue = carry[:, 0]
    avail = queue + arrive
    processed = jnp.minimum(avail, cap_bin)
    new_q = avail - processed
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(max_rps, 1e-9)
    return (jnp.stack([new_q, carry[:, 1]], axis=1),
            (processed, new_q, latency, usd_hr * dt,
             jnp.zeros_like(arrive)))


@register_policy("fifo", ("max_rps", "usd_per_hour", "base_latency_s"),
                 lane_step=_fifo_lane)
def _fifo_step(carry, arrive, p, dt):
    """Fixed capacity, fixed $/hr, FIFO infinite queue (paper Table I)."""
    max_rps, usd_hr, base_lat = p[0], p[1], p[2]
    cap_bin = max_rps * 3600.0 * dt
    queue = carry[0]
    avail = queue + arrive
    processed = jnp.minimum(avail, cap_bin)
    new_q = avail - processed
    # a record arriving this bin waits behind ~the average queue
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(max_rps, 1e-9)
    return (carry.at[0].set(new_q),
            (processed, new_q, latency, usd_hr * dt, jnp.zeros((), jnp.float32)))


def _quickscale_lane(carry, arrive, p, dt):
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    cap_bin = max_rps * 3600.0 * dt
    queue = carry[:, 0]
    instances = jnp.maximum(jnp.ceil(arrive / jnp.maximum(cap_bin, 1e-9)),
                            1.0)
    processed = arrive
    new_q = queue * 0.0
    cost = usd_hr * instances * dt
    return (jnp.stack([new_q, carry[:, 1]], axis=1),
            (processed, new_q, base_lat, cost, jnp.zeros_like(arrive)))


def _quickscale_lane_smooth(carry, arrive, p, dt):
    # fluid instance count: ceil() has zero gradient w.r.t. max_rps, so
    # the surrogate pays for fractional instances instead — cost varies
    # smoothly with capacity while latency/throughput stay exact
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    cap_bin = max_rps * 3600.0 * dt
    queue = carry[:, 0]
    instances = jnp.maximum(arrive / jnp.maximum(cap_bin, 1e-9), 1.0)
    processed = arrive
    new_q = queue * 0.0
    cost = usd_hr * instances * dt
    return (jnp.stack([new_q, carry[:, 1]], axis=1),
            (processed, new_q, base_lat, cost, jnp.zeros_like(arrive)))


@register_policy("quickscale", ("max_rps", "usd_per_hour",
                                "base_latency_s"),
                 lane_step=_quickscale_lane,
                 nondiff_params=("max_rps",),
                 surrogate_lane_step=_quickscale_lane_smooth)
def _quickscale_step(carry, arrive, p, dt):
    """Optimal scaling: never queues; pay ceil(load/capacity) instances."""
    max_rps, usd_hr, base_lat = p[0], p[1], p[2]
    cap_bin = max_rps * 3600.0 * dt
    queue = carry[0]
    instances = jnp.maximum(jnp.ceil(arrive / jnp.maximum(cap_bin, 1e-9)), 1.0)
    processed = arrive
    new_q = queue * 0.0
    cost = usd_hr * instances * dt
    return (carry.at[0].set(new_q),
            (processed, new_q, base_lat, cost, jnp.zeros((), jnp.float32)))


def _autoscale_lane(carry, arrive, p, dt):
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    min_i, max_i, delay = p[:, 3], p[:, 4], p[:, 5]
    cap1 = max_rps * 3600.0 * dt
    queue, prev = carry[:, 0], carry[:, 1]
    prev = jnp.clip(prev, min_i, max_i)
    avail = queue + arrive
    target = jnp.clip(jnp.ceil(avail / jnp.maximum(cap1, 1e-9)),
                      min_i, max_i)
    booting = prev + (target - prev) * dt / jnp.maximum(delay, dt)
    inst = jnp.where(target > prev, booting, target)
    processed = jnp.minimum(avail, inst * cap1)
    new_q = avail - processed
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(inst * max_rps, 1e-9)
    cost = usd_hr * inst * dt
    return (jnp.stack([new_q, inst], axis=1),
            (processed, new_q, latency, cost, jnp.zeros_like(arrive)))


def _autoscale_lane_smooth(carry, arrive, p, dt):
    # fluid scaling target: drop the ceil() (zero gradient w.r.t.
    # max_rps); clip keeps exact subgradients w.r.t. min/max_instances,
    # and the first-order boot dynamics already differentiate cleanly
    # w.r.t. scale_up_hours
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    min_i, max_i, delay = p[:, 3], p[:, 4], p[:, 5]
    cap1 = max_rps * 3600.0 * dt
    queue, prev = carry[:, 0], carry[:, 1]
    prev = jnp.clip(prev, min_i, max_i)
    avail = queue + arrive
    target = jnp.clip(avail / jnp.maximum(cap1, 1e-9), min_i, max_i)
    booting = prev + (target - prev) * dt / jnp.maximum(delay, dt)
    inst = jnp.where(target > prev, booting, target)
    processed = jnp.minimum(avail, inst * cap1)
    new_q = avail - processed
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(inst * max_rps, 1e-9)
    cost = usd_hr * inst * dt
    return (jnp.stack([new_q, inst], axis=1),
            (processed, new_q, latency, cost, jnp.zeros_like(arrive)))


@register_policy("autoscale",
                 ("max_rps", "usd_per_hour", "base_latency_s",
                  "min_instances", "max_instances", "scale_up_hours"),
                 defaults={"min_instances": 1.0, "max_instances": 64.0,
                           "scale_up_hours": 1.0},
                 bounds={"min_instances": (1.0, 4096.0),
                         "max_instances": (1.0, 4096.0),
                         "scale_up_hours": (0.1, 48.0)},
                 log_params=("max_rps", "usd_per_hour", "base_latency_s",
                             "scale_up_hours"),
                 frozen=("min_instances", "max_instances"),
                 lane_step=_autoscale_lane,
                 nondiff_params=("max_rps",),
                 surrogate_lane_step=_autoscale_lane_smooth)
def _autoscale_step(carry, arrive, p, dt):
    """Horizontal scaling with scale-up delay and min/max instance bounds.

    Demand (queue + arrivals) sets a target instance count; booting is
    first-order with time constant ``scale_up_hours`` (teardown is
    immediate), so a slow autoscaler under-provisions during ramps — the
    queueing/latency vs cost lever of cloud-pipeline autoscaling studies.
    params[0:2] are per-instance capacity and per-instance $/hr.
    """
    max_rps, usd_hr, base_lat = p[0], p[1], p[2]
    min_i, max_i, delay = p[3], p[4], p[5]
    cap1 = max_rps * 3600.0 * dt
    queue, prev = carry[0], carry[1]
    prev = jnp.clip(prev, min_i, max_i)   # bin 0: carry starts at min_i
    avail = queue + arrive
    target = jnp.clip(jnp.ceil(avail / jnp.maximum(cap1, 1e-9)),
                      min_i, max_i)
    booting = prev + (target - prev) * dt / jnp.maximum(delay, dt)
    inst = jnp.where(target > prev, booting, target)
    processed = jnp.minimum(avail, inst * cap1)
    new_q = avail - processed
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(inst * max_rps, 1e-9)
    cost = usd_hr * inst * dt
    return (jnp.stack([new_q, inst]),
            (processed, new_q, latency, cost, jnp.zeros((), jnp.float32)))


def _shed_lane(carry, arrive, p, dt):
    max_rps, usd_hr, base_lat, qcap_h = p[:, 0], p[:, 1], p[:, 2], p[:, 3]
    cap_hour = max_rps * 3600.0
    cap_bin = cap_hour * dt
    qmax = qcap_h * cap_hour
    queue = carry[:, 0]
    avail = queue + arrive
    processed = jnp.minimum(avail, cap_bin)
    backlog = avail - processed
    dropped = jnp.maximum(backlog - qmax, 0.0)
    new_q = backlog - dropped
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(max_rps, 1e-9)
    return (jnp.stack([new_q, carry[:, 1]], axis=1),
            (processed, new_q, latency, usd_hr * dt, dropped))


@register_policy("shed",
                 ("max_rps", "usd_per_hour", "base_latency_s",
                  "queue_cap_hours"),
                 defaults={"queue_cap_hours": 4.0},
                 bounds={"queue_cap_hours": (0.05, 168.0)},
                 log_params=("max_rps", "usd_per_hour", "base_latency_s",
                             "queue_cap_hours"),
                 lane_step=_shed_lane)
def _shed_step(carry, arrive, p, dt):
    """Bounded queue with load shedding: overflow beyond the cap is dropped.

    The queue holds at most ``queue_cap_hours`` hours of capacity worth of
    records; anything beyond is shed and reported in the dropped series, so
    latency stays bounded at the price of completeness.
    """
    max_rps, usd_hr, base_lat, qcap_h = p[0], p[1], p[2], p[3]
    cap_hour = max_rps * 3600.0
    cap_bin = cap_hour * dt
    qmax = qcap_h * cap_hour          # hours-of-capacity, not bins
    queue = carry[0]
    avail = queue + arrive
    processed = jnp.minimum(avail, cap_bin)
    backlog = avail - processed
    dropped = jnp.maximum(backlog - qmax, 0.0)
    new_q = backlog - dropped
    avg_q = 0.5 * (queue + new_q)
    latency = base_lat + avg_q / jnp.maximum(max_rps, 1e-9)
    return (carry.at[0].set(new_q),
            (processed, new_q, latency, usd_hr * dt, dropped))


def _batch_window_lane(carry, arrive, p, dt):
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    window, idle_frac = p[:, 3], p[:, 4]
    cap_hour = max_rps * 3600.0
    acc, timer = carry[:, 0], carry[:, 1]
    timer = timer + dt
    flush = timer >= window
    avail = acc + arrive
    processed = jnp.where(flush, jnp.minimum(avail, cap_hour * window), 0.0)
    new_acc = avail - processed
    latency = (base_lat + 0.5 * window * 3600.0
               + new_acc / jnp.maximum(max_rps, 1e-9))
    cost = (usd_hr * idle_frac * dt
            + usd_hr * processed / jnp.maximum(cap_hour, 1e-9))
    new_timer = jnp.where(flush, 0.0, timer)
    return (jnp.stack([new_acc, new_timer], axis=1),
            (processed, new_acc, latency, cost, jnp.zeros_like(arrive)))


def _batch_window_lane_smooth(carry, arrive, p, dt):
    # soft flush gate: the exact step's ``timer >= window`` comparison has
    # zero gradient w.r.t. window_hours, so the surrogate flushes a
    # sigmoid fraction of the accumulator as the timer crosses the
    # window — flush timing (and hence cost/latency) varies smoothly.
    # The TIMER update uses a detached gate: differentiating the soft
    # reset would multiply a ~|d new_timer/d timer| > 1 factor per flush
    # into the scan's backward chain (exponential blowup to inf over a
    # year of flushes); dropping that one term keeps per-bin window
    # sensitivity while the recurrence stays contraction-stable.
    max_rps, usd_hr, base_lat = p[:, 0], p[:, 1], p[:, 2]
    window, idle_frac = p[:, 3], p[:, 4]
    cap_hour = max_rps * 3600.0
    acc, timer = carry[:, 0], carry[:, 1]
    timer = timer + dt
    gate = jax.nn.sigmoid((timer - window) / (0.25 * dt))
    avail = acc + arrive
    processed = gate * jnp.minimum(avail, cap_hour * window)
    new_acc = avail - processed
    latency = (base_lat + 0.5 * window * 3600.0
               + new_acc / jnp.maximum(max_rps, 1e-9))
    cost = (usd_hr * idle_frac * dt
            + usd_hr * processed / jnp.maximum(cap_hour, 1e-9))
    new_timer = (1.0 - jax.lax.stop_gradient(gate)) * timer
    return (jnp.stack([new_acc, new_timer], axis=1),
            (processed, new_acc, latency, cost, jnp.zeros_like(arrive)))


@register_policy("batch_window",
                 ("max_rps", "usd_per_hour", "base_latency_s",
                  "window_hours", "idle_cost_fraction"),
                 defaults={"window_hours": 6.0, "idle_cost_fraction": 0.1},
                 bounds={"window_hours": (0.25, 48.0),
                         "idle_cost_fraction": (0.0, 1.0)},
                 log_params=("max_rps", "usd_per_hour", "base_latency_s",
                             "window_hours"),
                 lane_step=_batch_window_lane,
                 nondiff_params=("window_hours",),
                 surrogate_lane_step=_batch_window_lane_smooth)
def _batch_window_step(carry, arrive, p, dt):
    """Accumulate-then-flush batching: cheap hours, half-a-window latency.

    Records accumulate for ``window_hours``; a flush burst then processes up
    to a full window of capacity at once. Cost is pay-per-use (pipeline
    hours actually consumed) plus an ``idle_cost_fraction`` keep-warm charge
    every hour — bigger windows amortise the idle cost but add ~window/2 of
    batching latency.
    """
    max_rps, usd_hr, base_lat = p[0], p[1], p[2]
    window, idle_frac = p[3], p[4]
    cap_hour = max_rps * 3600.0
    acc, timer = carry[0], carry[1]
    timer = timer + dt                 # hours since last flush
    flush = timer >= window
    avail = acc + arrive
    processed = jnp.where(flush, jnp.minimum(avail, cap_hour * window), 0.0)
    new_acc = avail - processed
    latency = (base_lat + 0.5 * window * 3600.0
               + new_acc / jnp.maximum(max_rps, 1e-9))
    cost = (usd_hr * idle_frac * dt
            + usd_hr * processed / jnp.maximum(cap_hour, 1e-9))
    new_timer = jnp.where(flush, 0.0, timer)
    return (jnp.stack([new_acc, new_timer]),
            (processed, new_acc, latency, cost, jnp.zeros((), jnp.float32)))


# ---------------------------------------------------------------------------
# Constructor aliases (seed API) and fitting from wind-tunnel experiments
# ---------------------------------------------------------------------------

def SimpleTwin(name: str, max_rps: float, usd_per_hour: float,
               base_latency_s: float, policy: str = "fifo",
               kind: str = "simple") -> Twin:
    """Seed-compatible alias: fixed-capacity FIFO twin (paper Table I)."""
    return Twin(name=name, policy=policy, kind=kind,
                params=(float(max_rps), float(usd_per_hour),
                        float(base_latency_s)))


def QuickscalingTwin(name: str, max_rps: float, usd_per_hour: float,
                     base_latency_s: float, policy: str = "quickscale",
                     kind: str = "quickscaling") -> Twin:
    """Seed-compatible alias: optimal horizontal-scaling twin."""
    return Twin(name=name, policy=policy, kind=kind,
                params=(float(max_rps), float(usd_per_hour),
                        float(base_latency_s)))


def fit_twin(result: ExperimentResult, policy: str = "fifo",
             name: Optional[str] = None, **extra_params: float) -> Twin:
    """The paper's fit, generalised to any registered policy: apparent
    sustained throughput over the whole experiment, measured hourly cost,
    no-queue latency from stage medians; policy extras via kwargs."""
    return make_twin(name or result.pipeline_name, policy,
                     max_rps=result.sustained_rps,
                     usd_per_hour=result.cost["usd_per_hour"],
                     base_latency_s=result.base_latency_s,
                     **extra_params)


def fit_simple_twin(result: ExperimentResult,
                    name: Optional[str] = None) -> Twin:
    return fit_twin(result, "fifo", name)


def fit_quickscaling_twin(result: ExperimentResult,
                          name: Optional[str] = None) -> Twin:
    return fit_twin(result, "quickscale", name)


def roofline_twin(name: str, *, step_seconds: float, records_per_step: float,
                  chips: int, chip_usd_per_hour: float = 1.20,
                  base_latency_s: Optional[float] = None) -> Twin:
    """Capacity from the dry-run roofline bound: one serving step processes
    ``records_per_step`` requests in ``step_seconds`` (max of the three
    roofline terms). See launch/roofline.py for the term derivation."""
    cap = records_per_step / step_seconds
    return SimpleTwin(name=name, max_rps=cap,
                      usd_per_hour=chips * chip_usd_per_hour,
                      base_latency_s=base_latency_s or step_seconds,
                      kind="roofline")
