"""Pallas kernel: the whole TwinPolicy (scenario x bin) grid in one scan.

The what-if engine's hot path is a tiny f32 bin-step scanned over T bins
for N scenarios (paper Sec. V-G: only the load *shape* is simulated, so
the grid engine bounds how many scenarios a sweep can afford). The XLA
path (``core.simulate._grid_scan``) runs it as vmap-of-scan with a
``lax.switch`` per scenario; this kernel fuses the whole grid into ONE
``pallas_call``:

* grid = (scenario blocks, time chunks), time minor — each kernel
  instance advances a block of LANES scenarios through one chunk of bins;
* scenarios live on the vector lanes: operand blocks are [chunk, LANES]
  with the scenario axis minor, so each bin-step is straight-line VPU
  vector math over the lane block (``core.twin.lane_policy_step`` — every
  registered policy evaluated and blended by the [LANES, P] one-hot mask,
  no control flow);
* the [LANES, CARRY_DIM] scan carry lives in VMEM scratch and persists
  across time chunks, so HBM sees each load bin exactly once and the
  carry never round-trips (the XLA scan materialises it per step).

The public entry points compile with Mosaic on a TPU and run the Pallas
interpreter on the CPU (``kernels.ops.interpret_mode``); the
grid/BlockSpec structure is the TPU layout. Each bin's row is read from
the operand ref inside the bin loop (``ref[pl.ds(t, 1), :]``) and the
series kernel writes each bin's outputs straight into its output refs:
Mosaic has no lowering for a dynamic slice of a loaded value.
``chunk`` bounds VMEM: a (chunk x LANES) f32 block per operand/output.
The (lanes, chunk) tile is derived from the device's VMEM budget by
``tile_plan`` (lanes pinned
to the 128-wide VPU lane axis, chunk the largest divisor of the horizon
whose double-buffered operand blocks plus the per-lane resident state
fit the budget) rather than hard-coded year-shaped constants; the plan
is pure integer arithmetic, so interpret mode on CPU asserts the exact
tiles real silicon would get. Horizons an explicitly-passed chunk
doesn't divide fall back to a single chunk.

``policy_grid_agg`` is the STREAMING-AGGREGATE variant of the same
kernel (the O(N)-memory backend of ``simulate_grid(return_series=
False)``): the Table II statistics — twice-compensated sums, per-bin
max, SLO-ok counters and the quarter-octave load-weighted latency
histogram (``core.twin.lane_update_aggregate``, masked compare-adds on
the vector lanes, each bucket a compensated (sum, comp, comp2) triple)
— ride in a second VMEM scratch block across time chunks, and the only
HBM outputs are one [LANES, CARRY_DIM] carry row and one
[LANES, AGG_KDIM] kernel-row per scenario block (recombined to the
public [N, AGG_DIM] layout by ``core.twin.finalize_aggregate``). The
five [N, T] series are never allocated.

Dispatch through ``kernels.ops.policy_scan`` / ``ops.policy_scan_agg``
(the ``use_pallas`` / ``pallas_mode`` switch); the pure-jnp oracles are
``kernels.ref.policy_grid_scan`` / ``ref.policy_grid_agg``. No VJP is
defined — gradient users (twin calibration) pin the reference path,
which is the same branchless math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: VMEM budget the default tile plan targets: half of a 16 MB TPU core
#: VMEM, leaving headroom for compiler spills and semaphores
DEFAULT_VMEM_BYTES = 8 * 2**20
#: hardware vector-lane width the scenario axis is tiled to
LANE_WIDTH = 128
#: operand streams a kernel instance may double-buffer (loads + the two
#: fault streams, x2 for the pipelined next block)
_MAX_STREAM_BUFFERS = 6


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_plan(t_bins: int, param_dim: int,
              vmem_bytes: int = DEFAULT_VMEM_BYTES):
    """(lanes, chunk) kernel tile for a ``t_bins``-hour horizon under a
    VMEM budget — the device-spec-driven replacement for the old
    year-shaped DEFAULT_LANES/DEFAULT_CHUNK constants.

    Lanes are pinned to the 128-wide VPU lane axis (``_stage_operands``
    still shrinks tiny grids below that); the time chunk is the largest
    divisor of ``t_bins`` whose double-buffered [chunk, lanes] operand
    blocks fit what the budget leaves after the per-lane resident state
    (params + one-hot rows, the fault-extended carry scratch, and the
    packed + unpacked aggregate kernel rows). Pure integer arithmetic —
    no device queries — so interpret mode on CPU asserts the exact tiles
    real silicon would get, and a chunk choice can never change results
    (the scan carry and aggregate scratch persist across chunks, so any
    divisor replays the identical per-bin op sequence)."""
    from repro.core.twin import AGG_KDIM, CARRY_DIM, num_policies
    t_bins = max(int(t_bins), 1)
    lanes = LANE_WIDTH
    slots = max(int(vmem_bytes), 0) // (4 * lanes)
    resident = param_dim + num_policies() + (CARRY_DIM + 1) + 2 * AGG_KDIM
    cap = max((slots - resident) // _MAX_STREAM_BUFFERS, 1)
    chunk = next(d for d in range(min(cap, t_bins), 0, -1)
                 if t_bins % d == 0)
    return lanes, chunk


def _policy_scan_kernel(loads_ref, params_ref, onehot_ref,
                        proc_ref, queue_ref, lat_ref, cost_ref, drop_ref,
                        carry_end_ref, carry_ref, *,
                        step, dt: float, chunk: int, num_chunks: int,
                        carry_dim: int):
    """Grid: (scenario blocks, time chunks) — time minor; carry in scratch."""
    c = pl.program_id(1)
    lanes = loads_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros((lanes, carry_dim), jnp.float32)

    params = params_ref[...]          # [LANES, PARAM_DIM]
    onehot = onehot_ref[...]          # [LANES, P]
    dt_f = jnp.float32(dt)

    def bin_step(t, carry):
        row = pl.ds(t, 1)
        carry, outs = step(carry, loads_ref[row, :][0], params, onehot,
                           dt_f)
        for ref, o in zip((proc_ref, queue_ref, lat_ref, cost_ref,
                           drop_ref), outs):
            ref[row, :] = o[None]
        return carry

    carry = jax.lax.fori_loop(0, chunk, bin_step, carry_ref[...])
    carry_ref[...] = carry

    @pl.when(c == num_chunks - 1)
    def _fin():
        carry_end_ref[...] = carry


@functools.partial(jax.jit,
                   static_argnames=("dt_hours", "version", "lanes", "chunk",
                                    "interpret"))
def _policy_scan(loads_t: jnp.ndarray, params: jnp.ndarray,
                 onehot: jnp.ndarray, *, dt_hours: float, version: int,
                 lanes: int, chunk: int, interpret: bool):
    """loads_t [T, Npad] (scenarios minor/padded), params [Npad, D],
    onehot [Npad, P]; ``version`` is the policy-registry version (static)
    so late policy registration retraces the branch blend."""
    from repro.core.twin import CARRY_DIM, lane_policy_step
    del version
    t_bins, npad = loads_t.shape
    nb, nc = npad // lanes, t_bins // chunk

    kernel = functools.partial(
        _policy_scan_kernel, step=lane_policy_step, dt=float(dt_hours),
        chunk=chunk, num_chunks=nc, carry_dim=CARRY_DIM)
    series = jax.ShapeDtypeStruct((t_bins, npad), jnp.float32)
    outs = pl.pallas_call(
        kernel,
        grid=(nb, nc),
        in_specs=[
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((lanes, params.shape[1]), lambda i, c: (i, 0)),
            pl.BlockSpec((lanes, onehot.shape[1]), lambda i, c: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((lanes, CARRY_DIM), lambda i, c: (i, 0)),
        ],
        out_shape=[series, series, series, series, series,
                   jax.ShapeDtypeStruct((npad, CARRY_DIM), jnp.float32)],
        scratch_shapes=[_vmem((lanes, CARRY_DIM), jnp.float32)],
        interpret=interpret,
    )(loads_t, params, onehot)
    return outs


def _policy_agg_kernel(loads_ref, params_ref, onehot_ref,
                       carry_end_ref, agg_out_ref, carry_ref, agg_ref, *,
                       step, update, pack, unpack, dt: float,
                       slo_limit: float, slo_mode: int, chunk: int,
                       num_chunks: int, carry_dim: int, agg_dim: int):
    """Streaming-aggregate variant: same (scenario blocks, time chunks)
    grid, but BOTH the policy carry and the Table II aggregate state live
    in VMEM scratch and persist across time chunks — no [chunk, LANES]
    output block exists at all, so HBM traffic is the loads in and one
    [LANES, AGG_KDIM] row out per scenario block. Inside the bin loop the
    aggregate state is the unpacked pytree (pure vector arithmetic); the
    packed [LANES, AGG_KDIM] form only exists at chunk boundaries, where
    it round-trips through the scratch block."""
    c = pl.program_id(1)
    lanes = loads_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros((lanes, carry_dim), jnp.float32)
        agg_ref[...] = jnp.zeros((lanes, agg_dim), jnp.float32)

    params = params_ref[...]          # [LANES, PARAM_DIM]
    onehot = onehot_ref[...]          # [LANES, P]
    dt_f = jnp.float32(dt)

    def bin_step(t, state):
        carry, agg = state
        arrive = loads_ref[pl.ds(t, 1), :][0]
        carry, outs = step(carry, arrive, params, onehot, dt_f)
        agg = update(agg, arrive, outs, slo_limit, slo_mode)
        return carry, agg

    carry, agg = jax.lax.fori_loop(0, chunk, bin_step,
                                   (carry_ref[...], unpack(agg_ref[...])))
    packed = pack(agg)
    carry_ref[...] = carry
    agg_ref[...] = packed

    @pl.when(c == num_chunks - 1)
    def _fin():
        carry_end_ref[...] = carry
        agg_out_ref[...] = packed


def _policy_agg_fault_kernel(loads_ref, caps_ref, fmask_ref, params_ref,
                             onehot_ref, carry_end_ref, agg_out_ref,
                             carry_ref, agg_ref, *, step, update, pack,
                             unpack, dt: float, slo_limit: float,
                             slo_mode: int, chunk: int, num_chunks: int,
                             carry_dim: int, agg_dim: int):
    """Fault-schedule variant of ``_policy_agg_kernel``: two extra
    scenario-minor input streams (capacity multipliers + in-fault masks,
    same [chunk, LANES] blocks as the loads) and the fault-layer backlog
    queue riding as one extra column of the VMEM carry scratch. Padded
    lanes stream zero capacity AND zero load, so the fault gate holds
    their backlog at exactly zero. The final carry row folds the backlog
    into the queue slot (records conservation: offered = processed +
    dropped + carry_end[:, 0])."""
    c = pl.program_id(1)
    lanes = loads_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros((lanes, carry_dim + 1), jnp.float32)
        agg_ref[...] = jnp.zeros((lanes, agg_dim), jnp.float32)

    params = params_ref[...]          # [LANES, PARAM_DIM]
    onehot = onehot_ref[...]          # [LANES, P]
    dt_f = jnp.float32(dt)

    def bin_step(t, state):
        carry, fq, agg = state
        row = pl.ds(t, 1)
        arrive = loads_ref[row, :][0]
        (carry, fq), outs = step((carry, fq), arrive, caps_ref[row, :][0],
                                 params, onehot, dt_f)
        agg = update(agg, arrive, outs, slo_limit, slo_mode,
                     fmask_ref[row, :][0])
        return carry, fq, agg

    cf = carry_ref[...]
    carry, fq, agg = jax.lax.fori_loop(
        0, chunk, bin_step,
        (cf[:, :carry_dim], cf[:, carry_dim], unpack(agg_ref[...])))
    packed = pack(agg)
    carry_ref[...] = jnp.concatenate([carry, fq[:, None]], axis=1)
    agg_ref[...] = packed

    @pl.when(c == num_chunks - 1)
    def _fin():
        carry_end_ref[...] = jnp.concatenate(
            [(carry[:, 0] + fq)[:, None], carry[:, 1:]], axis=1)
        agg_out_ref[...] = packed


@functools.partial(jax.jit,
                   static_argnames=("dt_hours", "slo_limit", "slo_mode",
                                    "version", "lanes", "chunk",
                                    "interpret"))
def _policy_agg_fault(loads_t: jnp.ndarray, caps_t: jnp.ndarray,
                      fmask_t: jnp.ndarray, params: jnp.ndarray,
                      onehot: jnp.ndarray, *, dt_hours: float,
                      slo_limit: float, slo_mode: int, version: int,
                      lanes: int, chunk: int, interpret: bool):
    """Fault twin of ``_policy_agg``: identical grid and output layout,
    plus the two [T, Npad] fault operand streams."""
    from repro.core.twin import (AGG_KDIM, CARRY_DIM,
                                 fault_lane_policy_step,
                                 lane_update_aggregate, pack_aggregate,
                                 unpack_aggregate)
    del version
    t_bins, npad = loads_t.shape
    nb, nc = npad // lanes, t_bins // chunk

    kernel = functools.partial(
        _policy_agg_fault_kernel, step=fault_lane_policy_step,
        update=lane_update_aggregate, pack=pack_aggregate,
        unpack=unpack_aggregate, dt=float(dt_hours),
        slo_limit=float(slo_limit), slo_mode=int(slo_mode), chunk=chunk,
        num_chunks=nc, carry_dim=CARRY_DIM, agg_dim=AGG_KDIM)
    stream = pl.BlockSpec((chunk, lanes), lambda i, c: (c, i))
    return pl.pallas_call(
        kernel,
        grid=(nb, nc),
        in_specs=[
            stream, stream, stream,
            pl.BlockSpec((lanes, params.shape[1]), lambda i, c: (i, 0)),
            pl.BlockSpec((lanes, onehot.shape[1]), lambda i, c: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((lanes, CARRY_DIM), lambda i, c: (i, 0)),
            pl.BlockSpec((lanes, AGG_KDIM), lambda i, c: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((npad, CARRY_DIM), jnp.float32),
                   jax.ShapeDtypeStruct((npad, AGG_KDIM), jnp.float32)],
        scratch_shapes=[_vmem((lanes, CARRY_DIM + 1), jnp.float32),
                        _vmem((lanes, AGG_KDIM), jnp.float32)],
        interpret=interpret,
    )(loads_t, caps_t, fmask_t, params, onehot)


@functools.partial(jax.jit,
                   static_argnames=("dt_hours", "slo_limit", "slo_mode",
                                    "version", "lanes", "chunk",
                                    "interpret"))
def _policy_agg(loads_t: jnp.ndarray, params: jnp.ndarray,
                onehot: jnp.ndarray, *, dt_hours: float, slo_limit: float,
                slo_mode: int, version: int, lanes: int, chunk: int,
                interpret: bool):
    """Aggregate twin of ``_policy_scan``: same operand layout, O(N)
    outputs (carry_end [Npad, CARRY_DIM], agg [Npad, AGG_KDIM])."""
    from repro.core.twin import (AGG_KDIM, CARRY_DIM, lane_policy_step,
                                 lane_update_aggregate, pack_aggregate,
                                 unpack_aggregate)
    del version
    t_bins, npad = loads_t.shape
    nb, nc = npad // lanes, t_bins // chunk

    kernel = functools.partial(
        _policy_agg_kernel, step=lane_policy_step,
        update=lane_update_aggregate, pack=pack_aggregate,
        unpack=unpack_aggregate, dt=float(dt_hours),
        slo_limit=float(slo_limit), slo_mode=int(slo_mode), chunk=chunk,
        num_chunks=nc, carry_dim=CARRY_DIM, agg_dim=AGG_KDIM)
    return pl.pallas_call(
        kernel,
        grid=(nb, nc),
        in_specs=[
            pl.BlockSpec((chunk, lanes), lambda i, c: (c, i)),
            pl.BlockSpec((lanes, params.shape[1]), lambda i, c: (i, 0)),
            pl.BlockSpec((lanes, onehot.shape[1]), lambda i, c: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((lanes, CARRY_DIM), lambda i, c: (i, 0)),
            pl.BlockSpec((lanes, AGG_KDIM), lambda i, c: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((npad, CARRY_DIM), jnp.float32),
                   jax.ShapeDtypeStruct((npad, AGG_KDIM), jnp.float32)],
        scratch_shapes=[_vmem((lanes, CARRY_DIM), jnp.float32),
                        _vmem((lanes, AGG_KDIM), jnp.float32)],
        interpret=interpret,
    )(loads_t, params, onehot)


def _stage_operands(loads, loads_t, lanes, chunk, param_dim):
    """Common operand staging for both wrappers: accepts EXACTLY one of
    ``loads`` [N, T] (scenario-major, the historical API — transposed and
    zero-padded into the kernel layout) or ``loads_t`` [T, N] (already
    scenario-minor: the grid engine's block gathers produce this layout
    directly, so handing it over skips the [N, T] transpose copy that
    used to dominate per-block staging — the PR 3/4 layout follow-on).
    ``lanes`` / ``chunk`` = None resolve through ``tile_plan`` for the
    horizon at hand. Returns (n, t_bins, npad, lanes, chunk,
    staged_loads_t)."""
    if (loads is None) == (loads_t is None):
        raise ValueError("pass exactly one of loads= ([N, T]) or "
                         "loads_t= ([T, N] scenario-minor)")
    if loads_t is None:
        n, t_bins = loads.shape
    else:
        t_bins, n = loads_t.shape
    if lanes is None or chunk is None:
        plan = tile_plan(int(t_bins), int(param_dim))
        lanes = plan[0] if lanes is None else lanes
        chunk = plan[1] if chunk is None else chunk
    lanes = min(lanes, _round_up(max(n, 1), 8))
    npad = _round_up(max(n, 1), lanes)
    if t_bins % chunk:
        chunk = t_bins
    if loads_t is None:
        staged = jnp.zeros((t_bins, npad), jnp.float32)
        staged = staged.at[:, :n].set(jnp.asarray(loads, jnp.float32).T)
    else:
        staged = jnp.asarray(loads_t, jnp.float32)
        if npad != n:   # no-op (and no copy) when already lane-aligned
            staged = jnp.pad(staged, ((0, 0), (0, npad - n)))
    return n, t_bins, npad, lanes, chunk, staged


def _stage_aux(aux, aux_t, t_bins: int, n: int, npad: int, what: str):
    """Stage one optional per-bin fault stream into the kernel's [T, Npad]
    scenario-minor layout (zero-padded: a zero capacity multiplier on a
    zero-load padded lane keeps its fault backlog at exactly zero)."""
    if aux is None and aux_t is None:
        return None
    if (aux is None) == (aux_t is None):
        raise ValueError(f"pass exactly one of {what}= ([N, T]) or "
                         f"{what}_t= ([T, N] scenario-minor)")
    if aux_t is None:
        staged = jnp.zeros((t_bins, npad), jnp.float32)
        return staged.at[:, :n].set(jnp.asarray(aux, jnp.float32).T)
    staged = jnp.asarray(aux_t, jnp.float32)
    if npad != n:
        staged = jnp.pad(staged, ((0, 0), (0, npad - n)))
    return staged


def policy_grid_agg(loads: jnp.ndarray | None, params: jnp.ndarray,
                    onehot: jnp.ndarray, dt_hours: float = 1.0, *,
                    slo_limit: float = float("inf"), slo_mode: int = 0,
                    lanes: int = None, chunk: int = None, loads_t=None,
                    caps=None, fmask=None, caps_t=None, fmask_t=None,
                    finalize: bool = True):
    """Fused streaming-aggregate grid scan; semantics of
    ``ref.policy_grid_agg``. Same padding/transposition contract as
    ``policy_grid_scan``, but the only outputs are O(N): per-scenario
    final carries and the [AGG_DIM] aggregate rows — the five [N, T]
    series are never allocated, on HBM or anywhere else. ``slo_limit`` /
    ``slo_mode`` are static (see ``core.twin.AGG_SLO_*``). Pass
    ``loads_t=`` ([T, N], ``loads=None``) to hand over operands already
    in the kernel's scenario-minor layout. A fault schedule's capacity /
    in-fault streams ride along as ``caps``/``fmask`` (or the
    scenario-minor ``caps_t``/``fmask_t``) and select the fault kernel
    variant (``_policy_agg_fault_kernel``). ``finalize=False`` returns
    the raw [N, AGG_KDIM] kernel rows (per-bucket compensated triples)
    for drivers that recombine once at the end of a block loop
    (``core.twin.finalize_aggregate_x64``). Returns
    (carry_end [N, CARRY_DIM], agg [N, AGG_DIM]).
    """
    from repro.core.twin import finalize_aggregate_x64, registry_version
    from repro.kernels.ops import interpret_mode
    n, t_bins, npad, lanes, chunk, loads_t = _stage_operands(
        loads, loads_t, lanes, chunk, params.shape[1])
    pad = lambda a: jnp.zeros((npad, a.shape[1]), jnp.float32).at[:n].set(  # noqa: E731
        jnp.asarray(a, jnp.float32))
    caps_t = _stage_aux(caps, caps_t, t_bins, n, npad, "caps")
    fmask_t = _stage_aux(fmask, fmask_t, t_bins, n, npad, "fmask")
    if (caps_t is None) != (fmask_t is None):
        raise ValueError("pass caps and fmask together (or neither)")
    if caps_t is not None:
        carry_end, agg = _policy_agg_fault(
            loads_t, caps_t, fmask_t, pad(params), pad(onehot),
            dt_hours=float(dt_hours), slo_limit=float(slo_limit),
            slo_mode=int(slo_mode), version=registry_version(),
            lanes=lanes, chunk=chunk, interpret=interpret_mode())
    else:
        carry_end, agg = _policy_agg(
            loads_t, pad(params), pad(onehot), dt_hours=float(dt_hours),
            slo_limit=float(slo_limit), slo_mode=int(slo_mode),
            version=registry_version(), lanes=lanes, chunk=chunk,
            interpret=interpret_mode())
    if finalize:
        agg = finalize_aggregate_x64(agg)
    return carry_end[:n], agg[:n]


def policy_grid_scan(loads: jnp.ndarray | None, params: jnp.ndarray,
                     onehot: jnp.ndarray, dt_hours: float = 1.0, *,
                     lanes: int = None, chunk: int = None, loads_t=None):
    """Fused scenario-grid scan; same contract as ``ref.policy_grid_scan``.

    loads [N, T]; params [N, PARAM_DIM]; onehot [N, P]. The scenario axis
    is padded up to a LANES multiple (padded lanes carry an all-zero
    policy mask, so they blend to zeros) and transposed scenario-minor for
    the kernel; outputs come back truncated to N. ``loads_t=`` ([T, N],
    with ``loads=None``) skips the transpose for callers that already
    hold the kernel layout. Returns
    (carry_end [N, CARRY_DIM], (processed, queue, latency, cost, dropped))
    with each series [N, T].
    """
    from repro.core.twin import registry_version
    from repro.kernels.ops import interpret_mode
    n, t_bins, npad, lanes, chunk, loads_t = _stage_operands(
        loads, loads_t, lanes, chunk, params.shape[1])
    pad = lambda a: jnp.zeros((npad, a.shape[1]), jnp.float32).at[:n].set(  # noqa: E731
        jnp.asarray(a, jnp.float32))
    proc, queue, lat, cost, drop, carry_end = _policy_scan(
        loads_t, pad(params), pad(onehot), dt_hours=float(dt_hours),
        version=registry_version(), lanes=lanes, chunk=chunk,
        interpret=interpret_mode())
    series = tuple(o[:, :n].T for o in (proc, queue, lat, cost, drop))
    return carry_end[:n], series
