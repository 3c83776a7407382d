"""jit'd dispatch layer between Pallas kernels and jnp references.

``use_pallas(True)`` flips attention / rwkv6 / ssm / policy-grid hot
paths to their Pallas implementations. The default is the XLA reference
path so the 512-device dry-run lowers on the CPU container. Whether a
Pallas kernel compiles with Mosaic or runs in the interpreter is not an
option: ``interpret_mode()`` derives it from the platform (interpreter
on the CPU, Mosaic on a TPU). Model code imports ONLY from this
module; the what-if grid backend (``core.simulate._grid_scan``) selects
through ``policy_scan`` here.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from repro.kernels import ref

_state = threading.local()
_state.pallas = False
# XLA-path attention chunking: 0 = exact quadratic einsum; >0 = flash-style
# blocked online softmax with this chunk (dry-run memfit mode; ref of the
# Pallas kernel). Applies when seq_len is a multiple of the chunk.
_state.attn_chunk = 0


def use_pallas(enable: bool = True):
    _state.pallas = enable


def set_attn_chunk(chunk: int):
    _state.attn_chunk = chunk


def get_attn_chunk() -> int:
    return getattr(_state, "attn_chunk", 0)


def pallas_enabled() -> bool:
    return getattr(_state, "pallas", False)


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when the
    default backend is the CPU. On a TPU every kernel compiles with
    Mosaic."""
    return jax.default_backend() == "cpu"


@contextlib.contextmanager
def pallas_mode(enable: bool = True):
    prev = getattr(_state, "pallas", False)
    use_pallas(enable)
    try:
        yield
    finally:
        use_pallas(prev)


def sdpa(q, k, v, *, causal=True, scale=None, logit_cap=0.0, kv_len=None):
    if pallas_enabled() and kv_len is None and logit_cap == 0.0 and q.shape[1] > 1:
        from repro.kernels import flash_attention
        return flash_attention.flash_attention(
            q, k, v, causal=causal, scale=scale,
            interpret=interpret_mode())
    chunk = get_attn_chunk()
    if (chunk > 0 and kv_len is None and logit_cap == 0.0
            and q.shape[1] > chunk and q.shape[1] % chunk == 0
            and k.shape[1] % chunk == 0):
        return ref.sdpa_blocked(q, k, v, causal=causal, scale=scale,
                                chunk=chunk)
    return ref.sdpa(q, k, v, causal=causal, scale=scale,
                    logit_cap=logit_cap, kv_len=kv_len)


def rwkv6_scan(r, k, v, w, u, state=None):
    if pallas_enabled():
        from repro.kernels import rwkv6_kernel
        return rwkv6_kernel.rwkv6(r, k, v, w, u, state,
                                  interpret=interpret_mode())
    return ref.rwkv6_scan(r, k, v, w, u, state)


def ssm_scan(x, dt, A, B, C, D, state=None):
    if pallas_enabled():
        from repro.kernels import ssm_scan as ssm_kernel
        return ssm_kernel.ssm(x, dt, A, B, C, D, state,
                              interpret=interpret_mode())
    return ref.ssm_scan(x, dt, A, B, C, D, state)


def policy_scan(loads, params, onehot=None, dt_hours=1.0, *,
                policy_index=None, differentiable=False, surrogate=False,
                caps=None):
    """TwinPolicy scenario-grid scan: loads [N, T], params [N, PARAM_DIM]
    -> (carry_end [N, CARRY_DIM], five [N, T] series).

    Exactly one of ``onehot`` [N, P] (mixed-policy grid, masked-blend
    lane step) or ``policy_index`` (scalar, possibly traced — a
    uniform-policy lane block such as K calibration restarts; a single
    lane branch executes via ``lax.switch`` instead of all P) selects
    the policies; see ``ref.policy_grid_scan``.

    ``differentiable=True`` pins the pure-jnp lane path regardless of the
    Pallas switch — the kernel has no VJP, and twin calibration takes
    ``jax.grad`` through this scan. Both paths run the same
    lane-vectorized math, so the choice never changes the numbers. When
    the bin width is a static float, the differentiable path carries the
    checkpointed O(√T) custom VJP (``kernels.policy_vjp``), so fit/search
    backward passes rematerialize √T-bin segments instead of taping the
    whole horizon; a traced ``dt_hours`` falls back to the plain
    reference scan (autodiff-through-scan), same numbers either way.

    ``surrogate=True`` (implies the differentiable path) additionally
    swaps in the smooth-surrogate lane branches so hard-gated policy
    extras carry gradients — the policy-search inner loop
    (``repro.search``). Surrogate numbers are a gradient guide only;
    exact results always come from the non-surrogate forms.

    ``caps`` [N, T] (optional) threads a fault schedule's capacity
    multipliers through the scan (``repro.faults``): the step runs in
    the fault-layer wrapper (backlog queue, reconnect flood — see
    ``core.twin.fault_lane_policy_step``). The fault SERIES path always
    takes the reference lane scan (plain autodiff when differentiated;
    the Pallas series kernel covers the benign non-diff fast path).
    Gradient users who don't need the series should go through
    ``policy_scan_fold`` instead — its in-carry reductions stream on
    both the benign AND the fault path with the O(√T) backward, which
    is how the search/calibrate kernels dispatch since the streaming
    -objective rework.
    """
    if (onehot is None) == (policy_index is None):   # before dispatch, so
        # both backends reject the ambiguity identically (one_hot(None)
        # would otherwise make the Pallas path return silent zeros)
        raise ValueError("pass exactly one of onehot= (mixed grid) or "
                         "policy_index= (uniform lane block)")
    if caps is not None:
        return ref.policy_grid_scan(loads, params, onehot, dt_hours,
                                    policy_index=policy_index,
                                    surrogate=surrogate, caps=caps)
    if pallas_enabled() and not differentiable and not surrogate:
        from repro.kernels import policy_scan as policy_kernel
        if onehot is None:
            # the kernel's branch selector is the mask form; a traced
            # uniform index lowers to its one-hot row broadcast over lanes
            from repro.core.twin import num_policies
            onehot = jnp.broadcast_to(
                jax.nn.one_hot(policy_index, num_policies(),
                               dtype=jnp.float32),
                (loads.shape[0], num_policies()))
        return policy_kernel.policy_grid_scan(loads, params, onehot,
                                              dt_hours)
    if differentiable or surrogate:
        try:
            dt_static = float(dt_hours)   # tracers raise TypeError
        except TypeError:
            dt_static = None
        if dt_static is not None:
            from repro.kernels import policy_vjp
            return policy_vjp.policy_grid_scan_ckpt(
                loads, params, onehot, dt_static,
                policy_index=policy_index, surrogate=surrogate)
    return ref.policy_grid_scan(loads, params, onehot, dt_hours,
                                policy_index=policy_index,
                                surrogate=surrogate)


def policy_scan_fold(loads=None, params=None, onehot=None, dt_hours=1.0,
                     *, policy_index=None, surrogate=False, caps=None,
                     loads_t=None, caps_t=None, fold_init, fold_step,
                     ops_lane=(), xs=()):
    """Streaming-aggregate GRADIENT scan: fold per-bin policy outputs
    into a caller-defined accumulator inside the scan carry instead of
    materializing five [N, T] series — the gradient-path sibling of
    ``policy_scan_agg``, always the pure-jnp lane path (the Pallas
    kernels have no VJP).

    ``fold_init(n)`` / ``fold_step(acc, arrive, outs, ops_lane, xs_row)``
    must be module-level functions (they key trace caches); ``ops_lane``
    is a pytree of differentiable per-lane operands, ``xs`` a pytree of
    per-bin operands with leading axis T. Operands may come scenario
    -minor (``loads_t``/``caps_t`` [T, N]). With a static ``dt_hours``
    the scan carries the checkpointed O(√T) custom VJP — including the
    fault layer (``caps=``), which the series path above never streams —
    so neither direction holds an [N, T] intermediate; a traced bin
    width falls back to one plain differentiable scan, same numbers.
    Returns (carry_end [N, CARRY_DIM], acc); fault-backlog residue is
    folded into ``carry_end[:, 0]`` exactly like ``ref.policy_grid_scan``.
    """
    from repro.kernels import policy_vjp
    return policy_vjp.policy_grid_scan_fold(
        loads, params, onehot, dt_hours, policy_index=policy_index,
        surrogate=surrogate, caps=caps, loads_t=loads_t, caps_t=caps_t,
        fold_init=fold_init, fold_step=fold_step, ops_lane=ops_lane,
        xs=xs)


def policy_scan_agg(loads, params, onehot, dt_hours=1.0, *,
                    slo_limit=float("inf"), slo_mode=0, caps=None,
                    fmask=None):
    """Streaming-aggregate TwinPolicy grid scan: loads [N, T], params
    [N, PARAM_DIM], onehot [N, P] -> (carry_end [N, CARRY_DIM],
    agg [N, AGG_DIM]) — Table II statistics folded into the scan carry,
    NO [N, T] series materialized on either backend.

    Under ``use_pallas(True)`` this is the fused Pallas aggregate kernel
    (``kernels/policy_scan.policy_grid_agg``: carry + aggregates —
    load-weighted latency histogram included, as compensated in-kernel
    triples — resident in VMEM scratch across time chunks, tiled by
    ``tile_plan``); otherwise the pure-jnp lane oracle
    ``ref.policy_grid_agg``. Both return FINALIZED AGG_DIM rows
    (histogram triples recombined in f64 by
    ``core.twin.finalize_aggregate_x64``), bit-identical to the host
    ``np_latency_histogram`` oracle — no host binning round-trip exists
    on either path. ``slo_limit`` / ``slo_mode`` are static trace
    constants (``core.twin.AGG_SLO_*``; ``inf`` = no SLO).
    Not differentiable on either path — calibration differentiates the
    series scan, which keeps the full trace a loss needs anyway.

    ``caps`` / ``fmask`` [N, T] (together) thread a fault schedule
    through the scan on BOTH backends: the Pallas aggregate kernel has a
    native fault variant (two extra scenario-minor input streams, the
    backlog queue as one more VMEM scratch column), the reference path
    scans ``core.twin.fault_lane_policy_step``.
    """
    if (caps is None) != (fmask is None):
        raise ValueError("pass caps= and fmask= together (or neither)")
    if pallas_enabled():
        from repro.kernels import policy_scan as policy_kernel
        return policy_kernel.policy_grid_agg(
            loads, params, onehot, dt_hours, slo_limit=float(slo_limit),
            slo_mode=int(slo_mode), caps=caps, fmask=fmask)
    return ref.policy_grid_agg(loads, params, onehot, dt_hours,
                               slo_limit=float(slo_limit),
                               slo_mode=int(slo_mode), caps=caps,
                               fmask=fmask)
