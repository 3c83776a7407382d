"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked linear attention).

TPU-native adaptation of the CUDA wkv6 kernel: instead of a per-token
recurrence (serial, VPU-bound), the sequence is processed in chunks. Within
a chunk the contribution decomposes into MXU matmuls:

  A_t   = prod_{j<t} w_j                (cumulative decay inside the chunk)
  o_t   = (r_t*A_t) . S_chunk_start                      [carry term]
        + sum_{i<t} ((r_t*A_t).(k_i/A_{i+1})) v_i        [intra, strict tri]
        + ((r_t*u).k_t) v_t                              [bonus diagonal]
  S'    = diag(A_end) S + sum_i (A_end/A_{i+1}) k_i v_i^T

The chunk state S (head_dim x head_dim, fp32) lives in VMEM scratch and
persists across the chunk grid steps, so HBM traffic is O(s*n) instead of
the O(s*n^2) a naive XLA scan would incur. Chunks of 32 keep the decay
products in fp32 range for realistic decays.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sT_ref, state_ref, *, chunk: int, num_chunks: int):
    """Grid: (b*h, nc) — nc sequential; state scratch persists per (b,h)."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # [C, n]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)          # decay in (0,1)
    u = u_ref[0].astype(jnp.float32)          # [1, n] bonus

    # Exponent clamp: the factorized decay products exp(+cum) can overflow
    # for extreme data-dependent decays; +/-CLAMP keeps every representable
    # pair product exact (pairs beyond e^-CLAMP have decayed to zero).
    CLAMP = 80.0
    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = jnp.cumsum(logw, axis=0)            # inclusive: log prod_{j<=t}
    A_t = jnp.exp(jnp.maximum(cum - logw, -2 * CLAMP))   # prod_{j<t}
    A_end = jnp.exp(jnp.maximum(cum[-1], -2 * CLAMP))    # whole chunk
    inv_Ai1 = jnp.exp(jnp.minimum(-cum, CLAMP))          # 1 / prod_{j<=i}

    rd = r * A_t                              # [C, n]
    kd = k * inv_Ai1                          # [C, n]

    S = state_ref[...]                        # [n, n]
    carry = jax.lax.dot_general(rd, S, (((1,), (0,)), ((), ())))   # [C, n]

    scores = jax.lax.dot_general(rd, kd, (((1,), (1,)), ((), ())))  # [C, C]
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(ti > tj, scores, 0.0)                        # strict
    intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))

    bonus = ((r * u) * k).sum(axis=1, keepdims=True) * v            # diag
    o_ref[0] = (carry + intra + bonus).astype(o_ref.dtype)

    kv = jax.lax.dot_general(k * (A_end[None] * inv_Ai1), v,
                             (((0,), (0,)), ((), ())))              # [n, n]
    state_ref[...] = A_end[:, None] * S + kv

    @pl.when(c == num_chunks - 1)
    def _fin():
        sT_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
          u: jnp.ndarray, state: Optional[jnp.ndarray] = None, *,
          interpret: bool, chunk: int = 16
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,w: [b, s, h, n]; u: [h, n]; state: [b, h, n, n] or None."""
    b, s, h, n = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if state is None:
        state = jnp.zeros((b, h, n, n), jnp.float32)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, 1, n)
    s0 = state.reshape(b * h, n, n).astype(jnp.float32)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, num_chunks=nc)
    o, sT = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, n), lambda bh, c: (bh, 0, 0)),
            pl.BlockSpec((1, n, n), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, n, n), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, n), r.dtype),
            jax.ShapeDtypeStruct((b * h, n, n), jnp.float32),
        ],
        scratch_shapes=[_vmem((n, n), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    out = o.reshape(b, h, s, n).transpose(0, 2, 1, 3)
    return out, sT.reshape(b, h, n, n)
