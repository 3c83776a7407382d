"""Pallas TPU kernel for the Mamba-1 selective scan.

TPU adaptation: the CUDA kernel's warp-parallel scan becomes a
channel-blocked chunked scan — grid (batch, d_inner blocks, seq chunks),
seq minor/sequential; the per-channel SSM state [d_blk, n] lives in VMEM
scratch and persists across chunks, so HBM sees each input once and the
state never round-trips (the naive XLA scan writes [di, n] per step).
Inside a chunk the recurrence over time runs as a fori_loop on VMEM values
(elementwise VPU work; the heavy projections around the scan are MXU
matmuls that live OUTSIDE this kernel in the mamba block).
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


def _ssm_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, s0_ref,
                y_ref, sT_ref, state_ref, *, chunk: int, num_chunks: int):
    """Grid: (b, d_blocks, nc) — nc minor; state scratch [d_blk, n]."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [C, d_blk]
    dt = dt_ref[0].astype(jnp.float32)        # [C, d_blk]
    A = A_ref[...].astype(jnp.float32)        # [d_blk, n]
    B = B_ref[0].astype(jnp.float32)          # [C, n]
    Cm = C_ref[0].astype(jnp.float32)         # [C, n]
    D = D_ref[...].astype(jnp.float32)        # [1, d_blk]

    def step(t, carry):
        h, ys = carry
        dA = jnp.exp(dt[t][:, None] * A)                  # [d_blk, n]
        dBx = (dt[t] * x[t])[:, None] * B[t][None, :]     # [d_blk, n]
        h = dA * h + dBx
        yt = (h * Cm[t][None, :]).sum(axis=1) + D[0] * x[t]
        ys = jax.lax.dynamic_update_slice_in_dim(ys, yt[None], t, axis=0)
        return h, ys

    ys0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (state_ref[...], ys0))
    state_ref[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(c == num_chunks - 1)
    def _fin():
        sT_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "d_block", "interpret"))
def ssm(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, D: jnp.ndarray, state: Optional[jnp.ndarray] = None,
        *, interpret: bool, chunk: int = 64, d_block: int = 128
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt: [b, s, di]; A: [di, n]; B, C: [b, s, n]; D: [di]."""
    b, s, di = x.shape
    n = A.shape[-1]
    chunk = min(chunk, s)
    d_block = min(d_block, di)
    assert s % chunk == 0 and di % d_block == 0, (s, chunk, di, d_block)
    nc, nd = s // chunk, di // d_block
    if state is None:
        state = jnp.zeros((b, di, n), jnp.float32)

    kernel = functools.partial(_ssm_kernel, chunk=chunk, num_chunks=nc)
    y, sT = pl.pallas_call(
        kernel,
        grid=(b, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, d, c: (bi, c, d)),
            pl.BlockSpec((1, chunk, d_block), lambda bi, d, c: (bi, c, d)),
            pl.BlockSpec((d_block, n), lambda bi, d, c: (d, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, d, c: (bi, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, d, c: (bi, c, 0)),
            pl.BlockSpec((1, d_block), lambda bi, d, c: (0, d)),
            pl.BlockSpec((1, d_block, n), lambda bi, d, c: (bi, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, d, c: (bi, c, d)),
            pl.BlockSpec((1, d_block, n), lambda bi, d, c: (bi, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, di), x.dtype),
            jax.ShapeDtypeStruct((b, di, n), jnp.float32),
        ],
        scratch_shapes=[_vmem((d_block, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C, D.reshape(1, di), state.astype(jnp.float32))
    return y, sT
