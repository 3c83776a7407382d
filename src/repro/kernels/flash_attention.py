"""Pallas TPU flash attention (forward) with GQA and causal masking.

TPU-native adaptation: online-softmax tiling where the KV loop is the minor
(sequential) grid dimension; m/l/acc accumulators live in VMEM scratch and
persist across KV steps, so HBM traffic is O(s*d) per head instead of
O(s^2). Block shapes are MXU-aligned (multiples of 128 on the contracting
and lane dims). Validated against ref.sdpa in interpret mode on CPU; on
real TPU the same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  num_k_blocks: int):
    """Grid: (bh, nq, nk) — nk is minor/sequential; scratch persists."""
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv block strictly above the diagonal contributes nothing
    need = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(need if causal else (j >= 0))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                          (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                          (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jnp.ndarray:
    """q: [b, sq, h, d]; k/v: [b, sk, kh, d]. Returns [b, sq, h, dv]."""
    b, sq, h, d = q.shape
    _, sk, kh, dv = v.shape
    g = h // kh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k

    # layout: fold heads into the leading grid dim; kv head = head // g
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kh_ = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, dv)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               num_k_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, g=g: (bh // g, j, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda bh, i, j, g=g: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dv), q.dtype),
        scratch_shapes=[
            _vmem((block_q,), jnp.float32),        # m: running max
            _vmem((block_q,), jnp.float32),        # l: running denom
            _vmem((block_q, dv), jnp.float32),     # acc: running numerator
        ],
        interpret=interpret,
    )(qh, kh_, vh)
    return out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
