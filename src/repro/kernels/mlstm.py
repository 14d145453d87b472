"""mLSTM parallel-form Pallas TPU kernel (xLSTM matrix-memory blocks).

The xLSTM mLSTM training recurrence in parallel form is decay-weighted
linear attention:

    a_tj  = F_t - F_j + i_j            (F = cumsum log f, causal j <= t)
    w_tj  = exp(a_tj - m_t) * (q_t . k_j)
    h_t   = sum_j w_tj v_j / max(|sum_j w_tj|, exp(-m_t))

Blocked like flash attention: grid (b*h, sq/bq, skv/bkv) with kv innermost;
scratch carries the running stabilizer m, numerator acc and signed
denominator. Two MXU GEMMs per block; the decay matrix is VPU elementwise.
Oracle: repro.kernels.ref.mlstm_parallel_ref.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, resolve_interpret, tile

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, fcq_ref, fck_ref, li_ref, o_ref,
                  m_ref, num_ref, den_ref, *, scale: float, bq: int,
                  bkv: int, n_kv: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    q = q_ref[0] * scale                    # (bq, d)
    k = k_ref[0]
    v = v_ref[0]
    fq = fcq_ref[0, 0]                      # (bq,)  F_t rows of the q block
    fk = fck_ref[0, 0]                      # (bkv,) F_j rows of the kv block
    ik = li_ref[0, 0]                       # (bkv,) log i_j

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = kv_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    a = fq[:, None] - fk[None, :] + ik[None, :]
    a = jnp.where(q_pos >= k_pos, a, NEG_INF)

    m_prev = m_ref[...]                     # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(a, axis=1, keepdims=True))
    d_mat = jnp.exp(a - m_new)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    w = qk * d_mat
    corr = jnp.exp(m_prev - m_new)
    num_ref[...] = (num_ref[...] * corr
                    + jax.lax.dot(w.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
    den_ref[...] = den_ref[...] * corr + jnp.sum(w, axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _done():
        denom = jnp.maximum(jnp.abs(den_ref[...]), jnp.exp(-m_ref[...]))
        o_ref[0] = (num_ref[...] / denom).astype(o_ref.dtype)


def mlstm_parallel(q: jax.Array, k: jax.Array, v: jax.Array,
                   f_cum: jax.Array, log_i: jax.Array,
                   block_q: int = 128, block_kv: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """q/k/v: (b, h, s, d); f_cum/log_i: (b, h, s). Returns (b, h, s, d).

    The gate rows ride in blocks whose lane dim is the sequence block, so
    blocks are multiples of 128 that divide the sequence, or all of it.
    """
    b, h, s, d = q.shape
    scale = d ** -0.5
    bq = tile(block_q, s, LANE)
    bkv = tile(block_kv, s, LANE)
    n_kv = s // bkv

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)
    fc = f_cum.reshape(b * h, 1, s).astype(jnp.float32)
    li = log_i.reshape(b * h, 1, s).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, scale=scale, bq=bq, bkv=bkv,
                          n_kv=n_kv),
        grid=(b * h, s // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 1, bkv), lambda bh, qi, ki: (bh, 0, ki)),
            pl.BlockSpec((1, 1, bkv), lambda bh, qi, ki: (bh, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr, fc, fc, li)
    return out.reshape(b, h, s, d)
