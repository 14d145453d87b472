"""Flash-attention Pallas TPU kernel (blocked online softmax).

Needed by the runtime for train/prefill attention at 4k-32k sequence
lengths where materializing (sq, skv) scores would blow VMEM/HBM. Supports
causal masking, GQA (kv heads shared by head groups, via the kv BlockSpec
index_map — no materialized repeat), and a local attention window
(gemma3 / recurrentgemma local layers).

Grid: (batch*heads, sq/bq, skv/bkv), kv innermost; running max m, sum l and
the output accumulator live in VMEM scratch across kv steps (the standard
online-softmax recurrence). TPU adaptation notes in DESIGN.md: block shapes
are (8,128)-aligned, the two GEMMs per block hit the MXU with fp32
accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SUBLANE, resolve_interpret, tile

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 bq: int, bkv: int, n_kv: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                          # (bq, d)
    k = k_ref[0]                          # (bkv, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = kv_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                    # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                 # (bq, bkv)
    correction = jnp.exp(m_prev - m_new)   # (bq, 1)
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * correction
                    + jax.lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, h, sq, d); k/v: (b, h_kv, skv, d) with h % h_kv == 0.

    Returns (b, h, sq, d). `window`: keys with q_pos - k_pos >= window are
    masked (local attention); None = full context. Blocks are multiples
    of 8 rows that divide the sequence, or the whole sequence.
    """
    b, h, sq, d = q.shape
    _, h_kv, skv, _ = k.shape
    assert h % h_kv == 0, (h, h_kv)
    group = h // h_kv
    scale = scale if scale is not None else d ** -0.5
    bq = tile(block_q, sq, SUBLANE)
    bkv = tile(block_kv, skv, SUBLANE)
    n_kv = skv // bkv

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h_kv, skv, d)
    vr = v.reshape(b * h_kv, skv, d)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, n_kv=n_kv),
        grid=(b * h, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)
