"""jit'd public wrappers for the Pallas kernels.

Every op takes `use_pallas` / `interpret` switches. The default path is the
jnp reference (XLA), so the 512-device dry-run can lower; `use_pallas=True`
runs the Pallas kernel, compiled on a TPU and interpreted elsewhere unless
`interpret` says otherwise. CrossFlow's tiling search feeds `block_shape`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm as gemm_pallas
from repro.kernels.rglru import rglru_scan as rglru_pallas


@functools.partial(jax.jit, static_argnames=("block_shape", "use_pallas",
                                             "interpret"))
def matmul(x: jax.Array, w: jax.Array,
           block_shape: Optional[Tuple[int, int, int]] = None,
           use_pallas: bool = False,
           interpret: Optional[bool] = None) -> jax.Array:
    if use_pallas:
        return gemm_pallas(x, w, block_shape=block_shape,
                           interpret=interpret)
    return jnp.dot(x, w)


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                             "interpret", "block_q",
                                             "block_kv"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
              window: Optional[int] = None, use_pallas: bool = False,
              interpret: Optional[bool] = None, block_q: int = 128,
              block_kv: int = 128) -> jax.Array:
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=interpret)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array,
               use_pallas: bool = False,
               interpret: Optional[bool] = None) -> jax.Array:
    if use_pallas:
        return rglru_pallas(a, b, h0, interpret=interpret)
    return ref.rglru_scan_ref(a, b, h0)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_q", "block_kv"))
def mlstm(q: jax.Array, k: jax.Array, v: jax.Array, f_cum: jax.Array,
          log_i: jax.Array, use_pallas: bool = False,
          interpret: Optional[bool] = None, block_q: int = 128,
          block_kv: int = 128) -> jax.Array:
    from repro.kernels.mlstm import mlstm_parallel
    if use_pallas:
        return mlstm_parallel(q, k, v, f_cum, log_i, block_q=block_q,
                              block_kv=block_kv, interpret=interpret)
    return ref.mlstm_parallel_ref(q, k, v, f_cum, log_i)
