"""Block-tiled GEMM Pallas TPU kernel.

The paper's central compute object is the (distributed) GEMM; CrossFlow's
hierarchical-roofline tiling search (repro.core.roofline.best_gemm_tiling)
emits an (L2, L1, L0) tile triple — the L1 triple is exactly the VMEM
working set this kernel realizes as its BlockSpec (bm, bn, bk). This is the
cross-layer tie-in: the performance model's tiling decision IS the kernel's
tiling.

Grid layout: (m/bm, n/bn, k/bk), k innermost so each (i, j) output tile
stays resident in a VMEM fp32 scratch accumulator across the contraction
(output-stationary dataflow — the MXU-friendly choice in the paper's eq. 5
reuse taxonomy). MXU alignment: (8, 128) sublane/lane multiples.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, SUBLANE, resolve_interpret, tile


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; k is the innermost grid dim."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pick_block_shape(m: int, n: int, k: int,
                     bm: int = 256, bn: int = 256, bk: int = 512,
                     ) -> Tuple[int, int, int]:
    """Clamp requested tiles to divisors the TPU lowering accepts: bm a
    multiple of 8, bn and bk multiples of 128 (bk is the lane dim of the
    A block), each falling back to the whole dim."""
    return tile(bm, m, SUBLANE), tile(bn, n, LANE), tile(bk, k, LANE)


def gemm(x: jax.Array, w: jax.Array,
         block_shape: Optional[Tuple[int, int, int]] = None,
         out_dtype=None, interpret: Optional[bool] = None) -> jax.Array:
    """C[m, n] = A[m, k] @ B[k, n] via pl.pallas_call with VMEM BlockSpecs.

    `block_shape` defaults to an MXU-friendly (256, 256, 512); callers feed
    CrossFlow's `best_gemm_tiling(...)` L1 triple for the model-chosen
    tiling. `interpret` defaults to the platform (`resolve_interpret`).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = pick_block_shape(m, n, k, *(block_shape or (256, 256, 512)))
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w)
