"""RG-LRU linear-recurrence scan Pallas TPU kernel.

recurrentgemma's Real-Gated Linear Recurrent Unit reduces (after gate
precomputation, done in repro.models.rglru with cheap elementwise jnp) to a
first-order diagonal linear recurrence over the sequence:

    h_t = a_t * h_{t-1} + b_t        a, b, h: (width,) per step

The kernel carries h in VMEM scratch across sequence blocks (TPU grid
iterations execute in order along the last grid dim, making a sequential
scan natural); inside a block a fori_loop walks the rows. HBM traffic is
exactly one read of (a, b) and one write of h — the roofline optimum for a
bandwidth-bound recurrence (vs. log-depth associative scans that re-stream
intermediates; DESIGN.md hardware-adaptation notes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SUBLANE, resolve_interpret, tile


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, bt: int):
    t_i = pl.program_id(1)

    @pl.when(t_i == 0)
    def _init():
        carry_ref[...] = h0_ref[0]

    def step(i, h):                        # h: (1, width)
        row = pl.ds(i, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, bt, step, carry_ref[...])


def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array,
               block_t: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t, h_0 given. a/b: (batch, seq, width),
    h0: (batch, width). Returns h: (batch, seq, width). Time blocks are
    multiples of 8 rows that divide the sequence, or all of it."""
    batch, seq, width = a.shape
    bt = tile(block_t, seq, SUBLANE)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, bt=bt),
        grid=(batch, seq // bt),
        in_specs=[
            pl.BlockSpec((1, bt, width), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, bt, width), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, 1, width), lambda bi, ti: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, width), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, seq, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, width), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a.astype(jnp.float32), b.astype(jnp.float32),
      h0.astype(jnp.float32).reshape(batch, 1, width))
