"""Pipeline parallelism (the paper's LP axis) via shard_map + ppermute.

GPipe schedule: the layer stack is cut into S stages (one per mesh 'stage'
axis index); a microbatch streams through stages with collective_permute
moving activations between neighbours. Implemented with shard_map so each
stage executes only its own parameters — the standard JAX SPMD pipeline
pattern (rotate-and-compute over S + M - 1 ticks).

The paper's DPE treats LP as a graph cut with p2p cross-edges; this module
is the runtime realization. The planner proposes LP>1 for deep models on
multi-pod meshes (candidate_strategies); the dry-run exercises it through
`pipelined_loss_fn` variants.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_params_split(params_stacked: Any, n_stages: int) -> Any:
    """Reshape scan-stacked layer params (L, ...) -> (S, L/S, ...)."""
    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(r, params_stacked)


def gpipe(fn_stage: Callable, mesh: Mesh, stage_axis: str = "stage",
          n_microbatches: int = 4):
    """Wrap a per-stage apply `fn_stage(stage_params, x) -> x` into a
    GPipe pipeline over the mesh's `stage` axis.

    Returns pipelined(params_staged, x_microbatched) where
    params_staged leaves have leading dim S (sharded over stage_axis) and
    x_microbatched is (M, mb, ...) with M == n_microbatches.
    """
    s = mesh.shape[stage_axis]

    def per_device(params_local, x_all):
        # params_local: leaves (1, L/S, ...) — this device's stage params
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage_id = jax.lax.axis_index(stage_axis)
        m = x_all.shape[0]
        n_ticks = m + s - 1
        buf = jnp.zeros_like(x_all[0])
        outs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any remain)
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(stage_id == 0,
                             x_all[inject].astype(buf.dtype), buf)
            y = fn_stage(params_local, x_in)
            # last stage emits finished microbatch t - (s-1)
            emit = t - (s - 1)
            emit_c = jnp.clip(emit, 0, m - 1)
            outs = jnp.where(
                (stage_id == s - 1) & (emit >= 0),
                outs.at[emit_c].set(y.astype(outs.dtype)), outs)
            # rotate activations to the next stage
            buf = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % s) for i in range(s)])
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(n_ticks))
        # every device returns outs; only the last stage's is meaningful —
        # mask + psum broadcasts it to all stages (ppermute cannot fan out)
        if s > 1:
            mask = (stage_id == s - 1).astype(outs.dtype)
            outs = jax.lax.psum(outs * mask, stage_axis)
        return outs

    def pipelined(params_staged, x_microbatched):
        in_specs = (jax.tree.map(lambda _: P(stage_axis), params_staged),
                    P())
        return jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(
            params_staged, x_microbatched)

    return pipelined
