"""DeepSeek-V3 on the pathfinding path: the MLA graph, the leading dense
layers and the latent-cache memory model, against a plain float32
DeepSeek-V3 block (`repro.kernels.ref`) on seeded weights at a small size.

- decoding one token through the latent cache, absorbed, gives the full
  forward's last position;
- the graph's forward GEMM FLOPs of prefill and decode are the block's
  matrix-product FLOPs;
- parameter counts, the latent cache and its split match the published
  model;
- the graphs of every other architecture are the frozen reference's
  (`bench/crossflow/lmgraph.py`, copied from this program before MLA),
  node by node.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ARCH_IDS, SHAPE_CELLS, ShapeCell, get_config,
                                reduced)
from repro.core import lmgraph, scenarios
from repro.core.parallelism import Strategy
from repro.kernels import ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.crossflow import archconfig as frozen_arch  # noqa: E402
from bench.crossflow import lmgraph as frozen_lmgraph  # noqa: E402

DSV3 = get_config("deepseek-v3")
SMALL = reduced(DSV3)                   # 1 dense + 1 MoE layer, tiny widths


def _block(moe: bool, seed: int = 0):
    return ref.deepseek_weights(SMALL, jax.random.PRNGKey(seed), moe)


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_absorbed_decode_equals_full_forward_at_last_position(moe):
    w = _block(moe, seed=1 + moe)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, SMALL.d_model),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, _ = ref.deepseek_block_ref(SMALL, w, x, moe)
        _, cache = ref.deepseek_block_ref(SMALL, w, x[:, :-1], moe)
        step, (ckv, k_pe) = ref.deepseek_decode_ref(SMALL, w, x[:, -1:],
                                                    cache, moe)
    assert ckv.shape == (2, 9, SMALL.kv_lora_rank)
    assert k_pe.shape == (2, 9, SMALL.qk_rope_head_dim)
    # float32 with a different summation order (absorbed W_UK, W_UV):
    # agreement to a few ulps of the O(1) outputs
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5, atol=1e-6)


def _graph_layer_flops(cell: ShapeCell) -> float:
    g = lmgraph.build_graph(SMALL, cell)
    return sum(n.flops * n.meta.get("repeat", 1) for n in g.nodes.values()
               if n.kind == "gemm" and not n.name.startswith("lm_head"))


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_graph_gemm_flops_equal_the_blocks_matmul_flops(kind):
    batch, seq = 3, 16
    cell = ShapeCell(f"t_{kind}", seq, batch, kind)
    x = jnp.zeros((batch, seq, SMALL.d_model), jnp.float32)
    flops = []
    for layer in range(SMALL.n_layers):
        moe = SMALL.layer_is_moe(layer)
        w = _block(moe)
        if kind == "prefill":
            ref.deepseek_block_ref(SMALL, w, x, moe, flops)
        else:
            cache = ref.deepseek_block_ref(SMALL, w, x[:, :-1], moe)[1]
            ref.deepseek_decode_ref(SMALL, w, x[:, -1:], cache, moe, flops)
    assert _graph_layer_flops(cell) == pytest.approx(sum(flops), rel=0.01)


def test_param_counts_match_the_published_totals():
    # 671B without the 14B multi-token-prediction module; 37B activated
    assert DSV3.param_count() == pytest.approx(671.0e9, rel=0.005)
    assert DSV3.active_param_count() == pytest.approx(37.5e9, rel=0.005)
    assert scenarios.weight_bytes(DSV3) == 2.0 * DSV3.param_count()


def test_latent_cache_bytes_of_decode_32k():
    cell = SHAPE_CELLS["decode_32k"]
    got = scenarios.kv_cache_bytes(DSV3, cell.seq_len, cell.global_batch)
    assert got == 61 * 1152 * 32768 * 128
    # stated as 128 grouped-query heads of 128 it would be 57 times larger
    gqa = dataclasses.replace(DSV3, kv_lora_rank=0)
    assert scenarios.kv_cache_bytes(gqa, cell.seq_len, cell.global_batch) \
        == 61 * 65536 * 32768 * 128


def test_model_axis_does_not_split_the_latent():
    degrees = [scenarios._kv_shard_degree(
        DSV3, Strategy("RC", kp1=1, kp2=kp, dp=16, lp=1)) for kp in
        (1, 8, 64)]
    assert degrees == [16, 16, 16]
    # sequence parallelism splits the context, so it does split the latent
    assert scenarios._kv_shard_degree(
        DSV3, Strategy("RC", kp1=1, kp2=64, dp=16, lp=1, sp=64)) == 16 * 64
    qwen = get_config("qwen3-moe-30b-a3b")
    assert scenarios._kv_shard_degree(
        qwen, Strategy("RC", kp1=1, kp2=8, dp=16, lp=1)) == 16 * 4


def test_deepseek_graph_groups_and_tags():
    g = lmgraph.build_graph(DSV3, SHAPE_CELLS["decode_32k"])
    repeats = {n.name.split(".attn.")[0]: n.meta["repeat"]
               for n in g.nodes.values() if ".attn." in n.name}
    assert repeats == {"attn.global.dense": 3, "attn.global.moe": 58}
    qk = g.nodes["attn.global.moe.attn.qk"]
    # the latent (ctx x 576) is read once per sequence; heads on N
    assert (qk.b, qk.m, qk.n, qk.k) == (128, 32768, 128, 576)
    assert g.nodes["attn.global.moe.moe.router.select"].kind == "elementwise"
    assert g.nodes["attn.global.dense.ffn.up.fwd"].meta["dense"]
    assert all(n.meta.get("mla") for n in g.nodes.values()
               if ".attn." in n.name and n.kind == "gemm")


def _frozen(cfg):
    return frozen_arch.ArchConfig(**{
        f.name: getattr(cfg, f.name)
        for f in dataclasses.fields(frozen_arch.ArchConfig)})


def _nodes(g):
    return [(n.name, n.kind, n.b, n.m, n.n, n.k, n.n_elems, n.flops_per_elem,
             n.rows, n.width, n.dtype_bytes, sorted(n.meta.items()))
            for n in g.nodes.values()], \
        [(e.src, e.dst, e.bytes) for e in g.edges]


@pytest.mark.parametrize("arch", ARCH_IDS + ("paper-lm",))
def test_other_architectures_graphs_are_unchanged(arch):
    cfg = get_config(arch)
    for cell in SHAPE_CELLS.values():
        want = frozen_lmgraph.build_graph(_frozen(cfg),
                                          frozen_arch.SHAPE_CELLS[cell.name])
        assert _nodes(lmgraph.build_graph(cfg, cell)) == _nodes(want), cell


def test_runtime_models_refuse_latent_attention():
    from repro.models import build_model
    with pytest.raises(NotImplementedError, match="kv_lora_rank"):
        build_model(SMALL)
