"""Compile the Pallas kernels and a bucketed design executable for a TPU v5e.

Nothing runs: each program is lowered and compiled for one chip of a
described ``v5e:2x2`` topology, at the widths of the models that use it
(qwen1.5-0.5b for the GEMM and attention, recurrentgemma-2b for RG-LRU and
windowed GQA attention, xlstm-125m for mLSTM).  A kernel the TPU compiler
refuses — a tile off the (8, 128) grid, too much VMEM — fails here instead
of on the chip.  The topology is described inside a fixture, so only the
test process that runs these tests loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, gemm, mlstm_parallel, rglru_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable cannot be read back from the persistent cache on a
    # host without the chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("m,k,n", [
    (4096, 1024, 2816),     # qwen1.5-0.5b FFN up-projection
    (384, 384, 384),        # formerly stepped down to an unaligned 192
    (1000, 1000, 1000),     # no multiple of 128 divides: whole-dim tiles
])
def test_gemm_compiles(one_chip, m, k, n):
    x, w = _shapes(one_chip, ((m, k), BF16), ((k, n), BF16))
    hlo = _compile(lambda a, b: gemm(a, b, interpret=False), x, w).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b,h,h_kv,s,d,window", [
    (4, 16, 16, 512, 64, None),     # qwen1.5-0.5b causal MHA
    (1, 10, 1, 4096, 256, 2048),    # recurrentgemma-2b local GQA layer
])
def test_flash_attention_compiles(one_chip, b, h, h_kv, s, d, window):
    q, k, v = _shapes(one_chip, ((b, h, s, d), BF16),
                      ((b, h_kv, s, d), BF16), ((b, h_kv, s, d), BF16))
    hlo = _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False),
        q, k, v).as_text()
    assert "tpu_custom_call" in hlo


def test_rglru_compiles(one_chip):
    # recurrentgemma-2b: lru_width 2560
    a, b, h0 = _shapes(one_chip, ((2, 2048, 2560), F32),
                       ((2, 2048, 2560), F32), ((2, 2560), F32))
    hlo = _compile(lambda a, b, h: rglru_scan(a, b, h, interpret=False),
                   a, b, h0).as_text()
    assert "tpu_custom_call" in hlo


def test_mlstm_compiles(one_chip):
    # xlstm-125m: 4 heads of 192
    q, k, v, f, i = _shapes(one_chip, ((2, 4, 2048, 192), BF16),
                            ((2, 4, 2048, 192), BF16),
                            ((2, 4, 2048, 192), BF16),
                            ((2, 4, 2048), F32), ((2, 4, 2048), F32))
    hlo = _compile(lambda *a: mlstm_parallel(*a, interpret=False),
                   q, k, v, f, i).as_text()
    assert "tpu_custom_call" in hlo


def test_bucketed_design_executable_compiles(one_chip):
    """The canonical executable one bucket of sweep designs shares."""
    from repro.core import age, compileahead, lmgraph, pathfinder, techlib
    from repro.core.parallelism import Strategy
    from repro.core.roofline import PPEConfig

    graph = lmgraph.gemm_graph(2048, 1024, 4096, train=True)
    ev = pathfinder.BatchedEvaluator(graph, Strategy("RC", kp1=2, kp2=2,
                                                     dp=4),
                                     ppe=PPEConfig(n_tilings=8), cache=None)
    template = age.generate(techlib.make_tech_config("N7", "HBM2E"),
                            age.Budgets.default())
    dv = compileahead.design_vector(
        ("skel", ev._skeleton(template)), lambda: ev._scalar_fn(template),
        (jax.ShapeDtypeStruct((pathfinder.HW_DIM,), F32),))
    rows = 256
    packs = tuple(jax.ShapeDtypeStruct((rows,) + p.shape, p.dtype,
                                       sharding=one_chip) for p in dv.packs)
    hw = jax.ShapeDtypeStruct((rows, pathfinder.HW_DIM), F32,
                              sharding=one_chip)
    wrapper = compileahead.bucket_builder(dv.bucket)()
    compiled = wrapper.lower(packs, hw).compile()
    out = compiled.out_info
    assert out.shape == (rows, len(pathfinder.METRICS))
