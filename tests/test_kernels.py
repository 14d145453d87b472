"""Per-kernel allclose vs ref.py oracles: shape/dtype sweeps + hypothesis.

The kernels resolve to interpret mode on the CPU (the kernel body runs in
Python) and compile on a TPU; tests/test_tpu_compile.py compiles them for a
described v5e chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (pip install -e '.[dev]')")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm, pick_block_shape
from repro.kernels.rglru import rglru_scan


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) \
        .astype(dtype)


def test_kernels_resolve_to_interpret_mode_on_cpu(monkeypatch):
    """No kernel defaults to interpret mode on a TPU, and every one does on
    the CPU; an explicit flag wins."""
    from repro.kernels import common
    assert jax.default_backend() == "cpu"
    assert common.resolve_interpret(None) is True
    assert common.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.resolve_interpret(None) is False
    assert common.resolve_interpret(True) is True


# ---------------------------------------------------------------------- GEMM
@pytest.mark.parametrize("m,n,k", [
    (128, 128, 128), (256, 512, 128), (64, 384, 256), (8, 128, 128),
    (256, 256, 1024), (40, 120, 72),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_matches_ref(m, n, k, dtype):
    x, w = _rand(0, (m, k), dtype), _rand(1, (k, n), dtype)
    got = gemm(x, w, interpret=True)
    want = ref.gemm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("block", [(64, 64, 64), (128, 128, 128),
                                   (32, 128, 256)])
def test_gemm_block_shapes(block):
    """CrossFlow-chosen BlockSpecs must not change the numerics."""
    x, w = _rand(2, (256, 256), jnp.float32), _rand(3, (256, 256),
                                                    jnp.float32)
    got = gemm(x, w, block_shape=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gemm_ref(x, w)),
                               rtol=1e-4, atol=1e-3)


@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300),
       bm=st.integers(1, 512), bn=st.integers(1, 512), bk=st.integers(1, 512))
@settings(max_examples=200, deadline=None)
def test_pick_block_shape_always_divides(m, n, k, bm, bn, bk):
    tm, tn, tk = pick_block_shape(m, n, k, bm, bn, bk)
    assert m % tm == 0 and n % tn == 0 and k % tk == 0
    assert 1 <= tm <= m and 1 <= tn <= n and 1 <= tk <= k
    # tiles the TPU lowering accepts: (8, 128) multiples or the whole dim
    assert tm % 8 == 0 or tm == m
    assert tn % 128 == 0 or tn == n
    assert tk % 128 == 0 or tk == k


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,h,hkv,sq,skv,d", [
    (1, 4, 4, 128, 128, 64),        # MHA square
    (2, 8, 2, 128, 128, 64),        # GQA 4:1
    (1, 4, 1, 256, 256, 32),        # MQA
    (1, 2, 2, 128, 384, 64),        # cross/prefix: skv > sq
])
def test_flash_attention_matches_ref(b, h, hkv, sq, skv, d):
    q = _rand(0, (b, h, sq, d), jnp.float32)
    k = _rand(1, (b, hkv, skv, d), jnp.float32)
    v = _rand(2, (b, hkv, skv, d), jnp.float32)
    causal = sq == skv
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_local_window(window):
    b, h, s, d = 1, 2, 256, 32
    q, k, v = (_rand(i, (b, h, s, d), jnp.float32) for i in range(3))
    got = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    b, h, s, d = 1, 4, 128, 64
    q, k, v = (_rand(i, (b, h, s, d), jnp.bfloat16) for i in range(3))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


@given(bq=st.sampled_from([32, 64, 128]), bkv=st.sampled_from([32, 64, 128]))
@settings(max_examples=9, deadline=None)
def test_flash_attention_block_invariance(bq, bkv):
    """Output must be independent of the blocking (property)."""
    b, h, s, d = 1, 2, 128, 32
    q, k, v = (_rand(i, (b, h, s, d), jnp.float32) for i in range(3))
    got = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# -------------------------------------------------------------------- mLSTM
@pytest.mark.parametrize("b,h,s,d", [(1, 2, 128, 64), (2, 4, 256, 32)])
def test_mlstm_kernel_matches_ref(b, h, s, d):
    from repro.kernels.mlstm import mlstm_parallel
    q = _rand(0, (b, h, s, d), jnp.float32)
    k = _rand(1, (b, h, s, d), jnp.float32)
    v = _rand(2, (b, h, s, d), jnp.float32)
    log_f = jax.nn.log_sigmoid(_rand(3, (b, h, s), jnp.float32) + 1.0)
    f_cum = jnp.cumsum(log_f, axis=-1)
    log_i = _rand(4, (b, h, s), jnp.float32) * 0.3
    got = mlstm_parallel(q, k, v, f_cum, log_i, interpret=True)
    want = ref.mlstm_parallel_ref(q, k, v, f_cum, log_i)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


@given(bq=st.sampled_from([32, 64, 128]), bkv=st.sampled_from([32, 64]))
@settings(max_examples=6, deadline=None)
def test_mlstm_kernel_block_invariance(bq, bkv):
    from repro.kernels.mlstm import mlstm_parallel
    b, h, s, d = 1, 2, 128, 32
    q, k, v = (_rand(i, (b, h, s, d), jnp.float32) for i in range(3))
    log_f = jax.nn.log_sigmoid(_rand(7, (b, h, s), jnp.float32) + 1.0)
    f_cum = jnp.cumsum(log_f, axis=-1)
    log_i = _rand(8, (b, h, s), jnp.float32) * 0.3
    got = mlstm_parallel(q, k, v, f_cum, log_i, block_q=bq, block_kv=bkv,
                         interpret=True)
    want = ref.mlstm_parallel_ref(q, k, v, f_cum, log_i)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


# ------------------------------------------------------------------- RG-LRU
@pytest.mark.parametrize("batch,seq,width", [
    (1, 128, 64), (2, 256, 128), (3, 96, 32),
])
def test_rglru_scan_matches_ref(batch, seq, width):
    a = jax.nn.sigmoid(_rand(0, (batch, seq, width), jnp.float32))  # |a|<1
    b = _rand(1, (batch, seq, width), jnp.float32)
    h0 = _rand(2, (batch, width), jnp.float32)
    got = rglru_scan(a, b, h0, interpret=True)
    want = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@given(seq=st.sampled_from([64, 96, 128, 192]),
       bt=st.sampled_from([16, 32, 64, 128]))
@settings(max_examples=12, deadline=None)
def test_rglru_block_invariance(seq, bt):
    a = jax.nn.sigmoid(_rand(3, (1, seq, 32), jnp.float32))
    b = _rand(4, (1, seq, 32), jnp.float32)
    h0 = jnp.zeros((1, 32), jnp.float32)
    got = rglru_scan(a, b, h0, block_t=bt, interpret=True)
    want = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_rglru_decay_property():
    """With b=0 the state must decay monotonically for 0<a<1 (property)."""
    seq, w = 64, 16
    a = jnp.full((1, seq, w), 0.9)
    b = jnp.zeros((1, seq, w))
    h0 = jnp.ones((1, w))
    h = np.asarray(rglru_scan(a, b, h0, interpret=True))[0]
    norms = np.linalg.norm(h, axis=-1)
    assert np.all(np.diff(norms) < 0)
