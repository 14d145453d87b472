"""Pallas TPU kernels for the perf-critical compute layers.

  gemm.py             block-tiled GEMM; BlockSpec (bm, bn, bk) comes from
                      CrossFlow's hierarchical-roofline tiling search
  flash_attention.py  blocked online-softmax attention (causal/local/GQA)
  rglru.py            RG-LRU first-order linear-recurrence scan
  mlstm.py            xLSTM mLSTM decay-linear-attention (parallel form)
  ops.py              jit'd wrappers with use_pallas/interpret switches
  ref.py              pure-jnp oracles (the allclose targets)
  common.py           interpret-mode resolution and TPU tile alignment

Kernels compile on a TPU and run interpreted on the CPU (the tests); an
explicit `interpret=` overrides the platform.
"""

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm, pick_block_shape
from repro.kernels.mlstm import mlstm_parallel
from repro.kernels.rglru import rglru_scan
