"""DeepSeek-V3 in the frozen reference: multi-head latent attention (MLA),
leading dense layers, group-limited routing and a latent-cache memory
model.

Named by ``bench/configs/deepseek-v3.serve.json`` under ``"reference"``.
The layer equations follow the published model (DeepSeek-V3 technical
report, arXiv:2412.19437, section 2.1):

- attention: ``c_q = x W_DQ`` (q_lora_rank), ``q = c_q W_UQ`` per head
  (qk_nope_head_dim + qk_rope_head_dim); ``[c_kv, k_rope] = x W_DKV``
  (kv_lora_rank + qk_rope_head_dim, the only per-token cache);
  ``k_nope, v = c_kv W_UK, c_kv W_UV`` per head; ``o = softmax(q k^T) v
  W_O``.  Train and prefill cost this unabsorbed form.  Decode costs the
  absorbed form: ``q_nope W_UK^T`` per head, scores and values taken
  against the cached ``[c_kv, k_rope]`` itself, then ``W_UV`` per head.
  The cache GEMMs put the heads on N, which the model axis splits, and
  the sequence on B, which data parallelism splits: every KP rank reads
  its sequences' whole latent;
- the first ``n_dense_layers`` layers run a dense SwiGLU FFN of ``d_ff``;
  the rest route each token to ``experts_per_token`` of ``n_experts``
  (sigmoid scores, best ``topk_expert_groups`` of ``n_expert_groups``
  groups first) plus ``n_shared_experts`` shared ones, all ``moe_d_ff``
  wide;
- per token and layer the cache holds ``kv_lora_rank + qk_rope_head_dim``
  values, which no head owns, so the model axis does not split it.

It imports nothing of ``repro``: the program's own MLA builder is what it
checks.
"""

from __future__ import annotations

import dataclasses

from bench.crossflow import archconfig, lmgraph
from bench.crossflow.archconfig import ShapeCell
from bench.crossflow.graph import ComputeGraph
from bench.crossflow.parallelism import Strategy

DTYPE_BYTES = 2                     # bf16 weights and latent cache
# group-limited routing per (token, expert) score: sigmoid, bias, the
# group scores and two top-k selections
ROUTER_SELECT_FLOPS = 10.0


@dataclasses.dataclass(frozen=True)
class ArchConfig(archconfig.ArchConfig):
    n_expert_groups: int = 0
    topk_expert_groups: int = 0
    n_dense_layers: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    def param_count(self) -> int:
        return _params(self, active_only=False)

    def active_param_count(self) -> int:
        return _params(self, active_only=True)


def _params(cfg: ArchConfig, active_only: bool) -> int:
    """Embedding, output head, MLA projections and FFN weights (norms and
    the router's bias are left out, as the default accounting does)."""
    d, nh = cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat, ql = cfg.kv_lora_rank, cfg.q_lora_rank
    attn = (d * ql + ql * nh * (nope + rope)
            + d * (lat + rope) + lat * nh * (nope + dv)
            + nh * dv * d)

    def ffn(width):
        return 3 * d * width                    # SwiGLU: gate, up, down

    routed = cfg.experts_per_token if active_only else cfg.n_experts
    moe = (routed + cfg.n_shared_experts) * ffn(cfg.moe_d_ff) \
        + d * cfg.n_experts
    n_moe = cfg.n_layers - cfg.n_dense_layers
    embed = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    return int(embed + cfg.n_layers * attn + cfg.n_dense_layers
               * ffn(cfg.d_ff) + n_moe * moe)


def _mla(g: ComputeGraph, name: str, cfg: ArchConfig, batch: int,
         q_len: int, kv_len: int, deps, train: bool) -> str:
    d, nh = cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    tokens = batch * q_len
    qa = lmgraph._linear(g, f"{name}.q_a", tokens, d, cfg.q_lora_rank,
                         deps, train)
    q = lmgraph._linear(g, f"{name}.q_b", tokens, cfg.q_lora_rank,
                        nh * (nope + rope), [qa], train)
    if q_len == kv_len:                          # train and prefill
        kva = lmgraph._linear(g, f"{name}.kv_a", batch * kv_len, d,
                              lat + rope, deps, train)
        kv = lmgraph._linear(g, f"{name}.kv_b", batch * kv_len, lat,
                             nh * (nope + dv), [kva], train)
        ctx = max(int(kv_len * 0.5), 1)          # causal
        per_head = dict(b=batch * nh, m=q_len, shard_m=False, shard_n=False,
                        batch_dim="b", kp_b=True, gather_act=False)
        s = g.gemm(f"{name}.qk", n=ctx, k=nope + rope, deps=[q, kv],
                   **per_head)
        p = g.elementwise(f"{name}.softmax", n_elems=batch * nh * q_len * ctx,
                          flops_per_elem=6.0, deps=[s.name])
        last = g.gemm(f"{name}.av", n=dv, k=ctx, deps=[p.name],
                      **per_head).name
        if train:
            g.gemm(f"{name}.qk.bwd", n=ctx, k=nope + rope, deps=[last],
                   **per_head)
            last = g.gemm(f"{name}.av.bwd", n=dv, k=ctx,
                          deps=[f"{name}.qk.bwd"], **per_head).name
    else:                                        # decode, absorbed
        weight = dict(b=nh, shard_m=False, shard_n=False, kp_b=True,
                      gather_act=False, weight=True)
        qn = g.gemm(f"{name}.q_absorb", m=tokens, n=lat, k=nope, deps=[q],
                    **weight)
        kva = lmgraph._linear(g, f"{name}.kv_a", tokens, d, lat + rope,
                              deps, train)
        cache = dict(b=batch, shard_m=False, batch_dim="b", gather_act=False)
        s = g.gemm(f"{name}.qk", m=kv_len, n=q_len * nh, k=lat + rope,
                   deps=[qn.name, kva], **cache)
        p = g.elementwise(f"{name}.softmax",
                          n_elems=batch * nh * q_len * kv_len,
                          flops_per_elem=6.0, deps=[s.name])
        av = g.gemm(f"{name}.av", m=lat, n=q_len * nh, k=kv_len,
                    deps=[p.name], **cache)
        last = g.gemm(f"{name}.v_up", m=tokens, n=dv, k=lat,
                      deps=[av.name], **weight).name
    return lmgraph._linear(g, f"{name}.o", tokens, nh * dv, d, [last], train)


def _moe(g: ComputeGraph, name: str, cfg: ArchConfig, tokens: int, deps,
         train: bool) -> str:
    r = lmgraph._linear(g, f"{name}.router", tokens, cfg.d_model,
                        cfg.n_experts, deps, train)
    sel = g.elementwise(f"{name}.router.select",
                        n_elems=tokens * cfg.n_experts,
                        flops_per_elem=ROUTER_SELECT_FLOPS, deps=[r])
    last = lmgraph._ffn(g, f"{name}.experts", cfg,
                        tokens * cfg.experts_per_token, [sel.name], train,
                        d_ff=cfg.moe_d_ff, moe=True)
    if cfg.n_shared_experts:
        shared = lmgraph._ffn(g, f"{name}.shared", cfg, tokens, deps, train,
                              d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
        last = g.elementwise(f"{name}.combine", n_elems=tokens * cfg.d_model,
                             flops_per_elem=2.0, deps=[last, shared]).name
    return last


def build_graph(cfg: ArchConfig, cell: ShapeCell) -> ComputeGraph:
    """The leading dense layers and the MoE layers as two groups, each
    built once with a ``repeat`` of its layer count."""
    train = cell.kind == "train"
    batch = cell.global_batch
    q_len = 1 if cell.kind == "decode" else cell.seq_len
    kv_len = cell.seq_len
    tokens = batch * q_len
    g = ComputeGraph(f"{cfg.name}|{cell.name}")
    last = g.gather("embed", rows=tokens, width=cfg.d_model).name
    for kind, count in (("dense", cfg.n_dense_layers),
                        ("moe", cfg.n_layers - cfg.n_dense_layers)):
        if not count:
            continue
        nm = f"attn.global.{kind}"
        before = set(g.nodes)
        a = _mla(g, f"{nm}.attn", cfg, batch, q_len, kv_len, [last], train)
        if kind == "moe":
            last = _moe(g, f"{nm}.moe", cfg, tokens, [a], train)
        else:
            last = lmgraph._ffn(g, f"{nm}.ffn", cfg, tokens, [a], train)
        for n in set(g.nodes) - before:
            g.nodes[n].meta["repeat"] = count
    h = lmgraph._linear(g, "lm_head", tokens, cfg.d_model, cfg.vocab_size,
                        [last], train)
    if train or cell.kind == "prefill":
        g.elementwise("ce", n_elems=tokens * cfg.vocab_size,
                      flops_per_elem=4.0, deps=[h])
    g.validate()
    return g


def weight_bytes(cfg: ArchConfig) -> float:
    return float(cfg.param_count()) * DTYPE_BYTES


def kv_cache_bytes(cfg: ArchConfig, kv_len: int, batch: int) -> float:
    """The latent and the shared RoPE key of every layer and token."""
    return float(cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                 * kv_len * batch * DTYPE_BYTES)


def kv_shard_degree(cfg: ArchConfig, st: Strategy) -> int:
    """Data and layer parallelism split the cache; the model axis only
    where it splits the sequence."""
    return st.dp * st.lp * (st.kp if st.sp > 1 else 1)
