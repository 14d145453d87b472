"""Pure-jnp oracles for every Pallas kernel (the allclose targets), and
a plain DeepSeek-V3 block that the CrossFlow graph of latent attention is
checked against."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def gemm_ref(x: jax.Array, w: jax.Array, out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or x.dtype
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32)
                   ).astype(out_dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Naive softmax attention with GQA + causal + local-window masking."""
    b, h, sq, d = q.shape
    _, h_kv, skv, _ = k.shape
    group = h // h_kv
    kf = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def mlstm_parallel_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                       f_cum: jax.Array, log_i: jax.Array) -> jax.Array:
    """Naive decay-weighted linear attention (xLSTM parallel form)."""
    b, h, s, d = q.shape
    scale = d ** -0.5
    a = (f_cum[..., :, None] - f_cum[..., None, :]
         + log_i[..., None, :])                          # (b, h, s, s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    a = jnp.where(causal[None, None], a, -1e30)
    m = jnp.max(a, axis=-1, keepdims=True)
    dmat = jnp.exp(a - m)
    qk = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                    k.astype(jnp.float32))
    w = qk * dmat
    num = jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))
    den = jnp.maximum(jnp.abs(jnp.sum(w, axis=-1, keepdims=True)),
                      jnp.exp(-m))
    return (num / den).astype(q.dtype)


def rglru_scan_ref(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t via lax.scan over the sequence."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (jnp.swapaxes(a32, 0, 1), jnp.swapaxes(b32, 0, 1)))
    return jnp.swapaxes(hs, 0, 1)


# ---------------------------------------------------------------------------
# DeepSeek-V3 block: multi-head latent attention (MLA), dense FFN or
# group-limited routed experts.  The layer equations of the DeepSeek-V3
# technical report (arXiv:2412.19437, section 2.1) in float32; YaRN's
# RoPE rescaling and the multi-token-prediction layer are left out.
# Every matrix product goes through `_mm`, which adds its multiply-adds
# (2 per product term) to ``flops``; masked score and value products of
# causal attention count the half that causality needs.
# ---------------------------------------------------------------------------


def _mm(flops: list, spec: str, a: jax.Array, b: jax.Array,
        share: float = 1.0) -> jax.Array:
    ins, out = spec.split("->")
    size = {}
    for letters, x in zip(ins.split(","), (a, b)):
        size.update(zip(letters, x.shape))
    n = 2.0 * share
    for v in size.values():
        n *= v
    flops.append(n)
    return jnp.einsum(spec, a, b)


def _rms(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x: jax.Array, pos: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding of ``x`` (..., S, H, R) at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]   # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def deepseek_weights(cfg, key: jax.Array, moe: bool) -> dict:
    """Seeded float32 weights of one block of ``cfg`` (a
    `repro.configs.base.ArchConfig` with the MLA fields set)."""
    d, nh, e = cfg.d_model, cfg.n_heads, cfg.n_experts
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat, ql = cfg.kv_lora_rank, cfg.q_lora_rank
    shapes = {"w_dq": (d, ql), "w_uq": (ql, nh * (nope + rope)),
              "w_dkv": (d, lat + rope), "w_ukv": (lat, nh * (nope + dv)),
              "w_o": (nh * dv, d)}
    if moe:
        fm, fs = cfg.moe_d_ff, cfg.moe_d_ff * cfg.n_shared_experts
        shapes.update(router=(d, e), bias=(e,), e_gate=(e, d, fm),
                      e_up=(e, d, fm), e_down=(e, fm, d), s_gate=(d, fs),
                      s_up=(d, fs), s_down=(fs, d))
    else:
        shapes.update(gate=(d, cfg.d_ff), up=(d, cfg.d_ff),
                      down=(cfg.d_ff, d))
    keys = jax.random.split(key, len(shapes) + 4)
    w = {k: jax.random.normal(kk, s, jnp.float32)
         / jnp.sqrt(float(s[-2] if len(s) > 1 else 1))
         for kk, (k, s) in zip(keys, sorted(shapes.items()))}
    for kk, (k, n) in zip(keys[len(shapes):], (("g_attn", d), ("g_ffn", d),
                                               ("g_q", ql), ("g_kv", lat))):
        w[k] = 1.0 + 0.1 * jax.random.normal(kk, (n,), jnp.float32)
    return w


def _ffn_ref(cfg, w: dict, h: jax.Array, moe: bool, flops: list,
             routed_scale: float = 2.5) -> jax.Array:
    """Dense SwiGLU FFN, or sigmoid group-limited top-k routing over the
    experts plus the shared expert; ``h`` is (T, d)."""
    def swiglu(x, g, u, dn):
        a = _mm(flops, "td,df->tf", x, g)
        b = _mm(flops, "td,df->tf", x, u)
        return _mm(flops, "tf,fd->td", jax.nn.silu(a) * b, dn)

    if not moe:
        return swiglu(h, w["gate"], w["up"], w["down"])
    t, e, k = h.shape[0], cfg.n_experts, cfg.experts_per_token
    groups, keep = cfg.n_expert_groups, cfg.topk_expert_groups
    score = jax.nn.sigmoid(_mm(flops, "td,de->te", h, w["router"]))
    choice = (score + w["bias"]).reshape(t, groups, e // groups)
    group_score = jnp.sum(jax.lax.top_k(choice, 2)[0], -1)      # (T, G)
    kept = jax.lax.top_k(group_score, keep)[1]
    group_mask = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(group_mask[:, :, None], choice, -jnp.inf)
    idx = jax.lax.top_k(masked.reshape(t, e), k)[1]               # (T, k)
    gate = jnp.take_along_axis(score, idx, -1)
    gate = routed_scale * gate / jnp.sum(gate, -1, keepdims=True)
    a = _mm(flops, "td,tkdf->tkf", h, w["e_gate"][idx])
    b = _mm(flops, "td,tkdf->tkf", h, w["e_up"][idx])
    y = _mm(flops, "tkf,tkfd->tkd", jax.nn.silu(a) * b, w["e_down"][idx])
    routed = jnp.sum(gate[:, :, None] * y, 1)
    return routed + swiglu(h, w["s_gate"], w["s_up"], w["s_down"])


def _mla_q(cfg, w: dict, h: jax.Array, pos: jax.Array, flops: list):
    nh, nope = cfg.n_heads, cfg.qk_nope_head_dim
    b, s, _ = h.shape
    cq = _rms(_mm(flops, "bsd,dq->bsq", h, w["w_dq"]), w["g_q"])
    q = _mm(flops, "bsq,qo->bso", cq, w["w_uq"]).reshape(b, s, nh, -1)
    return q[..., :nope], _rope(q[..., nope:], pos)


def _mla_latent(cfg, w: dict, h: jax.Array, pos: jax.Array, flops: list):
    """The cached pair: normalized latent (B, S, lat), RoPE key (B, S, r)."""
    lat = cfg.kv_lora_rank
    ckv = _mm(flops, "bsd,dc->bsc", h, w["w_dkv"])
    k_pe = _rope(ckv[..., None, lat:], pos)[..., 0, :]
    return _rms(ckv[..., :lat], w["g_kv"]), k_pe


def deepseek_block_ref(cfg, w: dict, x: jax.Array, moe: bool,
                       flops: Optional[list] = None):
    """One block over a whole sequence, unabsorbed MLA with a causal
    mask: ``x`` (B, S, d) -> (output (B, S, d), latent cache)."""
    flops = [] if flops is None else flops
    nh, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    b, s, d = x.shape
    pos = jnp.arange(s)
    h = _rms(x, w["g_attn"])
    q_nope, q_pe = _mla_q(cfg, w, h, pos, flops)
    ckv, k_pe = _mla_latent(cfg, w, h, pos, flops)
    kv = _mm(flops, "bsc,co->bso", ckv, w["w_ukv"]).reshape(b, s, nh, -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe[:, :, None, :],
                                          (b, s, nh, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    sc = _mm(flops, "bqhk,bshk->bhqs", q, k, share=0.5) \
        / jnp.sqrt(float(q.shape[-1]))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = _mm(flops, "bhqs,bshv->bqhv", jax.nn.softmax(sc, -1), kv[..., nope:],
            share=0.5)
    x = x + _mm(flops, "bso,od->bsd", o.reshape(b, s, nh * dv), w["w_o"])
    y = _ffn_ref(cfg, w, _rms(x, w["g_ffn"]).reshape(b * s, d), moe, flops)
    return x + y.reshape(b, s, d), (ckv, k_pe)


def deepseek_decode_ref(cfg, w: dict, x: jax.Array, cache, moe: bool,
                        flops: Optional[list] = None):
    """One new token per sequence, absorbed MLA over the latent cache:
    ``x`` (B, 1, d) at position ``len(cache)`` -> (output, new cache)."""
    flops = [] if flops is None else flops
    nh, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    b, _, d = x.shape
    pos = jnp.arange(cache[0].shape[1], cache[0].shape[1] + 1)
    h = _rms(x, w["g_attn"])
    q_nope, q_pe = _mla_q(cfg, w, h, pos, flops)
    ckv, k_pe = _mla_latent(cfg, w, h, pos, flops)
    ckv = jnp.concatenate([cache[0], ckv], 1)
    k_pe = jnp.concatenate([cache[1], k_pe], 1)
    w_ukv = w["w_ukv"].reshape(lat, nh, nope + dv)
    q_lat = _mm(flops, "bhn,chn->bhc", q_nope[:, 0], w_ukv[..., :nope])
    q = jnp.concatenate([q_lat, q_pe[:, 0]], -1)            # (B, H, lat+r)
    sc = _mm(flops, "bhk,bsk->bhs", q, jnp.concatenate([ckv, k_pe], -1)) \
        / jnp.sqrt(float(nope + q_pe.shape[-1]))
    o_lat = _mm(flops, "bhs,bsc->bhc", jax.nn.softmax(sc, -1), ckv)
    o = _mm(flops, "bhc,chv->bhv", o_lat, w_ukv[..., nope:])
    x = x + _mm(flops, "bo,od->bd", o.reshape(b, nh * dv), w["w_o"])[:, None]
    y = _ffn_ref(cfg, w, _rms(x, w["g_ffn"]).reshape(b, d), moe, flops)
    return x + y.reshape(b, 1, d), (ckv, k_pe)
