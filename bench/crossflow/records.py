"""Per-point result records of the frozen reference model.

A copy of the scalar `record` paths of the program's train and
serving-traffic scenarios (with the serving memory model they use): one
design point's phase rows in, one result record out, in float64 on the
host.  Nothing here is batched, folded or compiled.

The serving memory model comes from the caller as a `MemoryModel`: this
module's `weight_bytes`, `kv_cache_bytes` and `_kv_shard_degree`, or a
configuration's own (see `bench.crossflow`).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Mapping, NamedTuple, Tuple

import numpy as np

from bench.crossflow import roofline, traffic
from bench.crossflow.archconfig import SHAPE_CELLS, ArchConfig, ShapeCell
from bench.crossflow.parallelism import Strategy

DTYPE_BYTES = 2                     # bf16 weights / KV cache


def point_key(arch: str, cell: str, mesh: Tuple[int, ...], logic: str,
              hbm: str, net: str, scale: float, strategy_name: str) -> str:
    return "|".join([arch, cell, "x".join(map(str, mesh)), logic, hbm,
                     net, f"{scale:g}", strategy_name])


def candidate_strategies(cfg: ArchConfig, cell: ShapeCell,
                         mesh_shape: Tuple[int, ...]) -> List[Strategy]:
    """The strategy axis of a sweep: what the runtime can realize on the
    mesh (the model axis used for RC sharding, EP for MoE, SP for long
    context, LP over the pod axis for deep models on multi-pod meshes)."""
    total = 1
    for s in mesh_shape:
        total *= s
    model = mesh_shape[-1]
    dp = total // model
    cands = [Strategy("RC", kp1=1, kp2=model, dp=dp, lp=1)]
    if cfg.is_moe:
        cands.append(Strategy("RC", kp1=1, kp2=model, dp=dp, lp=1, ep=model))
    if cell.name == "long_500k":
        cands.append(Strategy("RC", kp1=1, kp2=model, dp=dp, lp=1, sp=model))
    if cell.kind == "train" and cfg.n_layers >= 32 and len(mesh_shape) == 3:
        cands.append(Strategy("RC", kp1=1, kp2=model,
                              dp=dp // mesh_shape[0], lp=mesh_shape[0]))
    return cands


def label_fields(arch, cell, mesh, logic, hbm, net, scale,
                 strategy: Strategy) -> Dict[str, object]:
    return {"arch": arch, "cell": cell, "mesh": "x".join(map(str, mesh)),
            "logic": logic, "hbm": hbm, "net": net, "scale": scale,
            "strategy": strategy.name, "devices": strategy.devices}


def weight_bytes(cfg: ArchConfig, dtype_bytes: int = DTYPE_BYTES) -> float:
    return float(cfg.param_count()) * dtype_bytes


def kv_cache_bytes(cfg: ArchConfig, kv_len: int, batch: int,
                   dtype_bytes: int = DTYPE_BYTES) -> float:
    hd = cfg.resolved_head_dim
    if cfg.is_encoder_decoder:
        dec = min(cfg.decoder_len, kv_len)
        per_seq = cfg.n_layers * 2.0 * cfg.n_kv_heads * hd * \
            (dec + kv_len) * dtype_bytes
        return per_seq * batch
    per_seq = 0.0
    for i in range(cfg.n_layers):
        bk = cfg.block_kind(i)
        if bk == "attn":
            ctx = kv_len
            if cfg.attn_kind(i) == "local":
                ctx = min(kv_len, cfg.local_window)
            per_seq += 2.0 * cfg.n_kv_heads * hd * ctx * dtype_bytes
        elif bk == "rglru":
            w = cfg.lru_width or cfg.d_model
            per_seq += (w + cfg.conv1d_width * w) * 4
        else:
            per_seq += cfg.n_heads * hd * hd * 4
    return per_seq * batch


def _kv_shard_degree(cfg: ArchConfig, st: Strategy) -> int:
    kp_shard = min(st.kp, max(cfg.n_kv_heads, 1))
    if st.sp > 1:
        kp_shard = st.kp
    return st.dp * st.lp * max(kp_shard, 1)


class MemoryModel(NamedTuple):
    """What a serving record's memory fields are computed from."""

    weight_bytes: Callable[[ArchConfig], float]
    kv_cache_bytes: Callable[[ArchConfig, int, int], float]
    kv_shard_degree: Callable[[ArchConfig, Strategy], int]


def serving_bytes_per_device(cfg: ArchConfig, st: Strategy, cell,
                             memory: MemoryModel) -> Tuple[float, float]:
    w_dev = memory.weight_bytes(cfg) / max(st.kp * st.lp, 1)
    kv_dev = memory.kv_cache_bytes(cfg, cell.seq_len, cell.global_batch) \
        / memory.kv_shard_degree(cfg, st)
    return w_dev, kv_dev


def train_record(labels: Mapping, rows: np.ndarray) -> Dict:
    row = rows[0]
    return {**labels, "time_s": float(row[0]), "compute_s": float(row[1]),
            "comm_s": float(row[2]), "exposed_comm_s": float(row[3])}


def serving_traffic_record(labels: Mapping, rows: np.ndarray,
                           cfg: ArchConfig, st: Strategy,
                           dram_capacity: float, cells: Tuple[str, str],
                           params: Mapping, memory: MemoryModel) -> Dict:
    """``params``: the traffic scenario's flat parameter dict (defaults,
    the configuration's scalars and the record's variant overrides);
    ``memory``: the serving memory model of the configuration."""
    tm, policy, slo = traffic.split_params(
        {**traffic.PARAM_DEFAULTS, **params})
    pc, dc = SHAPE_CELLS[cells[0]], SHAPE_CELLS[cells[1]]
    w_dev, kv_dev = serving_bytes_per_device(cfg, st, dc, memory)
    w_f, kv_f = float(w_dev), float(kv_dev)
    knee = roofline.CAPACITY_PRESSURE_KNEE
    cap = max(float(dram_capacity), 1.0)
    occ = (w_f + kv_f) / cap
    over = max(occ - knee, 0.0) / max(1.0 - knee, 1e-9)
    derate = np.inf if occ >= 1.0 else 1.0 + 0.5 * over * over
    t_pf = float(rows[0][0])
    t_d = float(rows[1][0]) * derate
    c = traffic.build_consts(
        tm, policy, slots=dc.global_batch,
        prefill_tokens=float(pc.global_batch) * pc.seq_len,
        devices=float(st.devices))
    stats = traffic.continuous_batching_stats(
        np, np.float64(t_pf), np.float64(t_d), c)
    ok = traffic.slo_ok(stats, slo)
    f = lambda k: float(np.asarray(stats[k]))  # noqa: E731
    return {**labels,
            **{k: f(k) for k in
               ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                "util", "qps_max", "tokens_per_s",
                "tokens_per_s_per_device", "cost_device_s_per_token")},
            "prefill_s": t_pf, "decode_step_s": t_d,
            "kv_bytes_per_device": kv_f, "weight_bytes_per_device": w_f,
            "hbm_occupancy": occ, "kv_derate": derate,
            "feasible": bool(np.asarray(stats["feasible"])),
            "slo_ok": bool(np.asarray(ok))}
