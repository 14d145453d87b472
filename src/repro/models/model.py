"""Unified model API: `build_model(cfg)` -> Model with
defs / init / loss / forward / prefill-decode entry points + input_specs
for the dry-run (ShapeDtypeStruct stand-ins, no allocation).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models import common, encdec, lstm, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    defs: Any                                    # ParamDef tree
    loss_fn: Callable                            # (params, batch) -> loss, m
    forward: Callable
    init_cache: Optional[Callable]               # (batch, max_len) -> caches
    decode_step: Optional[Callable]              # (params, caches, tok, pos)
    prefill: Optional[Callable] = None

    def init(self, key: jax.Array, dtype=jnp.float32):
        return common.tree_init(self.defs, key, dtype)

    def abstract_params(self, dtype=jnp.float32):
        return common.tree_abstract(self.defs, dtype)

    def param_pspecs(self, rules: Dict):
        return common.tree_pspecs(self.defs, rules)


def build_model(cfg: ArchConfig) -> Model:
    if cfg.is_mla:
        # the layer library has no latent attention: building GQA under
        # an MLA model's name would run a different model
        raise NotImplementedError(
            f"{cfg.name}: multi-head latent attention (kv_lora_rank="
            f"{cfg.kv_lora_rank}) has no runtime model; it is costed on "
            f"the pathfinding path only (repro.core.lmgraph)")
    if cfg.family == "lstm":
        return Model(
            cfg=cfg, defs=lstm.lstm_defs(cfg),
            loss_fn=lambda p, b, **kw: lstm.loss_fn(p, b, cfg, **kw),
            forward=lambda p, b, **kw: lstm.forward(p, b["tokens"], cfg,
                                                    **kw),
            init_cache=None, decode_step=None)
    if cfg.is_encoder_decoder:
        return Model(
            cfg=cfg, defs=encdec.encdec_defs(cfg),
            loss_fn=lambda p, b, **kw: encdec.loss_fn(p, b, cfg, **kw),
            forward=lambda p, b, **kw: encdec.forward(
                p, b["frames"], b["tokens"], cfg, **kw),
            init_cache=lambda batch, max_len, dtype=jnp.bfloat16:
                encdec.init_cache(cfg, batch, max_len, dtype),
            decode_step=lambda p, c, t, pos, **kw:
                encdec.decode_step(p, c, t, pos, cfg, **kw),
            prefill=lambda p, b, **kw: encdec.prefill(p, b["frames"], cfg,
                                                      **kw))
    return Model(
        cfg=cfg, defs=transformer.lm_defs(cfg),
        loss_fn=lambda p, b, **kw: transformer.loss_fn(p, b, cfg, **kw),
        forward=lambda p, b, **kw: transformer.forward(
            p, b["tokens"], cfg, embeds=b.get("embeds"), **kw),
        init_cache=lambda batch, max_len, dtype=jnp.bfloat16:
            transformer.init_cache(cfg, batch, max_len, dtype),
        decode_step=lambda p, c, t, pos, **kw:
            transformer.decode_step(p, c, t, pos, cfg, **kw),
        prefill=lambda p, b, **kw: transformer.forward(
            p, b["tokens"], cfg, embeds=b.get("embeds"),
            caches=transformer.init_cache(
                cfg, b["tokens"].shape[0], b["tokens"].shape[1]), **kw)[1])


def input_specs(cfg: ArchConfig, cell: ShapeCell,
                dtype=jnp.int32) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell
    (dry-run: weak-type-correct, shardable, no device allocation)."""
    b, s = cell.global_batch, cell.seq_len
    act = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if cell.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.is_encoder_decoder:
        d = min(cfg.decoder_len, s)
        specs = {
            "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), act),
            "tokens": jax.ShapeDtypeStruct((b, d), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, d), jnp.int32),
        }
        if cell.kind == "prefill":
            specs.pop("labels")
        return specs
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    if cfg.frontend == "vision_stub" and cfg.n_patch_tokens:
        specs["embeds"] = jax.ShapeDtypeStruct(
            (b, min(cfg.n_patch_tokens, s), cfg.d_model), act)
    if cell.kind == "prefill":
        specs.pop("labels")
    return specs
