"""Architecture and shape-cell schema of the frozen reference model.

A copy of the program's `ArchConfig` and `SHAPE_CELLS`.  The reference
builds its `ArchConfig` from the numbers in a benchmark configuration file
(`bench/configs/<name>.json`, key ``arch``), never from the program's
registry.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple
@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # attention structure -------------------------------------------------
    attn_pattern: Tuple[str, ...] = ("global",)   # cycled over attn layers
    local_window: int = 1024
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # block structure (cycled over layers) ---------------------------------
    block_pattern: Tuple[str, ...] = ("attn",)    # attn | rglru | mlstm | slstm
    lru_width: Optional[int] = None               # rglru recurrence width
    conv1d_width: int = 4
    # MoE -------------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # scatter_ep: global scatter into an expert-sharded buffer (baseline);
    # grouped_tp: per-DP-group local dispatch + tensor-parallel expert
    # weights — the §Perf hillclimb winner (no cross-shard scatter)
    moe_impl: str = "scatter_ep"
    moe_groups: int = 0             # grouped_tp: groups (0 -> DP degree)
    # encoder-decoder ---------------------------------------------------------
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    decoder_len: int = 448          # trained decoder length (whisper: 448)
    # modality stubs ----------------------------------------------------------
    frontend: str = "none"          # none | audio_stub | vision_stub
    n_patch_tokens: int = 0         # vlm: stubbed ViT patch embeddings
    # misc --------------------------------------------------------------------
    ffn_kind: str = "swiglu"        # swiglu | gelu
    norm_kind: str = "rmsnorm"      # rmsnorm | layernorm
    tie_embeddings: bool = True
    logits_softcap: float = 0.0
    supports_long_context: bool = False
    dtype: str = "bfloat16"
    source: str = ""                # provenance tag from the assignment

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a 256 multiple: MXU-aligned and divisible by the
        model mesh axis (whisper's 51866 is not). Padded logit slots are
        masked to -inf in the head."""
        return -(-self.vocab_size // 256) * 256

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % len(self.block_pattern)]

    def attn_kind(self, layer: int) -> str:
        return self.attn_pattern[layer % len(self.attn_pattern)]

    # ---- parameter accounting (used for 6ND MODEL_FLOPS, roofline) -------
    def param_count(self) -> int:
        return _params(self, active_only=False)

    def active_param_count(self) -> int:
        return _params(self, active_only=True)


def _attn_params(cfg: ArchConfig) -> int:
    hd = cfg.resolved_head_dim
    q = cfg.d_model * cfg.n_heads * hd
    kv = 2 * cfg.d_model * cfg.n_kv_heads * hd
    o = cfg.n_heads * hd * cfg.d_model
    return q + kv + o


def _ffn_params(cfg: ArchConfig, d_ff: int) -> int:
    mult = 3 if cfg.ffn_kind == "swiglu" else 2
    return mult * cfg.d_model * d_ff


def _block_params(cfg: ArchConfig, kind: str, active_only: bool) -> int:
    d = cfg.d_model
    if kind == "attn":
        p = _attn_params(cfg)
        if cfg.is_moe:
            e_act = cfg.experts_per_token if active_only else cfg.n_experts
            p += e_act * _ffn_params(cfg, cfg.moe_d_ff)
            p += cfg.n_shared_experts * _ffn_params(cfg, cfg.moe_d_ff)
            p += d * cfg.n_experts                     # router
        else:
            p += _ffn_params(cfg, cfg.d_ff)
        return p
    if kind == "rglru":
        w = cfg.lru_width or d
        # in/out projections + gates + temporal conv (recurrentgemma block)
        p = 2 * d * w + 2 * w * w // 1 + cfg.conv1d_width * w + 2 * w
        p += _ffn_params(cfg, cfg.d_ff)
        return p
    if kind in ("mlstm", "slstm"):
        hd = cfg.resolved_head_dim
        nh = cfg.n_heads
        qkv = 3 * d * nh * hd
        gates = 3 * d * nh if kind == "mlstm" else 4 * d * nh * hd
        out = nh * hd * d
        up = 2 * d * (2 * d)                           # proj up/down block
        return qkv + gates + out + up
    raise ValueError(kind)


def _params(cfg: ArchConfig, active_only: bool) -> int:
    total = cfg.vocab_size * cfg.d_model              # embed
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model
    layers = list(range(cfg.n_layers))
    for i in layers:
        total += _block_params(cfg, cfg.block_kind(i), active_only)
    if cfg.is_encoder_decoder:
        for i in range(cfg.n_encoder_layers):
            total += _attn_params(cfg) + _ffn_params(cfg, cfg.d_ff)
        total += cfg.n_layers * _attn_params(cfg)     # cross-attention
    return int(total)


# ---------------------------------------------------------------------------
# Shape cells (assigned input shapes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode

    @property
    def tokens(self) -> int:
        if self.kind == "decode":
            return self.global_batch          # one new token per sequence
        return self.seq_len * self.global_batch


SHAPE_CELLS: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}

