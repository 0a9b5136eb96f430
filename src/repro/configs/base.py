"""Config system: model configs and their registry.

Every assigned architecture has one file in this package defining a
``CONFIG: ModelConfig`` with the exact published numbers, plus a
``reduced()`` variant used by CPU smoke tests.  The FULL configs are never
allocated; the chip runs cut them to one chip's share (``chip_share()``,
``bench/configs``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | hybrid | vlm | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // num_heads
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    # --- hybrid / ssm (Mamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    attn_every: int = 0          # zamba2: shared attn block every N layers
    # --- rwkv6 ---
    rwkv_head_dim: int = 64
    rwkv_chunk: int = 64
    rwkv_lora_dim: int = 64
    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_frames: int = 1500   # whisper: 30s audio -> 1500 frames (stub)
    # --- vlm (pixtral) ---
    image_token_frac: float = 0.0  # fraction of sequence that is image embeds
    # --- numerics / performance knobs ---
    dtype: str = "bfloat16"
    remat: str = "layer"         # none | layer | dots_saveable
    attn_chunk: int = 1024       # kv-chunk for online-softmax attention
    scan_layers: bool = True
    norm_eps: float = 1e-6
    tied_embeddings: bool = False

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim()

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim()

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    key = name.replace("_", "-")
    if key not in _REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def list_configs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_ASSIGNED = [
    "qwen3_moe_30b_a3b", "granite_moe_3b_a800m", "granite_20b", "qwen3_8b",
    "yi_9b", "qwen3_32b", "zamba2_7b", "pixtral_12b", "whisper_medium",
    "rwkv6_3b",
]


_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    import importlib
    for mod in _ASSIGNED + ["paper_vlm"]:
        importlib.import_module(f"repro.configs.{mod}")
    _LOADED = True
