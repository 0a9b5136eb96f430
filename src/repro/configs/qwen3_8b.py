"""Qwen3-8B  [hf:Qwen/Qwen3-8B].

36L d_model=4096 32H (GQA kv=8) d_ff=12288 vocab=151936, qk_norm.
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="qwen3-8b",
    family="dense",
    num_layers=36,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=12_288,
    vocab_size=151_936,
    qk_norm=True,
    rope_theta=1_000_000.0,
))


def chip_share() -> ModelConfig:
    """One TPU v5e chip's share of a qwen3-8b training job, at the
    published widths (d_model, heads, head_dim, d_ff, qk-norm unchanged).

    Changed keys, and the deployment each cut stands for:
      * vocab_size 151936 -> 18992: the vocabulary split over 8 chips
        (embedding and unembedding sharded 8 ways); this chip holds 1/8.
      * num_layers 36 -> 2: the other 34 layers run as further pipeline
        stages on other chips (18 stages of 2 layers).

    Sized by compiling the donated train step for one v5e: fp32 master
    weights plus Adam state at a 2 x 4096-token batch fit 16 GiB with
    headroom; the full vocabulary alone would need 19.9 GB of state.
    """
    return CONFIG.replace(name="qwen3-8b-chip-share", num_layers=2,
                          vocab_size=151_936 // 8)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="qwen3-8b-reduced", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256, attn_chunk=32)
