"""RWKV6-3B "Finch"  [arXiv:2404.05892] — attention-free, data-dependent decay.

32L d_model=2560 d_ff=8960 vocab=65536.  WKV heads of size 64 -> 40 heads.
LayerNorm epsilon 1e-5 (hf:RWKV/v6-Finch-3B-HF ``layer_norm_epsilon``).
"""
from repro.configs.base import ModelConfig, register

MIX_LORA = 32   # width of the five token-shift (ddlerp) LoRAs, as published

CONFIG = register(ModelConfig(
    name="rwkv6-3b",
    family="ssm",
    num_layers=32,
    d_model=2560,
    num_heads=40,            # d_model / rwkv_head_dim
    num_kv_heads=40,
    d_ff=8960,
    vocab_size=65_536,
    rwkv_head_dim=64,
    rwkv_chunk=64,   # perf §R1: probed 32/64/128 — 64 is the bytes sweet spot
    rwkv_lora_dim=64,
    norm_eps=1e-5,
))


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="rwkv6-3b-reduced", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=256, rwkv_head_dim=16,
        rwkv_chunk=16, rwkv_lora_dim=8)
