import os
import sys

# the tests run on the host CPU, never on an accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="rewrite checked-in golden files instead of comparing")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_lm_batch(cfg, b=2, s=64, seed=0, n_segments=2, trailing_pad=4):
    """Packed batch with segments + trailing padding for any family."""
    r = np.random.default_rng(seed)
    tokens = r.integers(1, cfg.vocab_size, (b, s)).astype(np.int32)
    seg = np.ones((b, s), np.int32)
    bounds = np.linspace(0, s - trailing_pad, n_segments + 1).astype(int)
    for i in range(n_segments):
        seg[:, bounds[i]:bounds[i + 1]] = i + 1
    if trailing_pad:
        seg[:, -trailing_pad:] = 0
    pos = np.zeros((b, s), np.int32)
    for i in range(n_segments):
        width = bounds[i + 1] - bounds[i]
        pos[:, bounds[i]:bounds[i + 1]] = np.arange(width)
    labels = np.where(seg > 0, tokens, -1).astype(np.int32)
    batch = dict(tokens=tokens, segment_ids=seg, positions=pos,
                 labels=labels)
    if cfg.family == "vlm" and cfg.image_token_frac > 0:
        n = int(s * cfg.image_token_frac)
        batch["image_embeds"] = r.normal(
            size=(b, n, cfg.d_model)).astype(np.float32) * 0.02
        batch["image_positions"] = np.broadcast_to(
            np.arange(n, dtype=np.int32) * 2, (b, n)).copy()
    if cfg.family == "audio":
        batch["enc_embeds"] = r.normal(
            size=(b, cfg.encoder_frames, cfg.d_model)).astype(
            np.float32) * 0.02
    return batch


def all_reduced_configs():
    import importlib
    mods = ["qwen3_moe_30b_a3b", "granite_moe_3b_a800m", "granite_20b",
            "qwen3_8b", "yi_9b", "qwen3_32b", "zamba2_7b", "pixtral_12b",
            "whisper_medium", "rwkv6_3b"]
    return [importlib.import_module(f"repro.configs.{m}").reduced()
            for m in mods]
