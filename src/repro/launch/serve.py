"""Serving launcher: batched prefill + decode with the KV-cache step.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.model_zoo import build_model
from repro.train.train_step import make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    if args.reduced:
        import importlib
        cfg = importlib.import_module(
            "repro.configs." + args.arch.replace("-", "_")).reduced()
    else:
        cfg = get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0), jnp.float32)

    b, s = args.batch, args.prompt_len
    max_len = s + args.gen
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(1, cfg.vocab_size, (b, s)).astype(np.int32),
        "segment_ids": np.ones((b, s), np.int32),
        "positions": np.broadcast_to(np.arange(s, dtype=np.int32),
                                     (b, s)).copy(),
    }
    if cfg.family == "vlm":
        n = int(s * cfg.image_token_frac)
        batch["image_embeds"] = rng.normal(
            size=(b, n, cfg.d_model)).astype(np.float32) * 0.02
        batch["image_positions"] = np.broadcast_to(
            np.arange(n, dtype=np.int32), (b, n)).copy()
    if cfg.family == "audio":
        batch["enc_embeds"] = rng.normal(
            size=(b, cfg.encoder_frames, cfg.d_model)).astype(
            np.float32) * 0.02

    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))

    t0 = time.time()
    logits, _pref_cache = prefill(params, batch)
    logits.block_until_ready()
    print(f"prefill {b}x{s}: {time.time() - t0:.3f}s "
          f"logits={logits.shape}")

    # decode loop against a full-size cache: write the prompt by replaying
    # it through decode_step (exercises the serving path end to end)
    cache = model.init_cache(b, max_len, jnp.float32)
    toks = batch["tokens"]
    for t in range(s):
        logits, cache = decode(params, cache, toks[:, t:t + 1],
                               jnp.int32(t))
    out = []
    t0 = time.time()
    cur = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    for t in range(s, max_len):
        out.append(np.asarray(cur)[:, 0])
        logits, cache = decode(params, cache, cur, jnp.int32(t))
        cur = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    dt = time.time() - t0
    gen = np.stack(out, 1)
    print(f"decoded {args.gen} tokens x {b} seqs in {dt:.3f}s "
          f"({args.gen * b / dt:.1f} tok/s)")
    print("greedy continuations:", gen[:, :8].tolist())


if __name__ == "__main__":
    main()
