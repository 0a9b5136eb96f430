#!/usr/bin/env python3
"""Smoke test of the Overlord-fed trainer on a TPU, at qwen3-8b widths.

    python chip_smoke.py            # one chip: train phase + kernel phase
    python chip_smoke.py --chips 4  # four chips: the data-parallel check only

One chip.  The train phase runs ``repro.launch.train.run`` -- the body of
``python -m repro.launch.train`` -- on qwen3-8b at one chip's share
(``configs/qwen3_8b.py: chip_share()``), fed by an Overlord over coyo-like
sources materialised from a fixed seed, seq_len 4096, ``backbone_balance``
over 2 DP ranks.  One warm-up step, then timed steps; each must give a
finite loss and grad norm, and the device's token count must equal the
host's count of ``labels >= 0 & segment_ids > 0`` in the delivered batch.
The kernel phase compiles each Pallas kernel for the chip
(``interpret=False``) at real widths and compares it, and the packed
attention's gradients, with kernels/ref.py at ``highest`` matmul
precision.

Four chips.  The same global batches train 3 steps over a 4-device
``("data",)`` mesh and then on the first device alone; the losses and a
parameter checksum must agree.

Every phase runs in this one process.  The last line of stdout is a JSON
object naming the device; it is printed only if every check passed.  Off
a TPU, or on any failed check, the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# AdamW's default peak lr, the usual one at 8B scale: the command line's
# 3e-3 suits reduced() widths, and at d_model 4096 the loss climbed from
# 10.36 to 14.76 in 6 steps on a v5e
LR = ["--lr", "3e-4"]
TRAIN_ARGV = ["--arch", "qwen3-8b", "--chip-share", "--seq-len", "4096",
              "--strategy", "backbone_balance", "--dp", "2", "--rows", "1",
              "--n-bins", "1", "--sources", "5", *LR]
TIMED_STEPS = 5
# 4 DP ranks x 1 row x 2048 tokens: the same 8192 tokens per step as the
# train phase, so the one-device reference also fits one chip
DP_ARGV = ["--arch", "qwen3-8b", "--chip-share", "--seq-len", "2048",
           "--strategy", "backbone_balance", "--dp", "4", "--rows", "1",
           "--n-bins", "1", "--sources", "5", *LR]
DP_STEPS = 3
# Both runs see the same rows through the same bf16 forward, so losses
# differ only by the order of f32 partial sums and bf16 gradient sums.
DP_LOSS_RTOL = 1e-3
# Adam moves every weight by about lr, so a flipped sign on a near-zero
# gradient moves one weight by 2 lr; such flips cancel in the sum, while a
# wrong update shifts it by a share of the update's L1 mass.
DP_CHECKSUM_RTOL = 1e-3


def device_info(need: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {info}")
    if info["count"] < need:
        sys.exit(f"chip_smoke: needs {need} chips, JAX found {info}")
    return info


# ---------------------------------------------------------------- train
def config_cuts(cfg) -> dict:
    from repro.configs import get_config
    full = dataclasses.asdict(get_config("qwen3-8b"))
    return {k: (full[k], v) for k, v in dataclasses.asdict(cfg).items()
            if full[k] != v}


def train_phase() -> list[str]:
    import jax
    from repro.launch import train

    args = train.build_parser().parse_args(
        TRAIN_ARGV + ["--steps", str(1 + TIMED_STEPS)])
    out = train.run(args)
    cfg, hist = out["config"], out["history"]
    print("config:", json.dumps({
        k: getattr(cfg, k) for k in ("name", "num_layers", "d_model",
                                     "num_heads", "num_kv_heads", "head_dim",
                                     "d_ff", "vocab_size", "qk_norm")}))
    print("cuts (published -> chip share):", config_cuts(cfg))
    print("train:", " ".join(TRAIN_ARGV))
    print(f"compile_s {out['compile_s']}")
    failures = []
    for rec in hist:
        kind = "warmup" if rec["step"] == 0 else "timed"
        print(f"step {rec['step']} {kind} fetch_s {rec['fetch_s']} "
              f"step_s {rec['step_s']} loss {rec['loss']} "
              f"grad_norm {rec['grad_norm']} tokens {rec['tokens']} "
              f"host_tokens {rec['host_tokens']}")
        if not (math.isfinite(rec["loss"]) and
                math.isfinite(rec["grad_norm"])):
            failures.append(f"step {rec['step']}: non-finite loss/grad_norm")
        if rec["tokens"] != rec["host_tokens"]:
            failures.append(f"step {rec['step']}: device counted "
                            f"{rec['tokens']} tokens, host "
                            f"{rec['host_tokens']}")
    if len(hist) < 1 + TIMED_STEPS:
        failures.append(f"only {len(hist)} steps ran")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print("memory_stats:", json.dumps(stats))
    return failures


# -------------------------------------------------------------- kernels
def _packed_segments(rng, b, s):
    """Rows packed with documents of log-normal length, then padding."""
    seg = np.zeros((b, s), np.int32)
    for i in range(b):
        pos, sid = 0, 1
        while True:
            ln = int(np.clip(rng.lognormal(5.5, 1.0), 8, s))
            if pos + ln > s - 64:
                break
            seg[i, pos:pos + ln] = sid
            pos, sid = pos + ln, sid + 1
    return seg


def _compiled(kernel, *args):
    """``kernel`` compiled for the chip (not interpreted); refuses a
    program that holds no Mosaic kernel."""
    import jax
    exe = jax.jit(functools.partial(kernel, interpret=False)).lower(
        *args).compile()
    if "tpu_custom_call" not in exe.as_text():
        raise RuntimeError(f"{kernel.__name__}: no Mosaic kernel compiled")
    return exe


def _compare(name, out, exp, atol, rtol, why) -> list[str]:
    out = np.asarray(out, np.float32)
    exp = np.asarray(exp, np.float32)
    err = np.abs(out - exp)
    excess = float(np.max(err - (atol + rtol * np.abs(exp))))
    ok = bool(np.isfinite(out).all()) and excess <= 0.0
    print(f"kernel {name}: shape {out.shape} max_abs_err {float(err.max())} "
          f"max_abs_ref {float(np.abs(exp).max())} atol {atol} rtol {rtol} "
          f"({why}) {'ok' if ok else 'FAIL'}")
    return [] if ok else [f"kernel {name} outside tolerance"]


def kernel_phase() -> list[str]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.packed_attention import packed_flash_attention
    from repro.kernels.wkv6 import wkv6_forward

    rng = np.random.default_rng(0)
    keys = iter(jax.random.split(jax.random.key(0), 16))
    normal = lambda shape, dt, scale=1.0: (
        jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dt)
    failures = []

    # packed attention: qwen3-8b, 32 q / 8 kv heads x 128, one 4096 row
    b, h, kh, s, d = 1, 32, 8, 4096, 128
    q = normal((b, h, s, d), jnp.bfloat16)
    k = normal((b, kh, s, d), jnp.bfloat16)
    v = normal((b, kh, s, d), jnp.bfloat16)
    seg = jnp.asarray(_packed_segments(rng, b, s))
    attn = _compiled(packed_flash_attention, q, k, v, seg, seg)
    out = attn(q, k, v, seg, seg)
    g = h // kh
    with jax.default_matmul_precision("highest"):
        ref_attn = jax.jit(ref.packed_attention_ref)
        exp = jnp.concatenate([   # one kv head's group at a time
            ref_attn(q[:, i * g:(i + 1) * g], k[:, i:i + 1], v[:, i:i + 1],
                     seg, seg) for i in range(kh)], axis=1)
    failures += _compare(
        "packed_attention", out, exp, 2e-2, 2e-2,
        "bf16 output: rounding is 2^-8 relative on O(1) values; the bound "
        "the interpret-mode tests hold bf16 to")
    del out, exp
    # its backward: gradients in q, k, v for a random cotangent
    w = normal((b, h, s, d), jnp.float32)

    def attn_grads(q, k, v, q_seg, kv_seg, w, interpret):
        loss = lambda q, k, v: jnp.sum(packed_flash_attention(
            q, k, v, q_seg, kv_seg, interpret=interpret
        ).astype(jnp.float32) * w)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = _compiled(attn_grads, q, k, v, seg, seg, w)(q, k, v, seg, seg, w)
    with jax.default_matmul_precision("highest"):
        ref_grads = jax.jit(jax.grad(
            lambda q, k, v, w: jnp.sum(ref.packed_attention_ref(
                q, k, v, seg, seg).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))
        parts = [ref_grads(q[:, i * g:(i + 1) * g], k[:, i:i + 1],
                           v[:, i:i + 1], w[:, i * g:(i + 1) * g])
                 for i in range(kh)]
    for i, name in enumerate(("dq", "dk", "dv")):
        failures += _compare(
            f"packed_attention_{name}", got[i],
            jnp.concatenate([p[i] for p in parts], axis=1), 2e-2, 2e-2,
            "bf16 gradients of f32 accumulations: rounding is 2^-8 "
            "relative; the bound the interpret-mode tests hold bf16 to")
    del q, k, v, w, got, parts

    # wkv6: rwkv6-3b, 40 heads x 64, one 4096 row with packed resets
    b, h, s, dk = 1, 40, 4096, 64
    r, kk, vv = (normal((b, h, s, dk), jnp.float32, 0.5) for _ in range(3))
    loga = -jnp.exp(normal((b, h, s, dk), jnp.float32, 0.5))
    u = normal((h, dk), jnp.float32, 0.5)
    starts = np.diff(_packed_segments(rng, b, s), prepend=-1, axis=1) != 0
    reset = jnp.asarray(starts)
    wkv = _compiled(wkv6_forward, r, kk, vv, loga, u, reset)
    out = wkv(r, kk, vv, loga, u, reset)
    tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    with jax.default_matmul_precision("highest"):
        exp = tr(jax.jit(ref.wkv6_ref)(tr(r), tr(kk), tr(vv), tr(loga), u,
                                       reset))
    failures += _compare(
        "wkv6", out, exp, 1e-3, 1e-3,
        "f32 with f32-precision dots; the chunked form reassociates the "
        "4096-step recurrence and takes exp of differences of 64-step "
        "cumsums, a few f32 ulps of O(10) exponents")
    del r, kk, vv, loga, out, exp

    # flash decode: qwen3-8b heads against a 32k cache, 8 sequences
    b, h, kh, S, d = 8, 32, 8, 32768, 128
    q = normal((b, h, d), jnp.bfloat16)
    kc = normal((b, kh, S, d), jnp.bfloat16)
    vc = normal((b, kh, S, d), jnp.bfloat16)
    clen = jnp.asarray(rng.integers(1, S + 1, b).astype(np.int32)
                       ).at[0].set(S)
    dec = _compiled(flash_decode, q, kc, vc, clen)
    out = dec(q, kc, vc, clen)
    with jax.default_matmul_precision("highest"):
        exp = jax.jit(ref.flash_decode_ref)(q, kc, vc, clen)
    failures += _compare(
        "flash_decode", out, exp, 2e-2, 2e-2,
        "bf16 output: rounding is 2^-8 relative on O(1) values; the bound "
        "the interpret-mode tests hold bf16 to")
    return failures


# ------------------------------------------------------- data parallel
def dp_agreement(argv: list[str], steps: int = DP_STEPS) -> dict:
    """Train ``steps`` steps on the same global batches over every local
    device and then on the first device alone; return both runs' losses
    and parameter checksums.  Runs one after the other, so each fits."""
    import jax
    from repro.launch import train
    from repro.models.model_zoo import build_model
    from repro.train.trainer import Trainer

    args = train.build_parser().parse_args(argv + ["--steps", str(steps)])
    cfg = train.model_config(args)
    model = build_model(cfg)
    tcfg = train.trainer_config(args)

    def params(t):
        return [np.asarray(x) for x in jax.tree.leaves(t.state.params)]

    def run(trainer, batches):
        losses = [trainer.step(b)["loss"] for b in batches]
        return losses, params(trainer)

    with train.overlord_for(args, cfg) as ov:
        dp = Trainer(model, ov, tcfg)
        batches = []
        for step in range(steps):
            batches.append(dp.fetch(step))
            ov.step_done(step)
    n_dev = dp.mesh.size
    p0 = params(dp)
    dp_losses, p_dp = run(dp, batches)
    del dp
    one = Trainer(model, ov, tcfg, devices=jax.devices()[:1])
    one_losses, p_one = run(one, batches)
    del one

    f64 = lambda a: a.astype(np.float64)
    l1_update = sum(float(np.abs(f64(a) - f64(z)).sum())
                    for a, z in zip(p_one, p0))
    cs_dp = sum(float(f64(a).sum()) for a in p_dp)
    cs_one = sum(float(f64(a).sum()) for a in p_one)
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(dp_losses, one_losses))
    cs_rel = abs(cs_dp - cs_one) / l1_update
    return {"devices": n_dev, "rows": int(batches[0]["tokens"].shape[0]),
            "seq_len": int(batches[0]["tokens"].shape[1]),
            "dp_losses": dp_losses, "one_losses": one_losses,
            "max_loss_rel_diff": loss_rel,
            "dp_checksum": cs_dp, "one_checksum": cs_one,
            "update_l1": l1_update, "checksum_diff_over_update_l1": cs_rel,
            "ok": loss_rel <= DP_LOSS_RTOL and cs_rel <= DP_CHECKSUM_RTOL}


# ------------------------------------------------------------------ main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel check on 4 chips")
    chips = ap.parse_args().chips
    info = device_info(chips)

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({warm} entries before this run)")
    if chips == 4:
        res = dp_agreement(DP_ARGV)
        print("dp:", json.dumps(res))
        failures = [] if res["ok"] else ["4-chip DP disagrees with 1 chip"]
    else:
        failures = train_phase() + kernel_phase()
    if failures:
        sys.exit("chip_smoke FAILED: " + "; ".join(failures))
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
