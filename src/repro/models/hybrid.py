"""Zamba2-style hybrid: Mamba2 backbone + a SHARED transformer block applied
every ``attn_every`` layers (weight sharing is the zamba2 signature).

Structure (81 layers, attn_every=6): 13 super-blocks of [6 x mamba2 +
shared-attn application] + 3 tail mamba2 layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm
from repro.models.attention import decode_attention, segment_attention
from repro.models.params import EMBED, VOCAB, ParamDef, stacked
from repro.sharding.logical import shard


def _split_counts(cfg: ModelConfig) -> tuple[int, int]:
    n_blocks = cfg.num_layers // cfg.attn_every
    tail = cfg.num_layers - n_blocks * cfg.attn_every
    return n_blocks, tail


def _shared_attn_def(cfg) -> dict:
    return {
        "attn_norm": L.rmsnorm_def(cfg.d_model),
        "attn": L.attention_proj_def(cfg),
        "mlp_norm": L.rmsnorm_def(cfg.d_model),
        "mlp": L.swiglu_def(cfg.d_model, cfg.d_ff),
    }


def hybrid_defs(cfg: ModelConfig) -> dict:
    n_blocks, tail = _split_counts(cfg)
    mamba = {"norm": L.rmsnorm_def(cfg.d_model), "mixer": ssm.mamba2_def(cfg)}
    defs = {
        "embed": L.embedding_def(cfg.vocab_size, cfg.d_model),
        "blocks": stacked(stacked(mamba, cfg.attn_every), n_blocks),
        "shared_attn": _shared_attn_def(cfg),   # ONE copy, reused
        "final_norm": L.rmsnorm_def(cfg.d_model),
        "unembed": ParamDef((cfg.d_model, cfg.vocab_size), (EMBED, VOCAB),
                            init="scaled"),
    }
    if tail:
        defs["tail"] = stacked(mamba, tail)
    return defs


def _mamba_layer(lp, cfg, h, seg):
    x = L.rmsnorm(lp["norm"], h, cfg.norm_eps)
    return h + ssm.mamba2_train(lp["mixer"], cfg, x, seg)


def _shared_attn_apply(sp, cfg, h, seg, pos):
    x = L.rmsnorm(sp["attn_norm"], h, cfg.norm_eps)
    q, k, v = L.qkv_project(sp["attn"], cfg, x, pos)
    attn = segment_attention(q, k, v, seg, seg, causal=True,
                             chunk=cfg.attn_chunk)
    h = h + L.attn_out_project(sp["attn"], attn)
    x = L.rmsnorm(sp["mlp_norm"], h, cfg.norm_eps)
    return h + L.swiglu(sp["mlp"], x)


def forward(params, cfg: ModelConfig, batch):
    seg, pos = batch["segment_ids"], batch["positions"]
    h = L.embed(params["embed"], batch["tokens"])
    h = shard(h, "batch", "seq", "act_embed")
    sp = params["shared_attn"]

    def inner(h, lp):
        return _mamba_layer(lp, cfg, h, seg), None

    def block_fn(h, bp):
        h, _ = jax.lax.scan(inner, h, bp)
        h = _shared_attn_apply(sp, cfg, h, seg, pos)
        h = shard(h, "batch", "seq", "act_embed")
        return h, None

    body = jax.checkpoint(block_fn) if cfg.remat != "none" else block_fn
    h, _ = jax.lax.scan(body, h, params["blocks"])
    if "tail" in params:
        h, _ = jax.lax.scan(inner, h, params["tail"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    return shard(logits, "batch", "seq", "act_vocab"), jnp.float32(0.0)


def prefill(params, cfg: ModelConfig, batch):
    """Prompt pass: returns (last-token logits, cache) for decode."""
    seg, pos = batch["segment_ids"], batch["positions"]
    h = L.embed(params["embed"], batch["tokens"])
    h = shard(h, "batch", "seq", "act_embed")
    sp = params["shared_attn"]
    n_blocks, tail = _split_counts(cfg)

    def inner(h, lp):
        x = L.rmsnorm(lp["norm"], h, cfg.norm_eps)
        y, st = ssm.mamba2_train(lp["mixer"], cfg, x, seg, return_state=True)
        return h + y, st

    def block_fn(h, bp):
        h, states = jax.lax.scan(inner, h, bp)
        x = L.rmsnorm(sp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(sp["attn"], cfg, x, pos)
        attn = segment_attention(q, k, v, seg, seg, causal=True,
                                 chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(sp["attn"], attn)
        x = L.rmsnorm(sp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.swiglu(sp["mlp"], x)
        return h, (states, {"k": k, "v": v})

    h, (block_states, kv) = jax.lax.scan(block_fn, h, params["blocks"])
    cache = {
        "blocks": jax.tree.map(
            lambda a: a.reshape((n_blocks * cfg.attn_every,) + a.shape[2:]),
            block_states),
        "k": kv["k"].astype(jnp.bfloat16),
        "v": kv["v"].astype(jnp.bfloat16),
    }
    if "tail" in params:
        h, tail_states = jax.lax.scan(inner, h, params["tail"])
        cache["tail"] = tail_states
    else:
        cache["tail"] = jax.tree.map(
            lambda a: a[:0], cache["blocks"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = h[:, -1:, :] @ params["unembed"]
    return logits, cache


# ---------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    n_blocks, tail = _split_counts(cfg)
    hd = cfg.resolved_head_dim()
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    kv_shape = (n_blocks, batch, max_len, cfg.num_kv_heads, hd)
    mk = lambda n: {
        "ssm": jnp.zeros((n, batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                         jnp.float32),
        "conv": jnp.zeros((n, batch, ssm.CONV_K - 1, d_in), jnp.float32),
    }
    return {
        "blocks": mk(n_blocks * cfg.attn_every),
        "tail": mk(tail),
        "k": jnp.zeros(kv_shape, dtype),
        "v": jnp.zeros(kv_shape, dtype),
    }


def cache_logical_axes(cfg: ModelConfig):
    st = {"ssm": ("layers", "batch", "act_ssm", None, None),
          "conv": ("layers", "batch", None, "act_ssm")}
    return {"blocks": st, "tail": dict(st),
            "k": ("layers", "batch", "kv_seq", "act_kv_heads", None),
            "v": ("layers", "batch", "kv_seq", "act_kv_heads", None)}


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    b = tokens.shape[0]
    n_blocks, tail = _split_counts(cfg)
    h = L.embed(params["embed"], tokens)
    positions = jnp.full((b, 1), pos, jnp.int32)
    cache_len = jnp.full((b,), pos + 1, jnp.int32)
    sp = params["shared_attn"]

    def mamba_step(h, xs):
        lp, st = xs
        x = L.rmsnorm(lp["norm"], h, cfg.norm_eps)
        y, st_new = ssm.mamba2_decode(lp["mixer"], cfg, x, st)
        return h + y, st_new

    # reshape the flat per-layer mamba states into (n_blocks, attn_every)
    bs = jax.tree.map(
        lambda a: a.reshape((n_blocks, cfg.attn_every) + a.shape[1:]),
        cache["blocks"])

    def block_fn(h, xs):
        bp, st, ck, cv = xs
        h, st_new = jax.lax.scan(mamba_step, h, (bp, st))
        x = L.rmsnorm(sp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(sp["attn"], cfg, x, positions)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 pos, axis=1)
        attn = decode_attention(q, ck, cv, cache_len)
        h = h + L.attn_out_project(sp["attn"], attn)
        x = L.rmsnorm(sp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.swiglu(sp["mlp"], x)
        return h, (st_new, ck, cv)

    h, (bs_new, ck_new, cv_new) = jax.lax.scan(
        block_fn, h, (params["blocks"], bs, cache["k"], cache["v"]))
    new_cache = {
        "blocks": jax.tree.map(
            lambda a: a.reshape((n_blocks * cfg.attn_every,) + a.shape[2:]),
            bs_new),
        "k": ck_new, "v": cv_new,
        "tail": cache["tail"],
    }
    if "tail" in params:
        h, tail_new = jax.lax.scan(mamba_step, h,
                                   (params["tail"], cache["tail"]))
        new_cache["tail"] = tail_new
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    return logits, new_cache
