"""Decoder-only transformer LM (dense / MoE / VLM-backbone families).

Layers are scan-stacked (small HLO, fast multi-device compiles) with a
selectable remat policy.  Three entry points per model: ``forward`` (train),
``prefill`` (build KV cache), ``decode_step`` (one token vs full cache).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.attention import (
    decode_attention, expand_kv, segment_attention,
)
from repro.models.params import (
    EMBED, VOCAB, ParamDef, stacked,
)
from repro.sharding.logical import shard


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots_saveable":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)  # "layer": save nothing


# ------------------------------------------------------------------- defs
def layer_def(cfg: ModelConfig) -> dict:
    d = {
        "attn_norm": L.rmsnorm_def(cfg.d_model),
        "attn": L.attention_proj_def(cfg),
        "mlp_norm": L.rmsnorm_def(cfg.d_model),
    }
    if cfg.family == "moe" or cfg.num_experts > 0:
        d["moe"] = moe_lib.moe_def(cfg)
    else:
        d["mlp"] = L.swiglu_def(cfg.d_model, cfg.d_ff)
    return d


def lm_defs(cfg: ModelConfig) -> dict:
    defs = {
        "embed": L.embedding_def(cfg.vocab_size, cfg.d_model),
        "layers": stacked(layer_def(cfg), cfg.num_layers),
        "final_norm": L.rmsnorm_def(cfg.d_model),
    }
    if not cfg.tied_embeddings:
        defs["unembed"] = ParamDef(
            (cfg.d_model, cfg.vocab_size), (EMBED, VOCAB), init="scaled")
    return defs


# ----------------------------------------------------------------- blocks
# The named scopes tag the blocks' HLO ops (op_name metadata), so a device
# trace can group its time by attention, FFN, loss and optimizer.
@jax.named_scope("attention")
def _attn_block(lp, cfg, h, segment_ids, positions):
    x = L.rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
    q, k, v = L.qkv_project(lp["attn"], cfg, x, positions)
    k = expand_kv(k, cfg.num_heads)
    v = expand_kv(v, cfg.num_heads)
    attn = segment_attention(q, k, v, segment_ids, segment_ids,
                             causal=True, chunk=cfg.attn_chunk)
    attn = shard(attn, "batch", "seq", "act_heads", None)
    return L.attn_out_project(lp["attn"], attn)


@jax.named_scope("ffn")
def _ffn_block(lp, cfg, h, global_tokens):
    x = L.rmsnorm(lp["mlp_norm"], h, cfg.norm_eps)
    if "moe" in lp:
        out, aux = moe_lib.moe_block(lp["moe"], cfg, x,
                                     global_tokens=global_tokens)
        return out, aux
    return L.swiglu(lp["mlp"], x), jnp.float32(0.0)


def _embed_inputs(params, cfg, batch):
    h = L.embed(params["embed"], batch["tokens"])
    if cfg.family == "vlm" and "image_embeds" in batch:
        b = h.shape[0]
        bi = jnp.arange(b)[:, None]
        h = h.at[bi, batch["image_positions"]].set(
            batch["image_embeds"].astype(h.dtype))
    return shard(h, "batch", "seq", "act_embed")


def _unembed(params, cfg, h):
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if cfg.tied_embeddings:
        return L.unembed(params["embed"], h)
    logits = h @ params["unembed"]
    return shard(logits, "batch", "seq", "act_vocab")


# ------------------------------------------------------------------ train
def forward(params, cfg: ModelConfig, batch) -> tuple[jax.Array, jax.Array]:
    """batch: tokens/segment_ids/positions (b, s) [+ vlm extras].
    Returns (logits (b, s, vocab), aux_loss scalar)."""
    h = _embed_inputs(params, cfg, batch)
    seg = batch["segment_ids"]
    pos = batch["positions"]
    b, s = seg.shape
    global_tokens = b * s

    def layer_fn(carry, lp):
        h, aux = carry
        h = h + _attn_block(lp, cfg, h, seg, pos)
        ffn, a = _ffn_block(lp, cfg, h, global_tokens)
        h = h + ffn
        h = shard(h, "batch", "seq", "act_embed")
        return (h, aux + a), None

    (h, aux), _ = jax.lax.scan(
        _remat(layer_fn, cfg), (h, jnp.float32(0.0)), params["layers"])
    return _unembed(params, cfg, h), aux


# ---------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim()
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: ModelConfig):
    return {"k": ("layers", "batch", "kv_seq", "act_kv_heads", None),
            "v": ("layers", "batch", "kv_seq", "act_kv_heads", None)}


def prefill(params, cfg: ModelConfig, batch):
    """Run the full prompt, return (last-token logits, populated cache)."""
    h = _embed_inputs(params, cfg, batch)
    seg = batch["segment_ids"]
    pos = batch["positions"]
    b, s = seg.shape

    def layer_fn(h, lp):
        x = L.rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, pos)
        ke = expand_kv(k, cfg.num_heads)
        ve = expand_kv(v, cfg.num_heads)
        attn = segment_attention(q, ke, ve, seg, seg, causal=True,
                                 chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(lp["attn"], attn)
        ffn, _ = _ffn_block(lp, cfg, h, b * s)
        h = h + ffn
        h = shard(h, "batch", "seq", "act_embed")
        return h, {"k": k, "v": v}

    h, kv = jax.lax.scan(_remat(layer_fn, cfg), h, params["layers"])
    logits = _unembed(params, cfg, h[:, -1:, :])
    kv = {n: shard(a, "layers", "batch", "kv_seq", "act_kv_heads", None)
          for n, a in kv.items()}
    return logits, kv


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """One decode step.  tokens: (b, 1); pos: scalar int32 — the index the
    new token is written at (cache positions <= pos are attended).
    Returns (logits (b, 1, vocab), updated cache)."""
    b = tokens.shape[0]
    h = L.embed(params["embed"], tokens)
    h = shard(h, "batch", "seq", "act_embed")
    positions = jnp.full((b, 1), pos, jnp.int32)
    cache_len = jnp.full((b,), pos + 1, jnp.int32)

    def layer_fn(h, xs):
        lp, ck, cv = xs
        x = L.rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, positions)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 pos, axis=1)
        attn = decode_attention(q, ck, cv, cache_len)
        h = h + L.attn_out_project(lp["attn"], attn)
        ffn, _ = _ffn_block(lp, cfg, h, b)
        h = h + ffn
        return h, {"k": ck, "v": cv}

    h, new_cache = jax.lax.scan(
        layer_fn, h, (params["layers"], cache["k"], cache["v"]))
    return _unembed(params, cfg, h), new_cache
