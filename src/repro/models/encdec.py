"""Whisper-style encoder-decoder (audio family).

The conv/mel frontend is a STUB per the assignment: the batch carries
precomputed frame embeddings ``enc_embeds`` (b, frames, d_model).  The
encoder is bidirectional; the decoder is causal with cross-attention.
Whisper uses LayerNorm; we keep that.  Learned absolute positions are
replaced by RoPE (TPU-friendly; noted in DESIGN.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.attention import (
    decode_attention, segment_attention,
)
from repro.models.params import EMBED, VOCAB, ParamDef, stacked
from repro.sharding.logical import shard


def _enc_layer_def(cfg) -> dict:
    return {
        "attn_norm": L.layernorm_def(cfg.d_model),
        "attn": L.attention_proj_def(cfg),
        "mlp_norm": L.layernorm_def(cfg.d_model),
        "mlp": L.gelu_mlp_def(cfg.d_model, cfg.d_ff),
    }


def _dec_layer_def(cfg) -> dict:
    d = _enc_layer_def(cfg)
    d["cross_norm"] = L.layernorm_def(cfg.d_model)
    d["cross"] = L.attention_proj_def(cfg.replace(qk_norm=False))
    return d


def encdec_defs(cfg: ModelConfig) -> dict:
    return {
        "embed": L.embedding_def(cfg.vocab_size, cfg.d_model),
        "enc_layers": stacked(_enc_layer_def(cfg), cfg.encoder_layers),
        "enc_norm": L.layernorm_def(cfg.d_model),
        "dec_layers": stacked(_dec_layer_def(cfg), cfg.num_layers),
        "final_norm": L.layernorm_def(cfg.d_model),
        "unembed": ParamDef((cfg.d_model, cfg.vocab_size), (EMBED, VOCAB),
                            init="scaled"),
    }


def encode(params, cfg: ModelConfig, enc_embeds: jax.Array) -> jax.Array:
    """enc_embeds: (b, F, d) stub frame embeddings -> encoder states."""
    b, F, _ = enc_embeds.shape
    h = shard(enc_embeds, "batch", "seq", "act_embed")
    pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (b, F))
    ones = jnp.ones((b, F), jnp.int32)

    def layer_fn(h, lp):
        x = L.layernorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, pos)
        attn = segment_attention(q, k, v, ones, ones, causal=False,
                                 chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(lp["attn"], attn)
        x = L.layernorm(lp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.gelu_mlp(lp["mlp"], x)
        h = shard(h, "batch", "seq", "act_embed")
        return h, None

    body = jax.checkpoint(layer_fn) if cfg.remat != "none" else layer_fn
    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return L.layernorm(params["enc_norm"], h, cfg.norm_eps)


def _cross_block(lp, cfg, h, enc_out, enc_valid):
    x = L.layernorm(lp["cross_norm"], h, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, lp["cross"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wv"])
    b, s = x.shape[:2]
    q_seg = jnp.ones((b, s), jnp.int32)
    attn = segment_attention(q, k, v, q_seg, enc_valid, causal=False,
                             chunk=cfg.attn_chunk)
    return h + L.attn_out_project(lp["cross"], attn)


def forward(params, cfg: ModelConfig, batch):
    """Train forward: loss over decoder tokens given stub frame embeds."""
    enc_out = encode(params, cfg, batch["enc_embeds"])
    enc_valid = jnp.ones(enc_out.shape[:2], jnp.int32)
    seg, pos = batch["segment_ids"], batch["positions"]
    h = L.embed(params["embed"], batch["tokens"])
    h = shard(h, "batch", "seq", "act_embed")

    def layer_fn(h, lp):
        x = L.layernorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, pos)
        attn = segment_attention(q, k, v, seg, seg, causal=True,
                                 chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(lp["attn"], attn)
        h = _cross_block(lp, cfg, h, enc_out, enc_valid)
        x = L.layernorm(lp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.gelu_mlp(lp["mlp"], x)
        h = shard(h, "batch", "seq", "act_embed")
        return h, None

    body = jax.checkpoint(layer_fn) if cfg.remat != "none" else layer_fn
    h, _ = jax.lax.scan(body, h, params["dec_layers"])
    h = L.layernorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    return shard(logits, "batch", "seq", "act_vocab"), jnp.float32(0.0)


# ---------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim()
    self_shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    cross_shape = (cfg.num_layers, batch, cfg.encoder_frames,
                   cfg.num_kv_heads, hd)
    return {
        "k": jnp.zeros(self_shape, dtype),
        "v": jnp.zeros(self_shape, dtype),
        "cross_k": jnp.zeros(cross_shape, dtype),
        "cross_v": jnp.zeros(cross_shape, dtype),
    }


def cache_logical_axes(cfg: ModelConfig):
    kv = ("layers", "batch", "kv_seq", "act_kv_heads", None)
    cross = ("layers", "batch", None, "act_kv_heads", None)
    return {"k": kv, "v": kv, "cross_k": cross, "cross_v": cross}


def build_cross_cache(params, cfg, enc_out):
    """Precompute per-layer cross K/V from encoder states."""
    def one(lp):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wv"])
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)

    ks, vs = jax.vmap(one)(params["dec_layers"])
    return ks, vs


def prefill(params, cfg: ModelConfig, batch):
    """Prompt pass for the decoder given stub frame embeddings."""
    enc_out = encode(params, cfg, batch["enc_embeds"])
    enc_valid = jnp.ones(enc_out.shape[:2], jnp.int32)
    cross_k, cross_v = build_cross_cache(params, cfg, enc_out)
    seg, pos = batch["segment_ids"], batch["positions"]
    h = L.embed(params["embed"], batch["tokens"])
    h = shard(h, "batch", "seq", "act_embed")

    def layer_fn(h, xs):
        lp, xk, xv = xs
        x = L.layernorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, pos)
        attn = segment_attention(q, k, v, seg, seg, causal=True,
                                 chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(lp["attn"], attn)
        x = L.layernorm(lp["cross_norm"], h, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["cross"]["wq"])
        q_seg = jnp.ones(x.shape[:2], jnp.int32)
        cattn = segment_attention(q, xk.astype(q.dtype), xv.astype(q.dtype),
                                  q_seg, enc_valid, causal=False,
                                  chunk=cfg.attn_chunk)
        h = h + L.attn_out_project(lp["cross"], cattn)
        x = L.layernorm(lp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.gelu_mlp(lp["mlp"], x)
        return h, {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    h, kv = jax.lax.scan(layer_fn, h,
                         (params["dec_layers"], cross_k, cross_v))
    h = L.layernorm(params["final_norm"], h, cfg.norm_eps)
    logits = h[:, -1:, :] @ params["unembed"]
    cache = {"k": kv["k"], "v": kv["v"],
             "cross_k": cross_k, "cross_v": cross_v}
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    b = tokens.shape[0]
    h = L.embed(params["embed"], tokens)
    positions = jnp.full((b, 1), pos, jnp.int32)
    cache_len = jnp.full((b,), pos + 1, jnp.int32)
    f_len = jnp.full((b,), cfg.encoder_frames, jnp.int32)

    def layer_fn(h, xs):
        lp, ck, cv, xk, xv = xs
        x = L.layernorm(lp["attn_norm"], h, cfg.norm_eps)
        q, k, v = L.qkv_project(lp["attn"], cfg, x, positions)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 pos, axis=1)
        attn = decode_attention(q, ck, cv, cache_len)
        h = h + L.attn_out_project(lp["attn"], attn)
        # cross attention vs static cross cache
        x = L.layernorm(lp["cross_norm"], h, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["cross"]["wq"])
        cattn = decode_attention(q, xk, xv, f_len)
        h = h + L.attn_out_project(lp["cross"], cattn)
        x = L.layernorm(lp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.gelu_mlp(lp["mlp"], x)
        return h, {"k": ck, "v": cv}

    h, kv = jax.lax.scan(layer_fn, h, (params["dec_layers"], cache["k"],
                                       cache["v"], cache["cross_k"],
                                       cache["cross_v"]))
    h = L.layernorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    new_cache = dict(cache)
    new_cache.update(kv)
    return logits, new_cache
