"""RWKV6 LM assembly: embed -> ln0 -> [timemix + channelmix] x L -> head."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import rwkv
from repro.models.params import EMBED, VOCAB, ParamDef, stacked
from repro.sharding.logical import shard


def rwkv_defs(cfg: ModelConfig) -> dict:
    layer = {
        "tm": rwkv.rwkv6_timemix_def(cfg),
        "cm": rwkv.rwkv6_channelmix_def(cfg),
    }
    return {
        "embed": L.embedding_def(cfg.vocab_size, cfg.d_model),
        "ln0": L.layernorm_def(cfg.d_model),
        "layers": stacked(layer, cfg.num_layers),
        "final_norm": L.layernorm_def(cfg.d_model),
        "unembed": ParamDef((cfg.d_model, cfg.vocab_size), (EMBED, VOCAB),
                            init="scaled"),
    }


def forward(params, cfg: ModelConfig, batch, return_state: bool = False):
    seg = batch["segment_ids"]
    h = L.embed(params["embed"], batch["tokens"])
    h = L.layernorm(params["ln0"], h, cfg.norm_eps)
    h = shard(h, "batch", "seq", "act_embed")

    # The named scopes tag the block's HLO ops (op_name metadata), so a
    # device trace can group its time by time mix (and its WKV scan) and
    # channel mix.
    def layer_fn(carry, lp):
        h = carry
        if return_state:
            with jax.named_scope("timemix"):
                tm_out, st = rwkv.rwkv6_timemix_train(
                    lp["tm"], cfg, h, seg, return_state=True)
            h = h + tm_out
            from repro.models.layers import layernorm
            cm_shift = layernorm(lp["cm"]["ln"], h, cfg.norm_eps)[:, -1:]
            with jax.named_scope("channelmix"):
                h = h + rwkv.rwkv6_channelmix_train(lp["cm"], cfg, h, seg)
            st["cm_shift"] = cm_shift
            return h, st
        with jax.named_scope("timemix"):
            h = h + rwkv.rwkv6_timemix_train(lp["tm"], cfg, h, seg)
        with jax.named_scope("channelmix"):
            h = h + rwkv.rwkv6_channelmix_train(lp["cm"], cfg, h, seg)
        h = shard(h, "batch", "seq", "act_embed")
        return h, None

    body = layer_fn
    if cfg.remat != "none" and not return_state:
        body = jax.checkpoint(layer_fn)
    h, states = jax.lax.scan(body, h, params["layers"])
    h = L.layernorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    logits = shard(logits, "batch", "seq", "act_vocab")
    if return_state:
        return logits, states
    return logits, jnp.float32(0.0)


# ---------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    del max_len  # constant-size state: the point of an SSM
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    dk = cfg.rwkv_head_dim
    Lc = cfg.num_layers
    return {
        "tm_shift": jnp.zeros((Lc, batch, 1, d), dtype),
        "cm_shift": jnp.zeros((Lc, batch, 1, d), dtype),
        "wkv": jnp.zeros((Lc, batch, h, dk, dk), jnp.float32),
    }


def cache_logical_axes(cfg: ModelConfig):
    return {"tm_shift": ("layers", "batch", None, None),
            "cm_shift": ("layers", "batch", None, None),
            "wkv": ("layers", "batch", "act_heads", None, None)}


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    del pos  # state carries all positional context
    h = L.embed(params["embed"], tokens)
    h = L.layernorm(params["ln0"], h, cfg.norm_eps)

    def layer_fn(h, xs):
        lp, tm_shift, cm_shift, wkv_state = xs
        tm_out, tm_new = rwkv.rwkv6_timemix_decode(
            lp["tm"], cfg, h, {"tm_shift": tm_shift, "wkv": wkv_state})
        h = h + tm_out
        cm_out, cm_new = rwkv.rwkv6_channelmix_decode(
            lp["cm"], cfg, h, {"cm_shift": cm_shift})
        h = h + cm_out
        return h, {"tm_shift": tm_new["tm_shift"].astype(tm_shift.dtype),
                   "cm_shift": cm_new["cm_shift"].astype(cm_shift.dtype),
                   "wkv": tm_new["wkv"]}

    h, new_cache = jax.lax.scan(
        layer_fn, h,
        (params["layers"], cache["tm_shift"], cache["cm_shift"],
         cache["wkv"]))
    h = L.layernorm(params["final_norm"], h, cfg.norm_eps)
    logits = h @ params["unembed"]
    return logits, new_cache
