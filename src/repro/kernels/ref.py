"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def packed_attention_ref(q, k, v, q_seg, kv_seg, *, causal: bool = True):
    """q: (b, h, sq, d); k, v: (b, kh, sk, d); segs: (b, s)."""
    b, h, sq, d = q.shape
    kh = k.shape[1]
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=1)
        v = jnp.repeat(v, h // kh, axis=1)
    scale = d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = (q_seg[:, None, :, None] == kv_seg[:, None, None, :]) \
        & (kv_seg[:, None, None, :] > 0)
    if causal:
        sq_i = jnp.arange(sq)[:, None]
        sk_i = jnp.arange(k.shape[2])[None, :]
        mask = mask & (sq_i >= sk_i)[None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-20),
                     v.astype(jnp.float32))
    out = jnp.where((q_seg > 0)[:, None, :, None], out, 0.0)
    return out.astype(q.dtype)


def flash_decode_ref(q, k_cache, v_cache, cache_len):
    """q: (b, h, d); caches: (b, kh, S, d); cache_len: (b,)."""
    b, h, d = q.shape
    kh, S = k_cache.shape[1], k_cache.shape[2]
    if kh != h:
        k_cache = jnp.repeat(k_cache, h // kh, axis=1)
        v_cache = jnp.repeat(v_cache, h // kh, axis=1)
    scale = d ** -0.5
    logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    mask = (jnp.arange(S)[None, None, :] < cache_len[:, None, None])
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhk,bhkd->bhd", p / jnp.maximum(l, 1e-20),
                     v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def wkv6_ref(r, k, v, loga, u, reset):
    """Sequential WKV6 oracle (one scan step per token).  r,k,v,loga:
    (b, s, h, dk) fp32; u: (h, dk); reset: (b, s) bool.  Returns
    (b, s, h, dk)."""
    b, s, h, dk = r.shape

    def step(S, xs):
        r_t, k_t, v_t, la_t, rst_t = xs
        S = jnp.where(rst_t[:, None, None, None], 0.0, S)
        kv = jnp.einsum("bhi,bhj->bhij", k_t, v_t)
        o = jnp.einsum("bhi,bhij->bhj", r_t, S + u[None, :, :, None] * kv)
        return S * jnp.exp(la_t)[..., None] + kv, o

    t_major = lambda a: jnp.moveaxis(jnp.asarray(a), 1, 0)
    _, outs = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dk), jnp.float32),
        (t_major(r), t_major(k), t_major(v), t_major(loga),
         t_major(reset)))
    return jnp.moveaxis(outs, 0, 1)
