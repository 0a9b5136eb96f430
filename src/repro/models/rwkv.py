"""RWKV6 "Finch" — attention-free time mixing with data-dependent decay.

WKV recurrence per head (dk = dv = head_dim):
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t,   w_t = exp(-exp(w0 + lora_w(x)))

Training uses a chunked formulation where *every* exponent is a cumulative
log-decay difference over a non-empty causal range, hence <= 0: no clamping
tricks or sub-chunk re-scaling are needed (unlike the exp(-cw) factorised
form, which overflows for fast decays).  The intra-chunk term is a fused
(t, s, i) reduce; on TPU this is the Pallas wkv kernel's tile loop
(kernels/wkv6.py), here it is the XLA reference path.

Packing: a document starts with an empty state and a zero token shift, as
at the start of a sequence.  The chunked scan counts segment starts and
drops every (t, s) interaction and carried state that crosses one; the
token shift is zero at a segment start and on padding.  Padding tokens
(seg 0) contribute nothing to the state (their k is zeroed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.rwkv6_3b import MIX_LORA
from repro.models.params import (
    EMBED, LORA, MLP, ParamDef, RWKV_HEADS,
)
from repro.models.layers import layernorm_def
from repro.sharding.logical import shard

_MIX_TARGETS = ("r", "k", "v", "w", "g")
# The WKV's f32 dots at full f32 precision: the TPU's default rounds their
# operands to bf16 (as in kernels/wkv6.py).
F32 = jax.lax.Precision.HIGHEST
# GroupNorm's epsilon after the WKV, as the Finch code sets it: 1e-5 x
# head_size_divisor^2 with head_size_divisor 8.  At a document's first
# token a head's WKV output is one scalar times v, and GroupNorm's gradient
# through that scalar near 0 is 1/sqrt(eps): the larger epsilon keeps it
# ~8x smaller than the LayerNorms' 1e-5 would.
GROUP_NORM_EPS = 1e-5 * 8 ** 2


def rwkv6_timemix_def(cfg) -> dict:
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    lo = cfg.rwkv_lora_dim
    p: dict = {
        "ln": layernorm_def(d),
        "mu_base": ParamDef((d,), (None,), init="uniform", scale=0.5),
    }
    for t in _MIX_TARGETS:
        p[f"mu_{t}"] = ParamDef((d,), (None,), init="uniform", scale=0.5)
        p[f"mixA_{t}"] = ParamDef((d, MIX_LORA), (EMBED, LORA), init="scaled")
        p[f"mixB_{t}"] = ParamDef((MIX_LORA, d), (LORA, EMBED), init="zeros")
    for t in ("r", "k", "v", "g", "o"):
        p[f"w_{t}"] = ParamDef((d, d), (EMBED, None), init="scaled")
    p["w0"] = ParamDef((d,), (None,), init="uniform", scale=1.0)
    p["loraA_w"] = ParamDef((d, lo), (EMBED, LORA), init="scaled")
    p["loraB_w"] = ParamDef((lo, d), (LORA, EMBED), init="zeros")
    p["u"] = ParamDef((h, cfg.rwkv_head_dim), (RWKV_HEADS, None),
                      init="uniform", scale=0.5)
    p["out_ln"] = layernorm_def(d)
    return p


def rwkv6_channelmix_def(cfg) -> dict:
    d, dff = cfg.d_model, cfg.d_ff
    return {
        "ln": layernorm_def(d),
        "mu_k": ParamDef((d,), (None,), init="uniform", scale=0.5),
        "mu_r": ParamDef((d,), (None,), init="uniform", scale=0.5),
        "w_k": ParamDef((d, dff), (EMBED, MLP), init="scaled"),
        "w_v": ParamDef((dff, d), (MLP, EMBED), init="scaled"),
        "w_r": ParamDef((d, d), (EMBED, None), init="scaled"),
    }


def _resets(segment_ids: jax.Array) -> jax.Array:
    """(b, s) bool: True where a document starts (the segment id differs
    from the previous token's, or t = 0) and on padding (segment 0)."""
    prev_seg = jnp.pad(segment_ids[:, :-1], ((0, 0), (1, 0)))
    return (segment_ids != prev_seg) | (segment_ids == 0)


def _token_shift(x: jax.Array, reset: jax.Array) -> jax.Array:
    """x: (b, s, d) -> the previous token's stream, zero where ``reset``
    (a document's first token, padding), so a packed document never takes
    in the one before it."""
    shifted = jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))
    return jnp.where(reset[..., None], jnp.zeros_like(x), shifted)


def _ddlerp(p, t: str, x, xs, base_mix):
    mu = p[f"mu_{t}"].astype(x.dtype)
    lora = jnp.tanh(base_mix @ p[f"mixA_{t}"]) @ p[f"mixB_{t}"]
    mix = mu + lora
    return x + (xs - x) * mix


def _per_head_ln(p, x):
    """x: (b, s, h, dk) — GroupNorm(heads) with a scale and bias per
    channel, (h * dk,)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    shape = x.shape[-2:]
    return ((xf - mu) * jax.lax.rsqrt(var + GROUP_NORM_EPS)
            * p["scale"].reshape(shape) + p["bias"].reshape(shape))


def _project(p, cfg, x, x_shift):
    """Shared r/k/v/w/g/u projection.  Returns fp32 tensors."""
    b, s, d = x.shape
    h = d // cfg.rwkv_head_dim
    dk = cfg.rwkv_head_dim
    base_mix = x + (x_shift - x) * p["mu_base"].astype(x.dtype)
    xr = _ddlerp(p, "r", x, x_shift, base_mix)
    xk = _ddlerp(p, "k", x, x_shift, base_mix)
    xv = _ddlerp(p, "v", x, x_shift, base_mix)
    xw = _ddlerp(p, "w", x, x_shift, base_mix)
    xg = _ddlerp(p, "g", x, x_shift, base_mix)
    r = (xr @ p["w_r"]).reshape(b, s, h, dk).astype(jnp.float32)
    k = (xk @ p["w_k"]).reshape(b, s, h, dk).astype(jnp.float32)
    v = (xv @ p["w_v"]).reshape(b, s, h, dk).astype(jnp.float32)
    g = jax.nn.silu((xg @ p["w_g"]).astype(jnp.float32))
    w_raw = p["w0"].astype(jnp.float32) \
        + (jnp.tanh(xw @ p["loraA_w"]) @ p["loraB_w"]).astype(jnp.float32)
    loga = -jnp.exp(w_raw).reshape(b, s, h, dk)        # log decay, <= 0
    return r, k, v, g, loga


def wkv6_chunked(r, k, v, loga, u, *, chunk: int, reset: jax.Array,
                 return_state: bool = False):
    """Chunked WKV6.  r,k,v,loga: (b, s, h, dk) fp32; u: (h, dk);
    reset: (b, s) bool — True where a new segment starts (or padding).
    Returns o: (b, s, h, dv) fp32 (+ final state S if requested).
    """
    b, s, h, dk = r.shape
    L = min(chunk, s)
    assert s % L == 0, (s, L)
    nc = s // L

    # Resets are tracked as COUNTS, never folded into the log-decay sums:
    # adding a -1e30 penalty into an fp32 cumsum destroys every subsequent
    # decay difference (catastrophic cancellation).  A (t, s) interaction
    # is valid iff the running reset count is equal at both ends.
    #
    # Layout is HEAD-MAJOR (b, h, L, dk) throughout the chunk body so the
    # large (b, h, t, s, i) decay tensor is produced and consumed in one
    # layout — the token-major form made XLA materialize a transposed copy
    # of it per chunk step (~17 TB/device/step at rwkv6-3b train_4k scale;
    # EXPERIMENTS.md §Perf iteration R2).
    rst = reset.astype(jnp.int32)

    def split(a):  # (b, s, h, dk) -> (nc, b, h, L, dk)
        return a.reshape(b, nc, L, h, dk).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, lac = map(split, (r, k, v, loga))
    pc = rst.reshape(b, nc, L).swapaxes(0, 1)
    tri_strict = jnp.tril(jnp.ones((L, L), bool), k=-1)

    @jax.checkpoint
    def body(S, inp):
        rb, kb, vb, lab, rb_rst = inp               # (b, h, L, dk); (b, L)
        cw = jnp.cumsum(lab, axis=2)                # incl current token
        cwm1 = cw - lab                             # excl current token
        R = jnp.cumsum(rb_rst, axis=1)              # resets up to & incl t
        # state (inter-chunk) term: valid only if NO reset in chunk <= t
        q_valid = (R == 0)[:, None, :, None]
        q_exp = jnp.where(q_valid, jnp.exp(jnp.minimum(cwm1, 0.0)), 0.0)
        o = jnp.einsum("bhti,bhij->bhtj", rb * q_exp, S, precision=F32)
        # intra: A[t,s] = sum_i r[t,i] k[s,i] exp(cwm1_t - cw_s), s < t,
        # valid iff no reset in (s, t]  <=>  R_t == R_s
        expo = cwm1[:, :, :, None] - cw[:, :, None]  # (b, h, t, s, i)
        pair_valid = (R[:, :, None] == R[:, None, :])[:, None, ..., None]
        ex = jnp.where(pair_valid, jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
        A = jnp.einsum("bhti,bhsi,bhtsi->bhts", rb, kb, ex, precision=F32)
        A = A * tri_strict[None, None]
        o = o + jnp.einsum("bhts,bhsj->bhtj", A, vb, precision=F32)
        # diagonal bonus term: (r_t . (u * k_t)) v_t
        diag = jnp.einsum("bhti,hi,bhti->bht", rb, u, kb, precision=F32)
        o = o + diag[..., None] * vb
        # state update: S' = exp(cw_L) S + sum_s exp(cw_L - cw_s) k_s^T v_s
        # carried state survives only a reset-free chunk; kv_s survives
        # only if no reset in (s, L]
        dec_all = jnp.where((R[:, -1] == 0)[:, None, None],
                            jnp.exp(jnp.minimum(cw[:, :, -1], 0.0)), 0.0)
        k_valid = (R[:, -1:] == R)[:, None, :, None]
        k_hat = kb * jnp.where(
            k_valid, jnp.exp(jnp.minimum(cw[:, :, -1:] - cw, 0.0)), 0.0)
        S_new = S * dec_all[..., None] \
            + jnp.einsum("bhsi,bhsj->bhij", k_hat, vb, precision=F32)
        return S_new, o

    S0 = jnp.zeros((b, h, dk, dk), jnp.float32)
    S_final, os_ = jax.lax.scan(body, S0, (rc, kc, vc, lac, pc))
    # (nc, b, h, L, dk) -> (b, s, h, dk)
    o = os_.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dk)
    if return_state:
        return o, S_final
    return o


def wkv_chunks(segment_ids: jax.Array, chunk: int):
    """(live, total) (row, chunk) pairs of the chunked WKV over a batch's
    packed rows (b, s), per head and layer: ``wkv6_chunked`` computes every
    chunk, padding or not.  int32 scalars."""
    b, s = segment_ids.shape
    total = jnp.int32(b * (s // min(chunk, s)))
    return total, total


def rwkv6_timemix_train(p, cfg, x, segment_ids, return_state: bool = False):
    """x: (b, s, d).  Full time-mix sublayer (includes its own LN)."""
    b, s, d = x.shape
    h = d // cfg.rwkv_head_dim
    from repro.models.layers import layernorm
    xn = layernorm(p["ln"], x, cfg.norm_eps)
    reset = _resets(segment_ids)
    xs = _token_shift(xn, reset)
    r, k, v, g, loga = _project(p, cfg, xn, xs)
    k = k * (segment_ids > 0)[..., None, None]      # padding adds no state
    u = p["u"].astype(jnp.float32)
    with jax.named_scope("wkv"):
        o = wkv6_chunked(r, k, v, loga, u, chunk=cfg.rwkv_chunk, reset=reset,
                         return_state=return_state)
    if return_state:
        o, S_final = o
    o = _per_head_ln(p["out_ln"], o) * g.reshape(b, s, h, -1)
    o = shard(o.astype(x.dtype), "batch", "seq", "act_heads", None)
    out = o.reshape(b, s, d) @ p["w_o"]
    if return_state:
        return out, {"tm_shift": xn[:, -1:], "wkv": S_final}
    return out


def rwkv6_channelmix_train(p, cfg, x, segment_ids):
    from repro.models.layers import layernorm
    xn = layernorm(p["ln"], x, cfg.norm_eps)
    xs = _token_shift(xn, _resets(segment_ids))
    xk = xn + (xs - xn) * p["mu_k"].astype(xn.dtype)
    xr = xn + (xs - xn) * p["mu_r"].astype(xn.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    kk = shard(kk, "batch", "seq", "act_mlp")
    return jax.nn.sigmoid(xr @ p["w_r"]) * (kk @ p["w_v"])


# ---------------------------------------------------------------- decode
def rwkv6_init_state(cfg, batch: int) -> dict:
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    dk = cfg.rwkv_head_dim
    return {
        "tm_shift": jnp.zeros((batch, 1, d), jnp.bfloat16),
        "cm_shift": jnp.zeros((batch, 1, d), jnp.bfloat16),
        "wkv": jnp.zeros((batch, h, dk, dk), jnp.float32),
    }


def rwkv6_timemix_decode(p, cfg, x, state):
    """x: (b, 1, d).  Returns (out, new_state pieces)."""
    b, _, d = x.shape
    h = d // cfg.rwkv_head_dim
    from repro.models.layers import layernorm
    xn = layernorm(p["ln"], x, cfg.norm_eps)
    xs = state["tm_shift"].astype(xn.dtype)
    r, k, v, g, loga = _project(p, cfg, xn, xs)
    u = p["u"].astype(jnp.float32)
    S = state["wkv"]
    r1, k1, v1 = r[:, 0], k[:, 0], v[:, 0]          # (b, h, dk)
    kv = jnp.einsum("bhi,bhj->bhij", k1, v1, precision=F32)
    o = jnp.einsum("bhi,bhij->bhj", r1, S + u[None, :, :, None] * kv,
                   precision=F32)
    S_new = S * jnp.exp(loga[:, 0])[..., None] + kv
    o = _per_head_ln(p["out_ln"], o[:, None])[:, 0] \
        * g.reshape(b, 1, h, -1)[:, 0]
    out = (o.reshape(b, 1 * d)[:, None, :]).astype(x.dtype) @ p["w_o"]
    return out, {"tm_shift": xn, "wkv": S_new}


def rwkv6_channelmix_decode(p, cfg, x, state):
    from repro.models.layers import layernorm
    xn = layernorm(p["ln"], x, cfg.norm_eps)
    xs = state["cm_shift"].astype(xn.dtype)
    xk = xn + (xs - xn) * p["mu_k"].astype(xn.dtype)
    xr = xn + (xs - xn) * p["mu_r"].astype(xn.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    out = jax.nn.sigmoid(xr @ p["w_r"]) * (kk @ p["w_v"])
    return out, {"cm_shift": xn}
