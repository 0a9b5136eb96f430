"""Mamba2 (SSD) block — chunked state-space duality formulation.

Used by the zamba2-7b hybrid.  Train path: chunked scan (O(s*L) memory with
rematerialized chunk bodies); decode path: single-step recurrence over the
carried (conv, ssm) state.

Recurrence (per head h, state size N, head dim P):
    S_t = a_t * S_{t-1} + (dt_t * x_t) (x) B_t          S in R^{P x N}
    y_t = C_t . S_t + D * x_t
with a_t = exp(dt_t * A), A = -exp(A_log) < 0, dt_t = softplus(...).

Simplifications vs the reference CUDA implementation (documented in
DESIGN.md): single B/C group; the causal depthwise conv is applied to the
SSM input stream only.  Both preserve the block's compute/memory
character.

Packing semantics: the SSM state resets exactly at segment starts (tracked
as reset COUNTS, see the chunked scan); the depthwise conv window leaks up
to CONV_K-1 tokens across packed boundaries — same accepted leakage as
RWKV's token shift (tests/test_models.py pins this contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import (
    CONV, EMBED, ParamDef, SSM_INNER, SSM_STATE,
)
from repro.models.layers import rmsnorm_def, rmsnorm
from repro.sharding.logical import shard

CONV_K = 4  # depthwise conv kernel width


def mamba2_def(cfg) -> dict:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    n_heads = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    return {
        # fused input projection -> [z, x, B, C, dt]
        "w_z": ParamDef((d, d_in), (EMBED, SSM_INNER), init="scaled"),
        "w_x": ParamDef((d, d_in), (EMBED, SSM_INNER), init="scaled"),
        "w_B": ParamDef((d, N), (EMBED, SSM_STATE), init="scaled"),
        "w_C": ParamDef((d, N), (EMBED, SSM_STATE), init="scaled"),
        "w_dt": ParamDef((d, n_heads), (EMBED, None), init="scaled"),
        "dt_bias": ParamDef((n_heads,), (None,), init="zeros"),
        "A_log": ParamDef((n_heads,), (None,), init="zeros"),
        "D": ParamDef((n_heads,), (None,), init="ones"),
        "conv": ParamDef((CONV_K, d_in), (CONV, SSM_INNER), init="scaled"),
        "norm": rmsnorm_def(d_in),
        "w_out": ParamDef((d_in, d), (SSM_INNER, EMBED), init="scaled"),
    }


def _causal_depthwise_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (b, s, c); w: (K, c).  Causal: output t sees x[t-K+1 .. t]."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(K):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def _project(p, cfg, x):
    """Shared projection for train/decode.  x: (b, s, d)."""
    z = x @ p["w_z"]
    xs = x @ p["w_x"]
    B = (x @ p["w_B"]).astype(jnp.float32)
    C = (x @ p["w_C"]).astype(jnp.float32)
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    loga = dt * A                                  # (b, s, H) log decay
    return z, xs, B, C, dt, loga


def mamba2_train(p: dict, cfg, x: jax.Array, segment_ids: jax.Array,
                 return_state: bool = False):
    """x: (b, s, d_model); segment_ids: (b, s).  Returns (b, s, d_model),
    and with ``return_state`` also the final {ssm, conv} state (prefill)."""
    b, s, d = x.shape
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    L = min(cfg.ssm_chunk, s)
    assert s % L == 0, (s, L)
    nc = s // L

    prev_seg = jnp.pad(segment_ids[:, :-1], ((0, 0), (1, 0)))
    seg_reset = (segment_ids != prev_seg) | (segment_ids == 0)

    z, xs, Bv, Cv, dt, loga = _project(p, cfg, x)
    xs_raw = xs                                    # pre-conv stream (prefill)
    xs = _causal_depthwise_conv(xs, p["conv"])
    xs = shard(xs, "batch", "seq", "act_ssm")
    xh = xs.reshape(b, s, H, P).astype(jnp.float32)
    dtx = xh * dt[..., None]                       # (b, s, H, P)

    # chunked SSD scan.  Segment resets are tracked as COUNTS (never folded
    # into the fp32 decay cumsum — catastrophic cancellation; see rwkv.py).
    def split(a):  # (b, s, ...) -> (nc, b, L, ...)
        return a.reshape((b, nc, L) + a.shape[2:]).swapaxes(0, 1)

    xc, dc, Bc, Cc, lac = map(split, (dtx, dt, Bv, Cv, loga))
    rc = seg_reset.astype(jnp.int32).reshape(b, nc, L).swapaxes(0, 1)
    tri = jnp.tril(jnp.ones((L, L), bool))

    @jax.checkpoint
    def body(S, inp):
        xb, dtb, Bb, Cb, lab, rb = inp             # (b,L,H,P) (b,L,H) (b,L)
        cla = jnp.cumsum(lab, axis=1)              # (b, L, H) cumulative
        R = jnp.cumsum(rb, axis=1)                 # resets up to & incl t
        # intra-chunk: M[b,h,l,m] = (C_l . B_m) * exp(cla_l - cla_m),
        # valid iff l >= m and no reset in (m, l]  <=>  R_l == R_m
        scores = jnp.einsum("bln,bmn->blm", Cb, Bb)
        decay = jnp.exp(jnp.minimum(
            cla[:, :, None, :] - cla[:, None, :, :], 0.0))  # (b, l, m, H)
        valid = (R[:, :, None] == R[:, None, :]) \
            & tri[None, :, :]                      # (b, l, m)
        M = scores[..., None] * decay * valid[..., None]
        y = jnp.einsum("blmh,bmhp->blhp", M, xb)
        # inter-chunk: carried state survives only until the first reset
        carry_gate = (R == 0)[:, :, None]          # (b, l, 1)
        y = y + jnp.einsum("bln,bhpn,blh->blhp", Cb, S,
                           jnp.exp(cla) * carry_gate)
        # state update: kv_m survives iff no reset in (m, L]
        k_gate = (R[:, -1:] == R)[:, :, None]      # (b, m, 1)
        S_new = jnp.einsum("bmhp,bmn,bmh->bhpn", xb, Bb,
                           jnp.exp(cla[:, -1:, :] - cla) * k_gate) \
            + S * (jnp.exp(cla[:, -1])
                   * (R[:, -1] == 0)[:, None])[:, :, None, None]
        return S_new, y

    S0 = jnp.zeros((b, H, P, N), jnp.float32)
    S_final, ys = jax.lax.scan(body, S0, (xc, dc, Bc, Cc, lac, rc))
    y = ys.swapaxes(0, 1).reshape(b, s, H, P)
    y = y + xh * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(b, s, d_in)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32)),
                cfg.norm_eps)
    y = shard(y.astype(x.dtype), "batch", "seq", "act_ssm")
    out = y @ p["w_out"]
    if return_state:
        state = {"ssm": S_final,
                 "conv": xs_raw[:, -(CONV_K - 1):].astype(jnp.float32)}
        return out, state
    return out


def mamba2_init_state(cfg, batch: int, dtype=jnp.float32) -> dict:
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    return {
        "ssm": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state), dtype),
        "conv": jnp.zeros((batch, CONV_K - 1, d_in), dtype),
    }


def mamba2_decode(p: dict, cfg, x: jax.Array, state: dict):
    """Single-step decode.  x: (b, 1, d_model).  Returns (y, new_state)."""
    b = x.shape[0]
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim

    z, xs, Bv, Cv, dt, loga = _project(p, cfg, x)
    # conv over carried window
    window = jnp.concatenate([state["conv"].astype(xs.dtype), xs], axis=1)
    w = p["conv"].astype(jnp.float32)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w)
    xs1 = jax.nn.silu(conv_out)[:, None, :]
    xh = xs1.reshape(b, 1, H, P).astype(jnp.float32)

    a = jnp.exp(loga[:, 0])                        # (b, H)
    dtx = (xh * dt[..., None])[:, 0]               # (b, H, P)
    S = state["ssm"] * a[:, :, None, None] \
        + jnp.einsum("bhp,bn->bhpn", dtx, Bv[:, 0])
    y = jnp.einsum("bn,bhpn->bhp", Cv[:, 0], S)
    y = y + xh[:, 0] * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, 1, d_in)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32)),
                cfg.norm_eps).astype(x.dtype)
    new_state = {"ssm": S, "conv": window[:, 1:]}
    return y @ p["w_out"], new_state
