"""Pallas TPU kernel: chunked WKV6 (RWKV6 linear-attention) forward.

Grid = (batch, heads, chunks); the chunk dim is innermost and sequential,
carrying the state matrix in VMEM scratch, stored transposed (dv x dk) so
per-key decay scales it along lanes — the linear-attention analogue of the
flash pattern.  Within-chunk cumulative sums (decay exponents, reset
counts) are taken by XLA before the call; reset counts arrive both as a
column and as a row, so no vector is reshaped or transposed in the kernel.
All decay exponents are causal-range cumulative sums (<= 0), so the kernel
needs no rescaling tricks (see models/rwkv.py for the math and the
reset-penalty packing semantics).

The intra-chunk (t, s, i) tensor lives entirely in VMEM:
L=64, dk=64 -> 1 MiB fp32, the MXU-friendly sweet spot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
NEG = -1e30
# f32 dots at full f32 precision: Mosaic's default rounds their operands
# to bf16, about 3e-3 of the output's scale at 4096 tokens on a v5e
F32 = jax.lax.Precision.HIGHEST


def _wkv_kernel(u_ref, rcol_ref, rrow_ref, r_ref, k_ref, v_ref, loga_ref,
                cw_ref, o_ref, st_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    r = r_ref[0, 0].astype(jnp.float32)           # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)           # (L, dv)
    loga = loga_ref[0, 0].astype(jnp.float32)     # (L, dk) pure log decay
    cw = cw_ref[0, 0]                             # (L, dk) incl current token
    R = rcol_ref[0, 0]                            # (L, 1) resets up to t
    R_row = rrow_ref[0, 0]                        # (1, L) same, along lanes
    u = u_ref[0].astype(jnp.float32)              # (1, dk)
    ST = st_ref[...]                              # (dv, dk): state, transposed

    # Reset counts (exact), never folded into the fp32 decay cumsum — see
    # models/rwkv.py for the catastrophic-cancellation rationale.
    cwm1 = cw - loga                              # excl current token

    # inter-chunk: valid only while no reset has occurred in this chunk
    q_exp = jnp.where(R == 0, jnp.exp(jnp.minimum(cwm1, 0.0)), 0.0)
    o = jax.lax.dot_general((r * q_exp), ST, (((1,), (1,)), ((), ())),
                            precision=F32,
                            preferred_element_type=jnp.float32)
    # intra-chunk: A[t,s] = sum_i r[t,i] k[s,i] exp(cwm1_t - cw_s),
    # s < t, valid iff R_t == R_s (no reset in (s, t])
    expo = jnp.minimum(cwm1[:, None, :] - cw[None, :, :], 0.0)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(expo), axis=-1)
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(jnp.logical_and(t_i > s_i, R == R_row), A, 0.0)
    o = o + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                precision=F32,
                                preferred_element_type=jnp.float32)
    # diagonal bonus: (r_t . (u * k_t)) v_t
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)
    o = o + diag * v
    # state update
    R_last = R[chunk - 1:, :]                     # (1, 1)
    cw_last = cw[chunk - 1:, :]                   # (1, dk)
    dec = jnp.where(R_last == 0, jnp.exp(jnp.minimum(cw_last, 0.0)), 0.0)
    k_hat = k * jnp.where(R_last == R,
                          jnp.exp(jnp.minimum(cw_last - cw, 0.0)), 0.0)
    st_ref[...] = ST * dec + jax.lax.dot_general(
        v, k_hat, (((0,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def wkv6_forward(r, k, v, loga, u, reset, *, chunk: int = DEFAULT_CHUNK,
                 interpret: bool):
    """r, k, v, loga: (b, h, s, dk) fp32; u: (h, dk); reset: (b, s) bool.
    Returns o: (b, h, s, dk).  ``interpret=True`` runs the Pallas
    interpreter (CPU tests); ``False`` lowers to Mosaic for the TPU."""
    b, h, s, dk = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    # within-chunk cumulative sums are taken here, outside the kernel:
    # decay exponents (b, h, s, dk) and reset counts as a column and a row
    cw = jnp.cumsum(jnp.asarray(loga, jnp.float32).reshape(
        b, h, nc, chunk, dk), axis=3).reshape(b, h, s, dk)
    R = jnp.cumsum(jnp.asarray(reset, jnp.int32).reshape(b, nc, chunk),
                   axis=-1)
    r_col, r_row = R[..., None], R[:, :, None, :]

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    blk = lambda ib, ih, ic: (ib, ih, ic, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, dk), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, chunk, dk), blk),
            pl.BlockSpec((1, 1, chunk, dk), blk),
            pl.BlockSpec((1, 1, chunk, dk), blk),
            pl.BlockSpec((1, 1, chunk, dk), blk),
            pl.BlockSpec((1, 1, chunk, dk), blk),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dk), blk),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dk), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(u).reshape(h, 1, dk), r_col, r_row, r, k, v, loga, cw)
