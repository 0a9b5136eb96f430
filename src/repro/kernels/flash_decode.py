"""Pallas TPU kernel: flash-decode (one query token vs a long KV cache).

Grid = (batch, kv_heads, kv_blocks); for each (b, kv-head) the query rows
are that head's GQA GROUP of q heads (group x d) — this keeps the MXU fed
even at decode (group>=2 for GQA archs) instead of one-row matmuls.
Online-softmax state lives in VMEM scratch across kv blocks; positions
beyond ``cache_len`` are masked.  This is the single-chip building block
of the KV-sequence-parallel decode path (each chip runs it over its KV
shard, then combines with a small psum — see models/attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 256
NEG_INF = -1e30
LANES = 128      # m/l scratch rows span one full vreg width


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, block_k: int,
                   num_kv_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[pl.program_id(0)]          # scalar from SMEM
    live = ik * block_k < cache_len

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (BK, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        mask = cols < cache_len
        logits = jnp.where(mask, logits, NEG_INF)
        # m/l scratch hold each row's value in every lane; column 0 is read
        m_prev = m_ref[:, :1]                      # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, cache_len, *,
                 block_k: int = DEFAULT_BLOCK_K, interpret: bool):
    """q: (b, h, d); caches: (b, kh, S, d); cache_len: (b,) int32.
    Returns (b, h, d).  ``cache_len`` is scalar-prefetched into SMEM;
    ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    b, h, d = q.shape
    kh, S = k_cache.shape[1], k_cache.shape[2]
    assert h % kh == 0
    group = h // kh
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kh, group, d)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, num_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda ib, ih, ik, _: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, ik, _: (ib, ih, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, ik, _: (ib, ih, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d),
                                   lambda ib, ih, ik, _: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, d), jnp.float32),
                pltpu.VMEM((group, LANES), jnp.float32),
                pltpu.VMEM((group, LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kh, group, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(cache_len, jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
