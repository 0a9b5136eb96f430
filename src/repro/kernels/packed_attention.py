"""Pallas TPU kernel: segment-aware block-skipping flash attention (fwd).

This is THE compute hot-spot the paper's load balancing targets: with
packed variable-length sequences, per-microbatch attention time is
proportional to sum(l_i^2) over segments — but ONLY if the kernel skips
(Q-block, KV-block) tiles whose segment ranges cannot intersect.  This
kernel does exactly that, making the planner's ``cost()`` model exact.

TPU mapping (DESIGN.md §2 hardware adaptation):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dim is innermost
    and sequential, carrying the online-softmax state in VMEM scratch
    (acc/m/l) across kv steps — the canonical TPU flash pattern.
  * BlockSpec tiles: q (BQ, d), k/v (BK, d) in VMEM; BQ=BK=128 aligns the
    MXU's 128x128 systolic tiles.
  * GQA without KV expansion: the k/v index_map divides the q-head index
    by the group size.
  * Tile skipping: causal skip (block fully above the diagonal) and
    segment skip (max(seg_q) < min(seg_k) or max(seg_k) < min(seg_q) —
    segment ids are nondecreasing within a packed row).  Skipped tiles do
    no MXU work; on real hardware the same predicate would drive scalar-
    prefetch DMA skipping, noted as a further optimization.

Validated in interpret mode against kernels/ref.py (pure jnp oracle);
tests/test_tpu_compile.py lowers it for a v5e at qwen3-8b widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
LANES = 128      # m/l scratch rows span one full vreg width


def _attn_kernel(q_seg_ref, k_seg_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                 block_q: int, block_k: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_seg = q_seg_ref[0]                          # (BQ, 1): sublanes
    k_seg = k_seg_ref[0]                          # (1, BK): lanes

    # --- tile skipping -------------------------------------------------
    causal_live = (iq * block_q + block_q - 1 >= ik * block_k) \
        if causal else True
    seg_live = jnp.logical_and(
        jnp.max(q_seg) >= jnp.min(k_seg),
        jnp.max(k_seg) >= jnp.min(q_seg))
    any_valid = jnp.logical_and(jnp.max(q_seg) > 0, jnp.max(k_seg) > 0)
    live = jnp.logical_and(jnp.logical_and(seg_live, any_valid),
                           causal_live)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (BQ, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (BK, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.logical_and(q_seg == k_seg, k_seg > 0)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        logits = jnp.where(mask, logits, NEG_INF)
        # m/l scratch hold each row's value in every lane; column 0 is read
        m_prev = m_ref[:, :1]                     # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)
        out = jnp.where(q_seg > 0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def packed_flash_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool):
    """q: (b, h, sq, d); k, v: (b, kh, sk, d); segs: (b, s) int32.
    Returns (b, h, sq, d) in q.dtype.  ``interpret=True`` runs the Pallas
    interpreter (CPU tests); ``False`` lowers to Mosaic for the TPU.
    """
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    group = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(d)
    # q ids run down sublanes and kv ids along lanes, so the mask is a
    # broadcast compare with no in-kernel reshape
    q_seg = jnp.asarray(q_seg, jnp.int32)[:, :, None]        # (b, sq, 1)
    kv_seg = jnp.asarray(kv_seg, jnp.int32)[:, None, :]      # (b, 1, sk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q_seg, kv_seg, q, k, v)
