"""Pallas TPU kernel: packed-row flash attention that computes only live
tiles, forward and backward.

With packed variable-length documents, a (query-block, key-block) tile of a
row's attention holds work only where a query and a key share a segment
(and, causal, the key does not come after the query).  The kernel runs
those tiles and no others, so a row's attention costs sum(l_i^2) over its
documents rather than seq_len^2: the cost the planner balances
(``data/cost_models.py``).

Live ranges.  ``block_ranges`` computes, inside the jit, from the segment
ids, for each (row, query block) the first and last key block it needs and
for each (row, key block) the first and last query block.  A range is the
hull of the key (query) positions that share a segment id with the block's
queries (keys), clipped by causality; it holds every live tile, and for
rows packed as ``data/packing.py`` packs them (contiguous segments, padding
0 at the end) no other.  The ranges reach the kernels by scalar prefetch.

TPU mapping.
  * forward and dQ: grid (batch, q_heads, q_blocks).  The row's K/V of the
    head's KV group stay resident in VMEM (their block index changes only
    with the KV head, so GQA needs no expansion and the grid makes no DMA
    per tile); each step loops over its live key blocks only.  A query
    block with no live key block runs zero iterations and writes zeros.
  * dK/dV: grid (batch, q_heads, k_blocks), the head's Q and dO resident,
    looping over the key block's live query blocks; each query head writes
    its own dK/dV, summed over the KV group after the kernel.
  * Blocks of 128 match the MXU.  Logits and softmax statistics are f32;
    the products with P, dS (P·V, dS·K, dSᵀ·Q, Pᵀ·dO) are f32 at full
    precision on the live tiles.

Validated in interpret mode against ``kernels/ref.py`` and, with its
gradients, the jnp ``models/attention.py: chunked_segment_attention``
(tests/test_kernels.py); tests/test_tpu_compile.py lowers it, forward and
backward, for a v5e at qwen3-8b widths, and ``chip_smoke.py`` checks both
on the chip.
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
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b


def _dot(a, b, dims, exact=False):
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


def block_ranges(q_seg, kv_seg, *, causal: bool, block_q: int,
                 block_k: int):
    """Live tiles as ranges: ``(k_lo, k_hi)``, each (b, sq // block_q), the
    first and last live key block of each (row, query block), and
    ``(q_lo, q_hi)``, each (b, sk // block_k), the first and last live query
    block of each (row, key block).  An empty range has hi < lo."""
    b, sq = q_seg.shape
    sk = kv_seg.shape[1]
    n_ids = max(sq, sk) + 1      # larger ids share the last slot: a hull
    rows = jnp.arange(b)[:, None]

    def first_last(seg):
        """Each id's first and last position in ``seg`` (n / -1: absent)."""
        n = seg.shape[1]
        ids = jnp.clip(seg, 0, n_ids - 1)
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), seg.shape)
        first = jnp.full((b, n_ids), n, jnp.int32).at[rows, ids].min(pos)
        last = jnp.full((b, n_ids), -1, jnp.int32).at[rows, ids].max(pos)
        return first, last

    def hull(seg, other, block):
        """For each block of ``seg``, the first and last position of
        ``other`` that shares an id with one of its positions."""
        first, last = first_last(other)
        ids = jnp.clip(seg, 0, n_ids - 1)
        live = seg > 0
        lo = jnp.where(live, jnp.take_along_axis(first, ids, 1),
                       other.shape[1])
        hi = jnp.where(live, jnp.take_along_axis(last, ids, 1), -1)
        nb = seg.shape[1] // block
        return (lo.reshape(b, nb, block).min(-1),
                hi.reshape(b, nb, block).max(-1))

    k_lo, k_hi = hull(q_seg, kv_seg, block_q)
    q_lo, q_hi = hull(kv_seg, q_seg, block_k)
    if causal:
        # a query block reaches no key past its last query, a key block no
        # query before its first key
        q_start = jnp.arange(sq // block_q, dtype=jnp.int32) * block_q
        k_start = jnp.arange(sk // block_k, dtype=jnp.int32) * block_k
        k_hi = jnp.minimum(k_hi, q_start + block_q - 1)
        q_lo = jnp.maximum(q_lo, k_start)
    return (k_lo // block_k, k_hi // block_k, q_lo // block_q,
            q_hi // block_q)


def live_tile_count(q_seg, kv_seg, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """The (query-block, key-block) tiles the kernel computes, over all rows
    (int32; one head's count, the same for every head)."""
    k_lo, k_hi, _, _ = block_ranges(q_seg, kv_seg, causal=causal,
                                    block_q=block_q, block_k=block_k)
    return jnp.sum(jnp.maximum(k_hi - k_lo + 1, 0))


def _mask(q_seg, k_seg, q_idx, k_idx, causal):
    m = jnp.logical_and(q_seg == k_seg, k_seg > 0)
    if causal:
        m = jnp.logical_and(m, q_idx >= k_idx)
    return m


# ------------------------------------------------------------------ kernels
def _fwd_kernel(lo_ref, hi_ref, qseg_ref, kseg_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    ib, iq = pl.program_id(0), pl.program_id(2)
    t = ib * pl.num_programs(2) + iq
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0, 0]                               # (BQ, d)
    q_seg = qseg_ref[0]                           # (BQ, 1): sublanes
    q_idx = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        start = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        s = _dot(q, k, _NT) * scale
        k_idx = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = _mask(q_seg, kseg_ref[0, :, pl.ds(start, block_k)], q_idx,
                     k_idx, causal)
        s = jnp.where(mask, s, NEG_INF)
        # m/l scratch hold each row's value in every lane; column 0 is read
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(
            p, v.astype(jnp.float32), _NN, exact=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(lo_ref[t], hi_ref[t] + 1, body, 0)
    l = jnp.maximum(l_ref[:, :1], 1e-20)
    o_ref[0, 0] = jnp.where(q_seg > 0, acc_ref[...] / l, 0.0)
    lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def _dq_kernel(lo_ref, hi_ref, qseg_ref, kseg_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, di_ref, dq_ref, acc_ref, *, scale: float,
               causal: bool, block_q: int, block_k: int):
    ib, iq = pl.program_id(0), pl.program_id(2)
    t = ib * pl.num_programs(2) + iq
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                           # (BQ, 1)
    di = di_ref[0, 0]
    q_seg = qseg_ref[0]
    q_idx = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        start = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        k_idx = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = _mask(q_seg, kseg_ref[0, :, pl.ds(start, block_k)], q_idx,
                     k_idx, causal)
        s = jnp.where(mask, _dot(q, k, _NT) * scale, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        ds = p * (_dot(do, v, _NT) - di)
        acc_ref[...] += _dot(ds, k.astype(jnp.float32), _NN, exact=True)
        return carry

    jax.lax.fori_loop(lo_ref[t], hi_ref[t] + 1, body, 0)
    dq_ref[0, 0] = acc_ref[...] * scale


def _dkv_kernel(lo_ref, hi_ref, kseg_ref, qseg_ref, k_ref, v_ref, q_ref,
                do_ref, lse_ref, di_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale: float, causal: bool, block_q: int, block_k: int):
    # transposed tiles: keys down the sublanes, queries along the lanes
    ib, ik = pl.program_id(0), pl.program_id(2)
    t = ib * pl.num_programs(2) + ik
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    k = k_ref[0, 0]                               # (BK, d)
    v = v_ref[0, 0]
    k_seg = kseg_ref[0]                           # (BK, 1)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)

    def body(qb, carry):
        start = pl.multiple_of(qb * block_q, block_q)
        q = q_ref[0, 0, pl.ds(start, block_q), :]
        do = do_ref[0, 0, pl.ds(start, block_q), :]
        lse = lse_ref[0, 0, :, pl.ds(start, block_q)]    # (1, BQ)
        di = di_ref[0, 0, :, pl.ds(start, block_q)]
        q_idx = start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        mask = _mask(qseg_ref[0, :, pl.ds(start, block_q)], k_seg, q_idx,
                     k_idx, causal)
        s = jnp.where(mask, _dot(k, q, _NT) * scale, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[...] += _dot(p, do.astype(jnp.float32), _NN, exact=True)
        ds = p * (_dot(v, do, _NT) - di)
        dk_acc[...] += _dot(ds, q.astype(jnp.float32), _NN, exact=True)
        return carry

    jax.lax.fori_loop(lo_ref[t], hi_ref[t] + 1, body, 0)
    dk_ref[0, 0] = dk_acc[...] * scale
    dv_ref[0, 0] = dv_acc[...]


# ------------------------------------------------------------------- calls
def _params(*resident):
    """Compiler parameters: room for the double-buffered resident arrays."""
    need = sum(2 * math.prod(a.shape[-2:]) * a.dtype.itemsize
               for a in resident)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=max(32 << 20, need + (16 << 20)))


def _forward(q, k, v, q_seg, kv_seg, k_lo, k_hi, *, causal, block_q,
             block_k, interpret):
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    group = h // kh
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, block_q=block_q,
                               block_k=block_k)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda ib, ih, iq, *_: (ib, iq, 0)),
            pl.BlockSpec((1, 1, sk), lambda ib, ih, iq, *_: (ib, 0, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, *_: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda ib, ih, iq, *_: (ib, ih // group, 0, 0)),
            pl.BlockSpec((1, 1, sk, d),
                         lambda ib, ih, iq, *_: (ib, ih // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, *_: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda ib, ih, iq, *_: (ib, ih, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ])
    return pl.pallas_call(
        kernel, grid_spec=spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)],
        compiler_params=_params(k, v), interpret=interpret,
        name="packed_attention_fwd",
    )(k_lo.reshape(-1), k_hi.reshape(-1), q_seg[:, :, None],
      kv_seg[:, None, :], q, k, v)


def _backward(q, k, v, q_seg, kv_seg, ranges, o, lse, do, *, causal,
              block_q, block_k, interpret):
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    group = h // kh
    k_lo, k_hi, q_lo, q_hi = ranges
    static = dict(scale=1.0 / math.sqrt(d), causal=causal, block_q=block_q,
                  block_k=block_k)
    di = jnp.sum(do.astype(jnp.float32) * o, axis=-1, keepdims=True)

    q_block = pl.BlockSpec((1, 1, block_q, d),
                           lambda ib, ih, iq, *_: (ib, ih, iq, 0))
    kv_row = pl.BlockSpec((1, 1, sk, d),
                          lambda ib, ih, iq, *_: (ib, ih // group, 0, 0))
    q_col = pl.BlockSpec((1, 1, block_q, 1),
                         lambda ib, ih, iq, *_: (ib, ih, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, 1),
                             lambda ib, ih, iq, *_: (ib, iq, 0)),
                pl.BlockSpec((1, 1, sk), lambda ib, ih, iq, *_: (ib, 0, 0)),
                q_block, kv_row, kv_row, q_block, q_col, q_col,
            ],
            out_specs=q_block,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        compiler_params=_params(k, v), interpret=interpret,
        name="packed_attention_dq",
    )(k_lo.reshape(-1), k_hi.reshape(-1), q_seg[:, :, None],
      kv_seg[:, None, :], q, k, v, do, lse, di)

    k_block = pl.BlockSpec((1, 1, block_k, d),
                           lambda ib, ih, ik, *_: (ib, ih // group, ik, 0))
    q_row = pl.BlockSpec((1, 1, sq, d), lambda ib, ih, ik, *_: (ib, ih, 0, 0))
    stat_row = pl.BlockSpec((1, 1, 1, sq),
                            lambda ib, ih, ik, *_: (ib, ih, 0, 0))
    dkv_block = pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, ik, *_: (ib, ih, ik, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, block_k, 1),
                             lambda ib, ih, ik, *_: (ib, ik, 0)),
                pl.BlockSpec((1, 1, sq), lambda ib, ih, ik, *_: (ib, 0, 0)),
                k_block, k_block, q_row, q_row, stat_row, stat_row,
            ],
            out_specs=[dkv_block, dkv_block],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)] * 2,
        compiler_params=_params(q, do), interpret=interpret,
        name="packed_attention_dkv",
    )(q_lo.reshape(-1), q_hi.reshape(-1), kv_seg[:, :, None],
      q_seg[:, None, :], k, v, q, do, lse.reshape(b, h, 1, sq),
      di.reshape(b, h, 1, sq))
    # each query head's share of its KV head's gradient
    dk = dk.reshape(b, kh, group, sk, d).sum(2)
    dv = dv.reshape(b, kh, group, sk, d).sum(2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attention(q, k, v, q_seg, kv_seg, causal, block_q, block_k, interpret):
    return _attention_fwd(q, k, v, q_seg, kv_seg, causal, block_q, block_k,
                          interpret)[0]


def _attention_fwd(q, k, v, q_seg, kv_seg, causal, block_q, block_k,
                   interpret):
    ranges = block_ranges(q_seg, kv_seg, causal=causal, block_q=block_q,
                          block_k=block_k)
    o, lse = _forward(q, k, v, q_seg, kv_seg, *ranges[:2], causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)
    return o.astype(q.dtype), (q, k, v, q_seg, kv_seg, ranges, o, lse)


def _attention_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, q_seg, kv_seg, ranges, o, lse = res
    dq, dk, dv = _backward(q, k, v, q_seg, kv_seg, ranges, o, lse, do,
                           causal=causal, block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def packed_flash_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool):
    """q: (b, h, sq, d); k, v: (b, kh, sk, d); segs: (b, s) int32, 0 =
    padding.  Returns (b, h, sq, d) in q.dtype, differentiable in q, k and
    v.  ``interpret=True`` runs the Pallas interpreter (CPU tests);
    ``False`` lowers to Mosaic for the TPU.
    """
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    if h % kh:
        raise ValueError(f"{h} query heads over {kh} kv heads")
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"lengths {sq}, {sk} not multiples of the blocks "
                         f"{block_q}, {block_k}")
    return _attention(q, k, v, jnp.asarray(q_seg, jnp.int32),
                      jnp.asarray(kv_seg, jnp.int32), causal, block_q,
                      block_k, interpret)
