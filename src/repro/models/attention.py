"""Attention: segment-aware (packed) online-softmax attention.

Entry points:
  * ``segment_attention``         — the training/prefill path.  On a TPU,
                                    for self-attention over lengths that are
                                    a multiple of the kernel's block, it runs
                                    the tile-skipping Pallas kernel
                                    (kernels/packed_attention.py, forward and
                                    backward); every other call runs
                                    ``chunked_segment_attention``.
  * ``chunked_segment_attention`` — chunked online-softmax (flash-style) over
                                    KV blocks in jnp: the fallback, and the
                                    reference the kernel is tested against.
  * ``full_segment_attention``    — unchunked oracle (tests / tiny configs).
  * ``attention_tiles``           — the (query-block, key-block) tiles the
                                    chosen path computes, live and in all.
  * ``decode_attention``          — one-token step against a (possibly
                                    sequence-sharded) KV cache.

Packing semantics: segment id 0 marks padding; q attends to k iff
``seg_q == seg_k != 0`` and (causal) buffer index ``k <= q``.  This is
exactly the workload the OVERLORD planner balances: per-microbatch FLOPs
are proportional to sum(l_i^2) over packed segments, which is what the
kernel computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import packed_attention as pk

NEG_INF = -1e30


def expand_kv(x: jax.Array, num_heads: int) -> jax.Array:
    """(b, s, kh, d) -> (b, s, h, d) by repeating each kv head h/kh times.

    The jnp path expands GQA KV heads explicitly (XLA fuses the broadcast
    into the dot); the Pallas kernel path handles GQA without expansion.
    """
    kh = x.shape[2]
    if kh == num_heads:
        return x
    assert num_heads % kh == 0, (num_heads, kh)
    return jnp.repeat(x, num_heads // kh, axis=2)


def _mask(q_seg, k_seg, q_idx, k_idx, causal):
    m = (q_seg[:, None, :, None] == k_seg[:, None, None, :]) \
        & (k_seg[:, None, None, :] > 0)
    if causal:
        m = m & (q_idx[None, None, :, None] >= k_idx[None, None, None, :])
    return m


def full_segment_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                           q_offset: int = 0):
    """Unchunked oracle.  q: (b,sq,h,d); k,v: (b,sk,h,d)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_idx = jnp.arange(sq) + q_offset
    k_idx = jnp.arange(sk)
    mask = _mask(q_seg, kv_seg, q_idx, k_idx, causal)
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-20), v)
    valid = (q_seg > 0)[:, :, None, None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)


# The kernel keeps a row's K/V (and, backward, Q/dO) of one head resident in
# VMEM; longer rows take the jnp path.
KERNEL_MAX_LEN = 16384


def _kernel_takes(sq: int, sk: int, q_offset: int) -> bool:
    """Whether a call's shapes suit the kernel: self-attention over whole
    rows whose length is a multiple of its block."""
    return (sq == sk and q_offset == 0 and sq % pk.DEFAULT_BLOCK_Q == 0
            and sq <= KERNEL_MAX_LEN)


def kernel_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                     interpret: bool):
    """The Pallas kernel in the models' layout: q (b, s, h, d); k, v
    (b, s, kh, d).  Under a mesh with a ``data`` axis (the trainer's, see
    ``train/trainer.py: data_parallel_step``) each device runs it on its own
    rows, so no device gathers another's q, k or v."""
    def run(q, k, v, q_seg, kv_seg):
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(pk.packed_flash_attention(
            t(q), t(k), t(v), q_seg, kv_seg, causal=causal,
            interpret=interpret))

    mesh = jax.sharding.get_abstract_mesh()
    n = dict(mesh.shape).get("data", 1) if not mesh.empty else 1
    if n > 1 and q.shape[0] % n == 0:
        # check_vma off: pallas_call's outputs carry no varying-axes type
        run = jax.shard_map(run, mesh=mesh, in_specs=(P("data"),) * 5,
                            out_specs=P("data"), check_vma=False)
    return run(q, k, v, q_seg, kv_seg)


def segment_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                      chunk: int = 1024, q_offset: int = 0):
    """Packed attention.  q: (b, sq, h, d); k, v: (b, sk, kh, d), GQA kv
    heads unexpanded.  The Pallas kernel where the lowering platform is a TPU
    and ``_kernel_takes`` the shapes; ``chunked_segment_attention`` (KV
    chunks of ``chunk``) everywhere else."""
    jnp_path = functools.partial(chunked_segment_attention, causal=causal,
                                 chunk=chunk, q_offset=q_offset)
    if not _kernel_takes(q.shape[1], k.shape[1], q_offset):
        return jnp_path(q, k, v, q_seg, kv_seg)
    return jax.lax.platform_dependent(
        q, k, v, q_seg, kv_seg,
        tpu=functools.partial(kernel_attention, causal=causal,
                              interpret=False),
        default=jnp_path)


def attention_tiles(segment_ids):
    """(live, total) (query-block, key-block) tiles of the causal
    self-attention over a batch's packed rows (b, s), per head and layer, on
    the path ``segment_attention`` takes: the kernel's live ranges, or every
    tile on the jnp path.  int32 scalars."""
    b, s = segment_ids.shape
    n = -(-s // pk.DEFAULT_BLOCK_Q)
    total = jnp.int32(b * n * n)
    if not _kernel_takes(s, s, 0):
        return total, total
    live = jax.lax.platform_dependent(
        segment_ids,
        tpu=lambda seg: pk.live_tile_count(seg, seg, causal=True),
        default=lambda seg: total)
    return live, total


def chunked_segment_attention(q, k, v, q_seg, kv_seg, *,
                              causal: bool = True, chunk: int = 1024,
                              q_offset: int = 0):
    """Chunked online-softmax attention over KV blocks, in jnp.
    q: (b, sq, h, d); k, v: (b, sk, kh, d).

    Flash-attention memory profile: per-step logits are (b, h, sq, chunk);
    the scan body is rematerialized in the backward pass.  Every tile is
    computed, live or not.
    """
    b, sq, h, d = q.shape
    k = expand_kv(k, h)
    v = expand_kv(v, h)
    sk = k.shape[1]
    chunk = min(chunk, sk)
    if sk % chunk != 0:
        pad = (-sk) % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad)))  # seg 0 == masked
        sk += pad
    n_chunks = sk // chunk
    if n_chunks == 1:
        return full_segment_attention(q, k, v, q_seg, kv_seg, causal=causal,
                                      q_offset=q_offset)

    scale = d ** -0.5
    q_idx = jnp.arange(sq) + q_offset

    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    segc = kv_seg.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    starts = jnp.arange(n_chunks) * chunk

    init = (
        jnp.full((b, h, sq), NEG_INF, jnp.float32),       # running max
        jnp.zeros((b, h, sq), jnp.float32),               # running denom
        jnp.zeros((b, h, sq, d), jnp.float32),            # running numer
    )

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, seg_blk, k0 = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                            preferred_element_type=jnp.float32) * scale
        k_idx = k0 + jnp.arange(chunk)
        mask = _mask(q_seg, seg_blk, q_idx, k_idx, causal)
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, -1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l = l * corr + jnp.sum(p, -1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, segc, starts))
    out = acc / jnp.maximum(l, 1e-20)[..., None]          # (b,h,sq,d)
    out = out.transpose(0, 2, 1, 3)                       # (b,sq,h,d)
    valid = (q_seg > 0)[:, :, None, None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len):
    """One-step decode.  q: (b,1,h,d); caches: (b,S,kh,d) (S may be sharded
    over the tensor axis — the stable-softmax reductions then lower to
    small all-reduces, i.e. KV-sequence-parallel flash-decode).
    cache_len: (b,) number of valid cache positions per sequence.
    """
    b, _, h, d = q.shape
    S = k_cache.shape[1]
    k = expand_kv(k_cache, h)
    v = expand_kv(v_cache, h)
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(S)[None, :] < cache_len[:, None])[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-20), v)
    return out.astype(q.dtype)
