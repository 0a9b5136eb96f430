"""Jit'd public wrappers for the Pallas kernels.

``interpret`` has no default: ``True`` runs the Pallas interpreter (the
CPU tests), ``False`` lowers the same BlockSpecs to Mosaic on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.packed_attention import packed_flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "interpret", "block_q",
                                             "block_k"))
def packed_attention(q, k, v, q_seg, kv_seg, *, causal: bool = True,
                     use_pallas: bool = True, interpret: bool,
                     block_q: int = 128, block_k: int = 128):
    """Layout: q (b, h, sq, d); k/v (b, kh, sk, d); segs (b, s)."""
    if not use_pallas:
        return ref.packed_attention_ref(q, k, v, q_seg, kv_seg,
                                        causal=causal)
    return packed_flash_attention(q, k, v, q_seg, kv_seg, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_k"))
def decode_attention(q, k_cache, v_cache, cache_len, *,
                     use_pallas: bool = True, interpret: bool,
                     block_k: int = 256):
    """Layout: q (b, h, d); caches (b, kh, S, d); cache_len (b,)."""
    if not use_pallas:
        return ref.flash_decode_ref(q, k_cache, v_cache, cache_len)
    return flash_decode(q, k_cache, v_cache, cache_len, block_k=block_k,
                        interpret=interpret)
