"""Kernel-level evidence that packing layout changes attention cost:
the segment-aware kernel skips dead (Q, KV) tiles, so one 512-token doc
costs ~10 live causal tiles while 4x128-token docs cost only the 4
diagonal tiles.  The kernel runs compiled for the TPU (``interpret=False``)
and only there: off the chip this section fails instead of timing the
Pallas interpreter.  Live-tile counts are computed from the segment ids.
"""
from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels.packed_attention import packed_flash_attention


def live_tiles(seg, block=128, causal=True):
    s = len(seg)
    n = s // block
    live = 0
    for iq in range(n):
        for ik in range(n):
            if causal and ik > iq:
                continue
            qs = seg[iq * block:(iq + 1) * block]
            ks = seg[ik * block:(ik + 1) * block]
            if qs.max() >= ks.min() and ks.max() >= qs.min() \
                    and qs.max() > 0 and ks.max() > 0:
                live += 1
    return live


def run():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"kernel_bench needs a TPU, found {dev.platform}")
    attn = jax.jit(functools.partial(packed_flash_attention,
                                     interpret=False))
    b, h, s, d = 1, 2, 1024, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    layouts = {
        "one_1024tok_doc": np.ones((b, s), np.int32),
        "eight_128tok_docs": np.repeat(
            np.arange(1, 9, dtype=np.int32), 128)[None].repeat(b, 0),
    }
    for name, seg in layouts.items():
        ids = jnp.asarray(seg)
        attn(q, q, q, ids, ids).block_until_ready()  # compile + warm up
        t0 = time.perf_counter()
        attn(q, q, q, ids, ids).block_until_ready()
        dt = time.perf_counter() - t0
        lt = live_tiles(seg[0])
        total_tiles = (s // 128) * (s // 128 + 1) // 2
        emit(f"kernel.segment_skip.{name}", dt * 1e6,
             f"device={dev.device_kind};"
             f"live_tiles={lt}/{total_tiles};cost_model_sum_l2="
             f"{sum(int((seg[0] == i).sum()) ** 2 for i in range(1, seg.max() + 1))}")


if __name__ == "__main__":
    run()
