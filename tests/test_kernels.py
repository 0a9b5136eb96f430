"""Per-kernel shape/dtype sweeps: pallas_call (interpret) vs ref.py oracle."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.packed_attention import packed_flash_attention
from repro.kernels.wkv6 import wkv6_forward

rng = np.random.default_rng(7)


def _segs(b, s):
    out = np.zeros((b, s), np.int32)
    for i in range(b):
        pos, sid = 0, 1
        while pos < s:
            ln = int(rng.integers(4, max(s // 2, 5)))
            out[i, pos:pos + ln] = sid
            pos += ln
            sid += 1
        if rng.random() < 0.5:
            out[i, -int(rng.integers(1, s // 4 + 1)):] = 0
    return out


TOL = {np.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,h,kh,s,d", [
    (2, 4, 2, 256, 64),
    (1, 8, 1, 128, 32),    # MQA
    (2, 2, 2, 384, 128),   # MHA, non-pow2 block count
    (1, 6, 3, 128, 80),    # odd head_dim (qwen3-32b style)
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_packed_attention_sweep(b, h, kh, s, d, dtype, causal):
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kh, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, kh, s, d)), dtype)
    seg = _segs(b, s)
    out = packed_flash_attention(q, k, v, seg, seg, causal=causal,
                                 block_q=128, block_k=128, interpret=True)
    exp = ref.packed_attention_ref(q, k, v, seg, seg, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_packed_attention_blocks_cross_segment_leakage():
    """Zeroing one segment's V must not change another segment's output."""
    b, h, s, d = 1, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = np.asarray(rng.normal(size=(b, h, s, d)), np.float32)
    seg = np.ones((b, s), np.int32)
    seg[:, 64:] = 2
    out1 = packed_flash_attention(q, k, jnp.asarray(v), seg, seg,
                                  interpret=True)
    v2 = v.copy()
    v2[:, :, 64:, :] = 0.0  # nuke segment 2's values
    out2 = packed_flash_attention(q, k, jnp.asarray(v2), seg, seg,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out1)[:, :, :64],
                               np.asarray(out2)[:, :, :64], atol=1e-6)


@pytest.mark.parametrize("b,h,kh,S,d,blk", [
    (2, 8, 2, 512, 64, 256),
    (4, 4, 4, 256, 32, 64),
    (1, 16, 2, 1024, 128, 256),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_decode_sweep(b, h, kh, S, d, blk, dtype):
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, kh, S, d)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, kh, S, d)), dtype)
    clen = rng.integers(1, S, size=(b,)).astype(np.int32)
    out = flash_decode(q, kc, vc, clen, block_k=blk, interpret=True)
    exp = ref.flash_decode_ref(q, kc, vc, clen)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("b,h,s,dk,chunk", [
    (2, 3, 128, 32, 32),
    (1, 2, 192, 64, 64),
    (2, 2, 64, 16, 16),
])
def test_wkv6_sweep(b, h, s, dk, chunk):
    r = rng.normal(size=(b, h, s, dk)).astype(np.float32) * 0.5
    k = rng.normal(size=(b, h, s, dk)).astype(np.float32) * 0.5
    v = rng.normal(size=(b, h, s, dk)).astype(np.float32) * 0.5
    loga = -np.exp(rng.normal(size=(b, h, s, dk)).astype(np.float32) * 0.5)
    u = rng.normal(size=(h, dk)).astype(np.float32) * 0.5
    reset = np.zeros((b, s), bool)
    reset[:, 0] = True
    reset[0, s // 3] = True          # mid-chunk reset (regression: fp32
    reset[-1, s // 2 + 3] = True     # cancellation with -1e30 penalties)
    out = wkv6_forward(r, k, v, loga, u, reset, chunk=chunk,
                       interpret=True)
    tr = lambda a: np.transpose(a, (0, 2, 1, 3))
    exp = ref.wkv6_ref(tr(r), tr(k), tr(v), tr(loga), u, reset)
    np.testing.assert_allclose(
        np.asarray(out), np.transpose(np.asarray(exp), (0, 2, 1, 3)),
        atol=5e-5, rtol=5e-4)
