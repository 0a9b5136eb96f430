"""Per-kernel shape/dtype sweeps: pallas_call (interpret) vs ref.py oracle,
and the packed-attention kernel's gradients and live tiles vs the jnp
training path."""
import functools
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.packed_attention import (
    block_ranges, live_tile_count, packed_flash_attention,
)
from repro.kernels.wkv6 import wkv6_forward
from repro.models.attention import (
    attention_tiles, chunked_segment_attention, kernel_attention,
)

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


def _packed(lens_per_row, s):
    """Rows as data/packing.py packs them: documents of the given lengths
    numbered from 1, back to back, then padding 0."""
    seg = np.zeros((len(lens_per_row), s), np.int32)
    for r, lens in enumerate(lens_per_row):
        at = 0
        for i, n in enumerate(lens):
            seg[r, at:at + n] = i + 1
            at += n
    return seg


def _short_docs(n_docs, seed):
    """~40-token documents, as the coyo5 traffic's texts."""
    return [int(x) for x in
            np.random.default_rng(seed).integers(30, 55, n_docs)]


LAYOUTS = {   # s = 512: four blocks of 128
    "short_docs": lambda: _packed([_short_docs(8, 1), _short_docs(5, 2)],
                                  512),
    "crosses_blocks": lambda: _packed([[100, 200, 150], [300, 212]], 512),
    "one_doc": lambda: _packed([[512], [512]], 512),
    "padding_row": lambda: _packed([_short_docs(6, 3), []], 512),
}


@pytest.mark.parametrize("layout,h,kh,causal,dtype", [
    ("short_docs", 32, 8, True, np.float32),     # qwen3's GQA 32/8
    ("short_docs", 32, 8, True, jnp.bfloat16),
    ("short_docs", 4, 4, True, np.float32),      # MHA
    ("crosses_blocks", 4, 2, True, np.float32),
    ("one_doc", 4, 2, True, np.float32),
    ("padding_row", 4, 2, True, np.float32),
    ("short_docs", 4, 2, False, np.float32),
    ("crosses_blocks", 4, 4, False, np.float32),
])
def test_packed_attention_matches_jnp_path(layout, h, kh, causal, dtype):
    """The kernel (model layout, interpret mode) and its gradients in q, k
    and v against the jnp training path, chunked over KV blocks."""
    seg = jnp.asarray(LAYOUTS[layout]())
    b, s = seg.shape
    d = 32
    r = np.random.default_rng(5)
    q = jnp.asarray(r.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(r.normal(size=(b, s, kh, d)), dtype)
    v = jnp.asarray(r.normal(size=(b, s, kh, d)), dtype)
    w = jnp.asarray(r.normal(size=(b, s, h, d)), np.float32)

    def run(attn):
        loss = lambda q, k, v: jnp.sum(
            attn(q, k, v, seg, seg, causal=causal).astype(jnp.float32) * w)
        out = attn(q, k, v, seg, seg, causal=causal)
        return [out] + list(jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    got = run(functools.partial(kernel_attention, interpret=True))
    exp = run(functools.partial(chunked_segment_attention, chunk=128))
    tol = TOL[dtype]
    for name, a, e in zip(("out", "dq", "dk", "dv"), got, exp):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        scale = max(np.abs(e).max(), 1.0)
        np.testing.assert_allclose(a, e, atol=tol * scale, rtol=tol,
                                   err_msg=name)
    pad = np.asarray(seg) == 0
    assert not np.asarray(got[0], np.float32)[pad].any()


def _live_tiles_brute(seg, causal, block=128):
    """(rows, nq, nk) bool: a tile is live when some query of the block
    attends to some key of the block."""
    b, s = seg.shape
    i = np.arange(s)
    m = (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] > 0)
    if causal:
        m &= i[:, None] >= i[None, :]
    n = s // block
    return m.reshape(b, n, block, n, block).any(axis=(2, 4))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("layout", sorted(LAYOUTS) + ["random"])
def test_live_tiles_match_brute_force(layout, causal):
    """The kernel's live ranges, both ways round, hold exactly the tiles a
    brute-force pass over the segment ids finds live, and the tile counter
    counts them."""
    if layout == "random":
        r = np.random.default_rng(11)
        seg = _packed([list(r.integers(1, 140, r.integers(1, 8)))
                       for _ in range(6)], 1024)
    else:
        seg = LAYOUTS[layout]()
    live = _live_tiles_brute(seg, causal)
    n = live.shape[1]
    k_lo, k_hi, q_lo, q_hi = (np.asarray(x) for x in block_ranges(
        jnp.asarray(seg), jnp.asarray(seg), causal=causal, block_q=128,
        block_k=128))
    idx = np.arange(n)
    by_q = (idx[None, None] >= k_lo[..., None]) & \
        (idx[None, None] <= k_hi[..., None])
    by_k = (idx[None, None] >= q_lo[..., None]) & \
        (idx[None, None] <= q_hi[..., None])
    np.testing.assert_array_equal(by_q, live)
    np.testing.assert_array_equal(by_k.transpose(0, 2, 1), live)
    assert int(live_tile_count(jnp.asarray(seg), jnp.asarray(seg),
                               causal=causal)) == live.sum()


def test_attention_tiles_count_every_tile_on_the_jnp_path():
    """Off the TPU the training path is jnp, which computes every tile."""
    seg = jnp.asarray(LAYOUTS["short_docs"]())
    live, total = jax.jit(attention_tiles)(seg)
    assert int(live) == int(total) == 2 * 4 * 4


def test_kernel_attention_runs_per_device_under_a_data_mesh():
    """Four virtual CPU devices, rows split over a ("data",) mesh: the
    kernel runs on each device's rows (shard_map), gathers no q, k or v,
    and its gradients match the jnp path's."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models.attention import kernel_attention, chunked_segment_attention
mesh = Mesh(np.array(jax.devices()), ("data",))
r = np.random.default_rng(0)
q = jnp.asarray(r.normal(size=(4, 256, 4, 32)), jnp.float32)
k, v = (jnp.asarray(r.normal(size=(4, 256, 2, 32)), jnp.float32)
        for _ in range(2))
seg = np.repeat(np.arange(1, 9), 32)[None].repeat(4, 0).astype(np.int32)
seg[:, 200:] = 0
def grads(attn, q, k, v, seg):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v, seg, seg) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
def on_mesh(q, k, v, seg):
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        return grads(lambda *a: kernel_attention(*a, interpret=True),
                     q, k, v, seg)
step = jax.jit(on_mesh, in_shardings=(NamedSharding(mesh, P("data")),) * 4)
text = step.lower(q, k, v, seg).compile().as_text()
assert "all-gather" not in text
got = step(q, k, v, seg)
exp = grads(chunked_segment_attention, q, k, v, seg)
print(max(float(np.abs(np.asarray(a) - np.asarray(e)).max())
          for a, e in zip(got, exp)))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run([sys.executable, "-c", code], cwd=root / "src",
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert float(proc.stdout.strip().splitlines()[-1]) < 1e-4


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
