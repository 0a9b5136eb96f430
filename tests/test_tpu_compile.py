"""Compiles for a described TPU v5e, with no chip attached.

The Pallas kernels at the widths ``chip_smoke.py`` runs them must lower to
Mosaic (``tpu_custom_call``; packed attention forward and backward), and
the donated qwen3-8b ``chip_share()`` train step must carry the attention
kernel and fit one v5e's 16 GiB; the 4-device data-parallel step must
compile with its gradient all-reduce and run the kernel on each device's
own rows, gathering no q, k or v.  Nothing runs: these catch what the
chip's compiler would refuse, at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable written to the persistent cache cannot be read
        # back without a chip, so keep the cache out of these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _kernel_args(name, sharding):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=sharding)
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if name == "packed_attention":    # qwen3-8b: 32 q / 8 kv heads x 128
        from repro.kernels.packed_attention import packed_flash_attention

        def fwd_bwd(q, k, v, q_seg, kv_seg, interpret):
            def loss(q, k, v):
                return jnp.sum(packed_flash_attention(
                    q, k, v, q_seg, kv_seg, interpret=interpret
                ).astype(jnp.float32))
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return fwd_bwd, [
            sds((1, 32, 4096, 128), bf16), sds((1, 8, 4096, 128), bf16),
            sds((1, 8, 4096, 128), bf16), sds((1, 4096), i32),
            sds((1, 4096), i32)]
    if name == "wkv6":                # rwkv6-3b: 40 heads x 64
        from repro.kernels.wkv6 import wkv6_forward
        return wkv6_forward, [sds((1, 40, 4096, 64), f32)] * 4 + [
            sds((40, 64), f32), sds((1, 4096), jnp.bool_)]
    from repro.kernels.flash_decode import flash_decode  # 32k cache
    return flash_decode, [
        sds((8, 32, 128), bf16), sds((8, 8, 32768, 128), bf16),
        sds((8, 8, 32768, 128), bf16), sds((8,), i32)]


@pytest.mark.parametrize("name", ["packed_attention", "wkv6",
                                  "flash_decode"])
def test_kernel_lowers_to_mosaic(topo, name):
    kernel, args = _kernel_args(name, SingleDeviceSharding(topo.devices[0]))
    exe = jax.jit(functools.partial(kernel, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in exe.as_text()


def _compile_chip_share_step(devices, rows, seq_len):
    from repro.configs.qwen3_8b import chip_share
    from repro.models.model_zoo import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import abstract_train_state
    from repro.train.trainer import data_parallel_step
    model = build_model(chip_share())
    mesh = Mesh(np.array(devices), ("data",))
    batch = {k: jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)
             for k in ("tokens", "segment_ids", "positions", "labels")}
    return data_parallel_step(model, AdamWConfig(), mesh).lower(
        abstract_train_state(model), batch).compile()


def _peak_bytes(exe):
    m = exe.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_chip_share_train_step_fits_one_v5e(topo):
    """The smoke run's step: 2 DP rows x 4096 tokens on one chip, its
    attention in the Pallas kernel."""
    exe = _compile_chip_share_step(topo.devices[:1], 2, 4096)
    assert exe.memory_analysis().alias_size_in_bytes > 0   # state donated
    assert "tpu_custom_call" in exe.as_text()
    assert _peak_bytes(exe) < 0.9 * V5E_HBM_BYTES


def test_data_parallel_step_compiles_for_four_chips(topo):
    """The 4-chip check's step: 4 DP rows x 2048 tokens, one per chip; the
    kernel runs on each chip's own row, so nothing is gathered."""
    exe = _compile_chip_share_step(topo.devices[:4], 4, 2048)
    text = exe.as_text()
    assert "all-reduce" in text
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    assert _peak_bytes(exe) < 0.9 * V5E_HBM_BYTES
