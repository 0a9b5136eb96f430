"""Unified model API over all families.

``Model`` bundles the per-family functions behind one interface.  The
trainer runs ``forward``; ``prefill``, ``decode_step``, ``init_cache`` and
``cache_axes`` are reached only from tests and ``chip_smoke.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, rwkv_model, transformer
from repro.models import params as pdefs


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    defs: Any                                   # ParamDef tree
    forward: Callable                           # (params, batch) -> (logits, aux)
    prefill: Callable                           # (params, batch) -> (logits, cache)
    decode_step: Callable                       # (params, cache, tokens, pos)
    init_cache: Callable                        # (batch, max_len) -> cache
    cache_axes: Callable                        # () -> logical axes tree

    def init(self, key: jax.Array, dtype=jnp.bfloat16):
        return pdefs.init_params(self.defs, key, dtype)

    def abstract_params(self, dtype=jnp.bfloat16):
        return pdefs.abstract_params(self.defs, dtype)

    def param_count(self) -> int:
        return pdefs.param_count(self.defs)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        mod = transformer
        defs = transformer.lm_defs(cfg)
    elif cfg.family == "hybrid":
        mod = hybrid
        defs = hybrid.hybrid_defs(cfg)
    elif cfg.family == "audio":
        mod = encdec
        defs = encdec.encdec_defs(cfg)
    elif cfg.family == "ssm":
        mod = rwkv_model
        defs = rwkv_model.rwkv_defs(cfg)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    if cfg.family == "hybrid":
        forward_fn = hybrid.forward
        prefill_fn = hybrid.prefill
    elif cfg.family == "audio":
        forward_fn = encdec.forward
        prefill_fn = encdec.prefill
    elif cfg.family == "ssm":
        forward_fn = rwkv_model.forward

        def prefill_fn(params, cfg_, batch):  # type: ignore[misc]
            logits, states = rwkv_model.forward(params, cfg_, batch,
                                                return_state=True)
            return logits[:, -1:, :], states
    else:
        forward_fn = transformer.forward
        prefill_fn = transformer.prefill

    return Model(
        cfg=cfg,
        defs=defs,
        forward=lambda p, b: forward_fn(p, cfg, b),
        prefill=lambda p, b: prefill_fn(p, cfg, b),
        decode_step=lambda p, c, t, pos: mod.decode_step(p, cfg, c, t, pos),
        init_cache=lambda b, m, dtype=jnp.bfloat16: mod.init_cache(
            cfg, b, m, dtype),
        cache_axes=lambda: mod.cache_logical_axes(cfg),
    )
