"""Minimal pure-JAX parameter system.

Models declare a tree of :class:`ParamDef` (shape + logical axis names +
initializer).  From one definition tree we derive:

  * materialized parameters  (``init_params``)
  * logical PartitionSpecs    (``logical_specs``)  -> sharding/logical.py
  * abstract shapes           (``abstract_params``) for compile-only checks

Keeping a single source of truth prevents params/spec drift, which is the
usual failure mode of hand-written sharding tables.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis names used across the model zoo.  sharding/logical.py maps
# these onto physical mesh axes ("pod", "data", "model").
EMBED = "embed"          # d_model           -> fsdp (data)
MLP = "mlp"              # d_ff              -> tensor (model)
HEADS = "heads"          # query heads       -> tensor (model)
KV_HEADS = "kv_heads"    # kv heads          -> replicated (GQA small)
HEAD_DIM = "head_dim"    # per-head dim      -> replicated
VOCAB = "vocab"          # vocabulary        -> tensor (model)
EXPERT = "expert"        # MoE experts       -> tensor (model) == EP
LAYERS = "layers"        # scan-stacked dim  -> replicated
SSM_STATE = "ssm_state"  # mamba2 state dim  -> replicated
SSM_INNER = "ssm_inner"  # mamba2 inner dim  -> tensor (model)
RWKV_HEADS = "rwkv_heads"  # wkv heads       -> tensor (model)
LORA = "lora"            # small lora dims   -> replicated
CONV = "conv"            # conv kernel taps  -> replicated
FRAMES = "frames"        # audio frames      -> replicated


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative description of one parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"        # normal | zeros | ones | scaled | uniform
    scale: float | None = None  # stddev override for "normal"/"scaled"
    dtype: Any = None           # override container dtype (e.g. fp32 norms)

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"shape {self.shape} and axes {self.axes} rank mismatch")


def _fan_in(shape: tuple[int, ...]) -> int:
    # Heuristic: all-but-last dims are fan-in for projection matrices.
    if len(shape) == 1:
        return shape[0]
    return int(np.prod(shape[:-1]))


def init_param(defn: ParamDef, key: jax.Array, dtype) -> jax.Array:
    dt = defn.dtype or dtype
    if defn.init == "zeros":
        return jnp.zeros(defn.shape, dt)
    if defn.init == "ones":
        return jnp.ones(defn.shape, dt)
    if defn.init == "uniform":
        lim = defn.scale or 1.0
        return jax.random.uniform(key, defn.shape, dt, -lim, lim)
    if defn.init == "scaled":  # 1/sqrt(fan_in) normal
        std = (defn.scale or 1.0) / math.sqrt(max(_fan_in(defn.shape), 1))
        return (jax.random.normal(key, defn.shape, jnp.float32) * std).astype(dt)
    # default truncated-normal-ish
    std = defn.scale if defn.scale is not None else 0.02
    return (jax.random.normal(key, defn.shape, jnp.float32) * std).astype(dt)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(defs, key: jax.Array, dtype=jnp.bfloat16):
    """Materialize a ParamDef tree into concrete arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [init_param(d, k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree (no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype or dtype),
        defs, is_leaf=is_def)


def logical_specs(defs):
    """Tree of logical-axis tuples, mirroring the params tree."""
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=is_def)


def stacked(defs, n: int):
    """Add a leading scan ("layers") dim to every ParamDef in the tree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, (LAYERS,) + d.axes, d.init,
                           d.scale, d.dtype),
        defs, is_leaf=is_def)


def param_count(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(int(np.prod(d.shape)) for d in leaves)


def param_bytes(defs, dtype=jnp.bfloat16) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(
        int(np.prod(d.shape)) * jnp.dtype(d.dtype or dtype).itemsize
        for d in leaves)
