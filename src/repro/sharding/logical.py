"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Models annotate params and activations with *logical* axis names
(models/params.py).  A :class:`ShardingRules` maps logical names onto mesh
axes.  Resolution is shape-aware: a mapping is dropped (replicated) when the
dim is not divisible by the mesh-axis product — this is what lets one rule
table serve every assigned architecture (e.g. 24 attention heads or 40
experts cannot shard 16-way; they fall back to replication instead of
failing to lower).  Dropped mappings are recorded for the roofline report.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
from typing import Any, Iterable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import params as pdefs

AxisMap = dict[str, tuple[str, ...]]

# --- rule tables --------------------------------------------------------
# fsdp := ("pod", "data"); tensor := ("model",).  Axes absent from the
# active mesh are silently skipped at resolution time, so the same table
# works for the single-pod (data, model) and multi-pod (pod, data, model)
# production meshes as well as 1-device CPU test meshes.

_COMMON: AxisMap = {
    # params
    pdefs.EMBED: ("pod", "data"),
    pdefs.MLP: ("model",),
    pdefs.HEADS: ("model",),
    pdefs.KV_HEADS: (),            # GQA kv heads: replicated
    pdefs.HEAD_DIM: (),
    pdefs.VOCAB: ("model",),
    pdefs.EXPERT: ("model",),      # expert parallelism on the tensor axis
    pdefs.LAYERS: (),
    pdefs.SSM_STATE: (),
    pdefs.SSM_INNER: ("model",),
    pdefs.RWKV_HEADS: ("model",),
    pdefs.LORA: (),
    pdefs.CONV: (),
    pdefs.FRAMES: (),
    # activations
    "batch": ("pod", "data"),
    "seq": (),
    "act_embed": (),
    "act_heads": ("model",),
    "act_kv_heads": (),
    "act_mlp": ("model",),
    "act_vocab": ("model",),
    "act_expert": ("model",),
    "act_ssm": ("model",),
    "kv_seq": (),
    "cap": (),
}

TRAIN_RULES: AxisMap = dict(_COMMON)

# Decode: KV cache sequence dim is sharded over the tensor axis
# ("KV-sequence-parallel flash-decode", DESIGN.md §5); query heads stay
# replicated for the single-token step.
DECODE_RULES: AxisMap = dict(_COMMON)
DECODE_RULES.update({
    "kv_seq": ("model",),
    "act_heads": (),
})


@dataclasses.dataclass
class ShardingRules:
    mesh: Optional[Mesh]
    mapping: AxisMap
    # (logical axis, dim, axes) combos that fell back to replication:
    dropped: list[tuple[str, int, tuple[str, ...]]] = dataclasses.field(
        default_factory=list)

    def resolve_axis(self, logical: Optional[str], dim: int,
                     used: set[str]) -> Optional[tuple[str, ...]]:
        """Resolve one logical axis for a dim of the given size."""
        if logical is None or self.mesh is None:
            return None
        axes = self.mapping.get(logical, ())
        axes = tuple(a for a in axes if a in self.mesh.axis_names
                     and a not in used)
        if not axes:
            return None
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        if size <= 1:
            return None
        if dim % size != 0:
            # try progressively shorter prefixes before replicating
            for cut in range(len(axes) - 1, 0, -1):
                sub = axes[:cut]
                s = 1
                for a in sub:
                    s *= self.mesh.shape[a]
                if s > 1 and dim % s == 0:
                    self.dropped.append((logical, dim, axes[cut:]))
                    return sub
            self.dropped.append((logical, dim, axes))
            return None
        return axes

    def spec(self, axes: Iterable[Optional[str]],
             shape: tuple[int, ...]) -> P:
        used: set[str] = set()
        out = []
        for logical, dim in zip(axes, shape):
            r = self.resolve_axis(logical, dim, used)
            if r is None:
                out.append(None)
            else:
                used.update(r)
                # multi-axis mappings keep tuple form even when only one
                # mesh axis survives filtering (e.g. embed -> ("pod",
                # "data") on a pod-less mesh resolves to ("data",)), so a
                # spec records whether the rule was compound
                multi = len(self.mapping.get(logical, ())) > 1
                out.append(r if (len(r) > 1 or multi) else r[0])
        return P(*out)


_current: contextvars.ContextVar[Optional[ShardingRules]] = \
    contextvars.ContextVar("sharding_rules", default=None)


def current_rules() -> Optional[ShardingRules]:
    return _current.get()


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh], mapping: AxisMap = TRAIN_RULES):
    rules = ShardingRules(mesh, mapping) if mesh is not None else None
    token = _current.set(rules)
    try:
        yield rules
    finally:
        _current.reset(token)


def spec_for(axes: Iterable[Optional[str]], shape: tuple[int, ...]) -> P:
    rules = current_rules()
    if rules is None:
        return P()
    return rules.spec(axes, shape)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint to an activation (no-op outside
    a ``use_rules`` context, so tests on 1 device never see constraints)."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard(): {len(axes)} axes for rank-{x.ndim} array")
    spec = rules.spec(axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def param_shardings(defs, mesh: Mesh, mapping: AxisMap = TRAIN_RULES):
    """NamedSharding tree for a ParamDef tree (used for in_shardings)."""
    rules = ShardingRules(mesh, mapping)

    def one(d: pdefs.ParamDef):
        return NamedSharding(mesh, rules.spec(d.axes, d.shape))

    return jax.tree.map(one, defs, is_leaf=pdefs.is_def), rules
