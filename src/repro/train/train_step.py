"""Loss + train/serve step factories (pjit-ready, shape-polymorphic)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as pdefs
from repro.models.attention import attention_tiles
from repro.models.model_zoo import Model
from repro.train.optimizer import (
    AdamWConfig, AdamWState, adamw_update, init_adamw,
)

AUX_LOSS_WEIGHT = 0.01


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def init_train_state(model: Model, key: jax.Array) -> TrainState:
    # master weights fp32; compute casts to bf16 (see _cast_for_compute)
    params = model.init(key, jnp.float32)
    return TrainState(params=params, opt=init_adamw(params))


def abstract_train_state(model: Model) -> TrainState:
    params = model.abstract_params(jnp.float32)
    zeros = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    opt = AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32), mu=zeros,
                     nu=jax.tree.map(lambda z: z, zeros))
    return TrainState(params=params, opt=opt)


def _cast_for_compute(params, compute_dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda p: p.astype(compute_dtype)
        if p.dtype == jnp.float32 and p.ndim > 1 else p, params)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mean masked token xent (fp32) + accuracy."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom
    return jnp.sum(nll) / denom, acc


def make_loss_fn(model: Model):
    def loss_fn(params, batch):
        cparams = _cast_for_compute(params)
        logits, aux = model.forward(cparams, batch)
        labels = batch["labels"]
        with jax.named_scope("loss"):
            mask = ((labels >= 0) & (batch["segment_ids"] > 0)).astype(
                jnp.float32)
            loss, acc = cross_entropy(logits, labels, mask)
            total = loss + AUX_LOSS_WEIGHT * aux
        metrics = {"loss": loss, "aux_loss": aux, "accuracy": acc,
                   "tokens": jnp.sum(mask)}
        if model.cfg.family != "ssm":      # the ssm family has no attention
            metrics["attn_tiles_live"], metrics["attn_tiles_total"] = \
                attention_tiles(batch["segment_ids"])
        return total, metrics
    return loss_fn


def make_train_step(model: Model, opt_cfg: AdamWConfig = AdamWConfig()):
    loss_fn = make_loss_fn(model)

    def train_step(state: TrainState, batch):
        (total, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics, total_loss=total, **opt_metrics)
        return TrainState(new_params, new_opt), metrics

    return train_step


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        return model.prefill(_cast_for_compute(params), batch)
    return prefill_step


def make_decode_step(model: Model):
    def decode_step(params, cache, tokens, pos):
        return model.decode_step(_cast_for_compute(params), cache, tokens,
                                 pos)
    return decode_step
