"""Trainer: OVERLORD data plane -> jit'd train step, with unified
checkpointing (model state + data-plane state snapshot together, so a
restart resumes both consistently).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import pickle
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.orchestrator import Overlord
from repro.models.model_zoo import Model
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def data_parallel_step(model: Model, opt: AdamWConfig, mesh: Mesh):
    """The jitted train step over a 1-D ``("data",)`` mesh: batch rows are
    split over the mesh, the train state is replicated and donated (so
    params and Adam moments are not double-allocated at peak).  XLA puts
    in the gradient all-reduce; on one device it is the plain step.  The
    step is traced under the mesh, so the attention kernel runs on each
    device's own rows (``models/attention.py: kernel_attention``)."""
    step = make_train_step(model, opt)

    def on_mesh(state, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(state, batch)

    repl = NamedSharding(mesh, P())
    return jax.jit(on_mesh,
                   in_shardings=(repl, NamedSharding(mesh, P("data"))),
                   out_shardings=repl, donate_argnums=(0,))


class Trainer:
    """Single-process trainer consuming OVERLORD batches.

    Every data-fetching client's rows are concatenated in rank order into
    one global batch, which is placed over a 1-D ``("data",)`` mesh of
    ``devices`` (default: all of ``jax.devices()``): with one device per
    DP rank, each rank's rows land on its own chip.  The train state is
    replicated.  A multi-host slice would build the same global array with
    ``jax.make_array_from_process_local_data`` from per-host constructors.

    The loop writes its spans to the Overlord's telemetry, beside the data
    plane's.  Each iteration is one ``trainer.step`` span (attr ``step``)
    whose children, in order, are ``trainer.fetch`` (the
    ``overlord.get_batch`` calls nest inside), ``trainer.put`` (the
    batch's host-to-device copy), ``trainer.dispatch`` (the compiled
    step's call until it returns; the device starts the step early in
    it), ``trainer.wait`` (until the device has finished the step),
    ``trainer.readback`` (the metrics' device-to-host copy and
    ``host_tokens``) and ``overlord.step_done``; the first step also holds
    ``trainer.compile``.
    The Trainer hands ``jax.profiler.TraceAnnotation`` to the tracer as its
    mirror and runs each iteration under a ``StepTraceAnnotation``, so in
    any profile an operator takes the loop's and the plane's spans lie on
    the device ops' timeline.
    """

    def __init__(self, model: Model, overlord: Overlord,
                 cfg: TrainerConfig = TrainerConfig(), seed: int = 0,
                 devices: Optional[Sequence] = None):
        self.model = model
        self.ov = overlord
        self.telemetry = overlord.telemetry
        self.telemetry.tracer.mirror = jax.profiler.TraceAnnotation
        self.cfg = cfg
        self.mesh = Mesh(np.array(devices or jax.devices()), ("data",))
        self.batch_sharding = NamedSharding(self.mesh, P("data"))
        self.state = jax.jit(
            functools.partial(init_train_state, model),
            out_shardings=NamedSharding(self.mesh, P()))(
                jax.random.key(seed))
        self.step_fn = data_parallel_step(model, cfg.opt, self.mesh)
        self._compiled = None
        self.compile_s: Optional[float] = None
        self.history: list[dict] = []

    def fetch(self, step: int) -> dict:
        """Pull every data-fetching client's view; concatenate bucket/bin
        rows into the global batch."""
        axis = self.ov.cfg.strategy_params.get("axis", "DP")
        parts = []
        with self.telemetry.span("trainer.fetch", step=step):
            for rank in self.ov.tree.data_fetching_clients(axis):
                view = self.ov.get_batch(step, rank)
                if view["role"] != "data" or view.get("cp_rank", 0) != 0:
                    continue
                for b in view["bins"]:
                    parts.append(b)
            tokens = np.concatenate([p.tokens for p in parts], 0)
            seg = np.concatenate([p.segment_ids for p in parts], 0)
            pos = np.concatenate([p.positions for p in parts], 0)
            labels = np.concatenate([p.labels for p in parts], 0)
        if len(tokens) % self.mesh.size:
            raise ValueError(f"{len(tokens)} global rows do not split over "
                             f"{self.mesh.size} devices")
        return {"tokens": tokens, "segment_ids": seg, "positions": pos,
                "labels": labels}

    def step(self, batch: dict) -> dict:
        """One train step on a host batch; returns its metrics as floats
        and the batch's trained label tokens (``host_tokens``).
        ``step_s`` covers the host-to-device copy, the step and the
        metrics' readback; the device has finished the step within it.
        The first call compiles (timed apart as ``compile_s``)."""
        span = self.telemetry.span
        if self._compiled is None:
            t0 = time.perf_counter()
            with span("trainer.compile"):
                self._compiled = self.step_fn.lower(
                    self.state, batch).compile()
            self.compile_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        with span("trainer.put"):
            placed = jax.device_put(batch, self.batch_sharding)
        with span("trainer.dispatch"):
            self.state, metrics = self._compiled(self.state, placed)
        with span("trainer.wait"):
            jax.block_until_ready(self.state)
        with span("trainer.readback"):
            rec = {k: float(v) for k, v in jax.device_get(metrics).items()}
            rec["step_s"] = time.perf_counter() - t1
            rec["host_tokens"] = int(np.sum((batch["labels"] >= 0)
                                            & (batch["segment_ids"] > 0)))
        return rec

    def train(self, steps: Optional[int] = None) -> list[dict]:
        steps = steps or self.cfg.steps
        for step in range(steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step), \
                    self.telemetry.span("trainer.step", step=step):
                self._iteration(step)
        return self.history

    def _iteration(self, step: int) -> None:
        t0 = time.perf_counter()
        batch = self.fetch(step)
        fetch_s = time.perf_counter() - t0
        m = self.step(batch)
        rec = {"step": step, "loss": m["loss"],
               "accuracy": m["accuracy"], "grad_norm": m["grad_norm"],
               "tokens": m["tokens"], "host_tokens": m["host_tokens"],
               "fetch_s": fetch_s, "step_s": m["step_s"]}
        # live and total attention tiles of the step (models with attention)
        rec.update({k: m[k] for k in ("attn_tiles_live", "attn_tiles_total")
                    if k in m})
        self.history.append(rec)
        self.ov.step_done(step, {"loss": rec["loss"]})
        if step % self.cfg.log_every == 0:
            print(f"step {step:5d} loss {rec['loss']:8.4f} "
                  f"acc {rec['accuracy']:.3f} "
                  f"fetch {fetch_s*1e3:6.1f}ms "
                  f"step {rec['step_s']*1e3:7.1f}ms", flush=True)
        if self.cfg.ckpt_dir and step and step % self.cfg.ckpt_every == 0:
            self.save_checkpoint(step)

    # ------------------------------------------------- unified checkpoint
    def save_checkpoint(self, step: int):
        os.makedirs(self.cfg.ckpt_dir, exist_ok=True)
        flat, treedef = jax.tree.flatten(self.state)
        np.savez(os.path.join(self.cfg.ckpt_dir, f"model_{step}.npz"),
                 *[np.asarray(x) for x in flat])
        with open(os.path.join(self.cfg.ckpt_dir, f"meta_{step}.pkl"),
                  "wb") as f:
            pickle.dump({"step": step}, f)

    def load_checkpoint(self, step: int):
        data = np.load(os.path.join(self.cfg.ckpt_dir,
                                    f"model_{step}.npz"))
        flat = [data[k] for k in data.files]
        treedef = jax.tree.structure(self.state)
        leaves = jax.tree.leaves(self.state)
        self.state = jax.tree.unflatten(
            treedef, [jax.device_put(np.asarray(a, l.dtype), l.sharding)
                      for a, l in zip(flat, leaves)])
