"""End-to-end training launcher: OVERLORD data plane + jitted train step.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 100 --strategy hybrid_balance

Everything runs in one process: the OVERLORD actors are threads beside
the trainer, and the train step is placed over every local device (see
``Trainer``).  ``run(args)`` is the body of the command; ``chip_smoke.py``
drives the same function on the TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import tempfile

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import (
    ClientPlaceTree, CurriculumSchedule, Overlord, OverlordConfig,
    StaticSchedule,
)
from repro.data.cost_models import backbone_cost, encoder_cost
from repro.data.sources import coyo_like_specs, materialize_group
from repro.launch.cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--reduced", action="store_true",
                      help="tiny widths and depth for CPU tests "
                           "(reduced() in the arch's config module)")
    size.add_argument("--chip-share", action="store_true",
                      help="published widths, cut in depth and vocabulary "
                           "to one chip's share (chip_share() in the "
                           "arch's config module)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--strategy", default="backbone_balance",
                    choices=["vanilla", "backbone_balance",
                             "hybrid_balance"])
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--n-bins", type=int, default=1)
    ap.add_argument("--sources", type=int, default=4)
    ap.add_argument("--curriculum", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    return ap


def model_config(args) -> ModelConfig:
    if args.reduced or args.chip_share:
        mod = importlib.import_module(
            "repro.configs." + args.arch.replace("-", "_"))
        return mod.reduced() if args.reduced else mod.chip_share()
    return get_config(args.arch)


def trainer_config(args) -> TrainerConfig:
    return TrainerConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        opt=AdamWConfig(peak_lr=args.lr, warmup_steps=10,
                        total_steps=max(args.steps, 20)))


@contextlib.contextmanager
def overlord_for(args, cfg: ModelConfig):
    """A started Overlord over ``args.sources`` coyo-like sources,
    materialised from their fixed seed into a directory removed on exit."""
    with tempfile.TemporaryDirectory(prefix="overlord_train_") as root:
        specs = coyo_like_specs(args.sources)
        paths = materialize_group(specs, root)
        names = [s.name for s in specs]
        if args.curriculum:
            sched = CurriculumSchedule(
                easy={names[0]: 1.0},
                hard={n: 1.0 for n in names[1:]},
                ramp_steps=max(args.steps // 2, 1))
        else:
            sched = StaticSchedule({n: 1.0 for n in names})

        sparams = {"broadcast": ("TP",) if args.tp > 1 else ()}
        if args.strategy == "hybrid_balance":
            sparams.update(backbone_costfn=backbone_cost(cfg),
                           encoder_costfn=encoder_cost(48, 1664))
        else:
            sparams.update(costfn=backbone_cost(cfg))

        tree = ClientPlaceTree([("PP", 1), ("DP", args.dp), ("CP", 1),
                                ("TP", args.tp)])
        ov = Overlord(paths, tree, sched, OverlordConfig(
            seq_len=args.seq_len, rows_per_microbatch=args.rows,
            n_bins=args.n_bins, strategy=args.strategy,
            strategy_params=sparams, vocab_size=cfg.vocab_size,
        )).start()
        try:
            yield ov
        finally:
            ov.shutdown()


def run(args) -> dict:
    """Train ``args.steps`` steps; returns the config, the per-step
    history and the step's compile seconds."""
    enable_compile_cache()
    cfg = model_config(args)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={model.param_count():,}")
    with overlord_for(args, cfg) as ov:
        trainer = Trainer(model, ov, trainer_config(args))
        hist = trainer.train()
        print(f"final loss {hist[-1]['loss']:.4f} "
              f"(first {hist[0]['loss']:.4f})")
        print("memory:", {k: f"{v / 1e6:.1f}MB"
                          for k, v in ov.memory_report().items()})
    return {"config": cfg, "history": hist, "compile_s": trainer.compile_s}


def main(argv=None):
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
