"""Run a CPU-size cell with a fault planted in the program underneath the
harness, and print the run's result line.

    python bench/tests/plant.py FAULT

FAULT is one of:
  none         nothing planted;
  unchanged    the train step returns its state unchanged;
  half_batch   the step drops the second half of the batch's label
               positions from the loss, the mean taken over the rest;
  token        the constructor alters one token of each packed bin.
"""
from __future__ import annotations

import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]


def plant(fault: str) -> None:
    import jax
    import numpy as np
    from repro.core import constructor
    from repro.train import trainer, train_step

    real_step = train_step.make_train_step
    if fault == "unchanged":
        def make(model, opt):
            step = real_step(model, opt)
            return lambda state, batch: (state, step(state, batch)[1])
        trainer.make_train_step = make
    elif fault == "half_batch":
        def make(model, opt):
            step = real_step(model, opt)

            def half(state, batch):
                labels = batch["labels"]
                keep = jax.numpy.arange(labels.size).reshape(labels.shape) \
                    < labels.size // 2
                labels = jax.numpy.where(keep, labels, -1)
                return step(state, dict(batch, labels=labels))
            return half
        trainer.make_train_step = make
    elif fault == "token":
        real_pack = constructor.packing.pack_sequences

        def pack(*a, **kw):
            b = real_pack(*a, **kw)
            r, c = np.nonzero(b.segment_ids)
            if len(r):
                b.tokens[r[0], c[0]] = b.tokens[r[0], c[0]] % 200 + 1 \
                    if b.tokens[r[0], c[0]] != 7 else 8
            return b
        constructor.packing.pack_sequences = pack
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main(fault: str) -> None:
    import jax
    from bench import harness
    from tiny import tiny_cell
    plant(fault)
    c = tiny_cell()
    out = harness.run_cell(c, 2**31 + 977, 1.0, False, T_START,
                           jax.devices()[:c["chips"]])
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:])
