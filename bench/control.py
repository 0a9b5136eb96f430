#!/usr/bin/env python3
"""Readings that set the upper end of each correctness limit.

    python3 bench/control.py --workload qwen3-8b.coyo5 --seeds 1 2 3

For each seed it builds the cell as a run does, takes the first
``warm_steps`` global batches from the program's feed, frees the program
and trains the reference on them, then puts in the program's place:

* ``int8``: the reference with every matmul in int8 (the control: the
  configuration computes in bfloat16, int8 is the next precision down);
* ``half_batch``: the reference with the second half of each batch's
  label positions left out and the mean taken over the rest.

It prints one JSON line per seed: for each variant, its numbers against
the reference beside the cell's limits (``bench/limits/<cell>.json``) and
whether they pass, by the comparison a run makes (``harness.verdict``).
A control has to come out not correct.  A state left unchanged reads 1 on
``update_gap`` and needs no run.  Not part of a benchmark run; needs the
cell's chips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def half_batch(b: dict) -> dict:
    """The batch with the second half of its label positions left out
    (on a batch of several rows, its second half of rows)."""
    out = {k: v.copy() for k, v in b.items()}
    flat = out["labels"].reshape(-1)
    flat[flat.size // 2:] = -1
    return out


def readings(c: dict, seed: int, devices) -> dict:
    import jax
    from bench import harness
    from bench.reference import family
    conf, mix = c["config"], c["traffic"]
    with harness.training(c, seed, devices) as (cfg, sd, ov, trainer):
        batches = [trainer.fetch(s) for s in range(mix["warm_steps"])]
    key = jax.random.key(sd["params"])
    opt = conf["optimizer"]

    def train(bs, precision="f32"):
        return family(conf["family"]).train(conf, opt, key, bs, precision,
                                            device=devices[0])

    ref = train(batches)
    variants = {"int8": train(batches, "int8"),
                "half_batch": train([half_batch(b) for b in batches])}
    out = {"seed": seed, "ref_losses": ref["losses"]}
    for name, v in variants.items():
        checks, correct = harness.verdict(harness.model_numbers(
            v["losses"], v["grad_norms"], v["update_norms"], ref),
            c["limits"])
        out[name] = {"correct": correct, "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    c = harness.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c["chips"]:
        print(f"control: {args.workload} needs {c['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(c, seed, devs[:c["chips"]])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
