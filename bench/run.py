#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 bench/run.py --workload qwen3-8b.coyo5 --seed 7 --seconds 30 \
        --trace 0

The cell, its configuration, traffic mix, metrics and limits come from
BENCHMARK.json and the files it names under bench/.  The run needs a TPU
with at least the cell's chips; anywhere else it exits non-zero and
prints no result.  Its last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number the correctness check compared, with its limit.  The same numbers
close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    c = harness.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c["chips"]:
        print(f"bench: {args.workload} needs {c['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run_cell(c, args.seed, args.seconds, bool(args.trace),
                           T_START, devs[:c["chips"]])
    for name, v in out["checks"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
