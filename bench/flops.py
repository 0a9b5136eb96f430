"""Model FLOPs of the documents a step trains on, from the configuration's
shapes and the delivered segment lengths, and the chips' peaks.

Each model family counts one document's training FLOPs in its own file
(``bench/models/<family>.py``, ``doc_flops``): 6 FLOPs per token for
every weight that multiplies it (2 forward, 4 backward), the output head
over the vocabulary slice included and the embedding lookup not, plus the
family's token-mixing term.  Padding, recomputation and work across
document boundaries do not count, so these FLOPs over a window can never
exceed what the chip computed.
"""
from __future__ import annotations

import json
import os

from bench.reference import family

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peak_flops(device_kind: str) -> float:
    """Dense bf16 peak FLOP/s of one chip of ``device_kind``."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind]["bf16_flops"])


def segment_lengths(segment_ids) -> list[int]:
    """Lengths of the documents packed into rows of segment ids (0 = pad)."""
    import numpy as np
    out = []
    for row in np.asarray(segment_ids):
        ids = row[row > 0]
        if ids.size:
            out.extend(np.bincount(ids)[1:][np.bincount(ids)[1:] > 0]
                       .tolist())
    return out


def batch_flops(conf: dict, segment_ids) -> float:
    """Training FLOPs of every document in a batch, for the benchmark's
    configuration ``conf``."""
    doc = family(conf["family"]).doc_flops
    return sum(doc(conf, n) for n in segment_lengths(segment_ids))
