"""The benchmark's traffic generator: source files written from a mix's
data file and the run's seed.

A mix (``bench/traffic/<mix>.json``) lists its sources by their length
distributions.  Every seed gets the same multiset of record lengths and
transform costs: both are drawn once from a fixed stream per source, and
the seed only permutes them inside consecutive blocks of
``order_block`` records and draws the token content.  So whatever prefix
of a source a window consumes, seeds differ in order and content, not in
work.  Records follow the schema of ``repro.data.sources.
materialize_source`` and are written with ``repro.data.storage``, the
program's file format.

``record_tokens`` is the plain statement of what a record decodes to, the
data-plane reference the correctness check rebuilds batches from.
"""
from __future__ import annotations

import os

import numpy as np

MODALITY_COST = {"text": 1.0, "image": 50.0, "video": 120.0, "audio": 300.0}
MAX_TEXT_TOKENS = 8192
LENGTH_STREAM = 20_260_101   # fixed: lengths and costs never follow the seed


def source_specs(mix: dict) -> list[dict]:
    """The mix's sources with every field filled in."""
    out = []
    for s in mix["sources"]:
        spec = {"modality": "text", "n_samples": 2048, "text_mu": 3.2,
                "text_sigma": 1.1, "image_mu": 5.5, "image_sigma": 0.9}
        spec.update(s)
        out.append(spec)
    return out


def _lengths(spec: dict, index: int) -> tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
    rng = np.random.default_rng([LENGTH_STREAM, index])
    n = spec["n_samples"]
    text = np.clip(rng.lognormal(spec["text_mu"], spec["text_sigma"], n),
                   1, MAX_TEXT_TOKENS).astype(np.int64)
    if spec["modality"] == "text":
        image = np.zeros(n, np.int64)
    else:
        image = np.clip(rng.lognormal(spec["image_mu"], spec["image_sigma"],
                                      n), 16, 16384).astype(np.int64)
    cost = MODALITY_COST[spec["modality"]] * (1.0 + rng.uniform(0, 0.5, n))
    return text, image, cost


def _block_order(n: int, block: int, rng) -> np.ndarray:
    order = np.arange(n)
    for a in range(0, n, block):
        order[a:a + block] = a + rng.permutation(min(block, n - a))
    return order


def source_records(spec: dict, index: int, seed: int,
                   order_block: int) -> list[dict]:
    text, image, cost = _lengths(spec, index)
    rng = np.random.default_rng([seed, index])
    order = _block_order(len(text), order_block, rng)
    text, image, cost = text[order], image[order], cost[order]
    seeds = rng.integers(0, 2**31 - 1, len(text))
    records = []
    for i in range(len(text)):
        payload_len = int(text[i]) * 4 + int(image[i]) * 12
        records.append({
            "sample_id": f"{spec['name']}/{i}",
            "text_tokens": int(text[i]),
            "image_tokens": int(image[i]),
            "modality": spec["modality"],
            "transform_cost": float(cost[i]),
            "payload": bytes([payload_len % 251]) * min(payload_len, 512),
            "seed": int(seeds[i]),
        })
    return records


def materialize(mix: dict, seed: int, root: str) -> dict[str, str]:
    """Write each source of ``mix`` for ``seed`` under ``root`` (a file
    already there is kept); returns {source name: path}."""
    from repro.data import storage
    os.makedirs(root, exist_ok=True)
    paths = {}
    for i, spec in enumerate(source_specs(mix)):
        path = os.path.join(root, f"{spec['name']}.colstore")
        if not os.path.exists(path):
            tmp = path + ".tmp"
            storage.write_source(tmp, source_records(
                spec, i, seed, mix["order_block"]))
            os.replace(tmp, path)
        paths[spec["name"]] = path
    return paths


def record_tokens(mix: dict, seed: int, vocab_size: int) -> dict:
    """{sample_id: token ids} for every record of every source: a record
    decodes to ``text_tokens`` ids drawn uniformly from [1, vocab) by
    numpy's default generator seeded with the record's ``seed``."""
    out = {}
    for i, spec in enumerate(source_specs(mix)):
        for rec in source_records(spec, i, seed, mix["order_block"]):
            out[rec["sample_id"]] = (rec["seed"], rec["text_tokens"])
    return _Decoder(out, vocab_size)


class _Decoder(dict):
    """Decodes a sample id's tokens on first use."""

    def __init__(self, meta: dict, vocab_size: int):
        super().__init__()
        self.meta = meta
        self.vocab_size = vocab_size

    def __missing__(self, sid: str) -> np.ndarray:
        seed, n = self.meta[sid]
        toks = np.random.default_rng(seed).integers(
            1, self.vocab_size, size=n, dtype=np.int32)
        self[sid] = toks
        return toks
