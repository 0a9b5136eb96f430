"""Plain references for the correctness check.  Nothing here or under
``bench/models/`` imports the program under test.

* ``expected_rows``: the global batch a step should deliver, rebuilt from
  the sample ids the planner chose and the records' own decoding
  (``sources.record_tokens``): each row is its documents' tokens end to
  end, segment ids 1..k, positions restarting at 0, next-token labels
  inside a document and -1 elsewhere, zeros after.
* ``family``: the model family a configuration names (its ``family``
  key), found by name as ``bench/models/<family>.py``.  That file holds
  the family's weights (``param_spec``, ``init_params``), its training
  reference (``train``) and its model FLOPs (``doc_flops``), so a new
  architecture is a new file there.
* ``leaf_norms``: the per-leaf norms that the model comparison reads.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "models")


# ------------------------------------------------------------ data plane
def expected_rows(doc_rows: list[list[str]], tokens_of, seq_len: int
                  ) -> dict:
    n = len(doc_rows)
    out = {"tokens": np.zeros((n, seq_len), np.int32),
           "segment_ids": np.zeros((n, seq_len), np.int32),
           "positions": np.zeros((n, seq_len), np.int32),
           "labels": np.full((n, seq_len), -1, np.int32)}
    for r, docs in enumerate(doc_rows):
        at = 0
        for k, sid in enumerate(docs, start=1):
            toks = np.asarray(tokens_of[sid])[:seq_len]
            m = len(toks)
            if at + m > seq_len:
                raise ValueError(f"row {r} overflows at {sid}")
            out["tokens"][r, at:at + m] = toks
            out["segment_ids"][r, at:at + m] = k
            out["positions"][r, at:at + m] = np.arange(m)
            out["labels"][r, at:at + m - 1] = toks[1:]
            at += m
    return out


# ----------------------------------------------------------------- model
@functools.cache
def family(name: str):
    """The module ``bench/models/<name>.py``."""
    path = os.path.join(MODELS, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no model family {name!r} under {MODELS}")
    spec = importlib.util.spec_from_file_location("bench_model_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def leaf_norms(tree) -> dict:
    """{"path#layer": L2 norm} with stacked layers split per layer."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        x = x.astype(jnp.float32)
        if name.startswith("layers/"):
            per = jnp.sqrt(jnp.sum(jnp.square(x),
                                   axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"{name}#{i}"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out
