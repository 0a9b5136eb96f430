"""Cells at CPU-test sizes: the committed configuration and traffic files
with widths, depth, vocabulary, sequence and sources cut down."""
from __future__ import annotations

from bench import harness

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 256}
# set from CPU readings at these sizes (bench/tests/test_control.py): the
# program's worst of 8 seeds read loss 3.6e-4, grad 1.9e-3, update
# 4.6e-3; the int8 control's grad_gap 1.4e-2 to 3.7e-2
TINY_LIMITS = {"rows_wrong": 0, "tokens_gap": 0, "loss_gap": 1e-3,
               "grad_gap": 5e-3, "update_gap": 0.02}


def tiny_cell(seq_len: int = 256, samples: int = 256) -> dict:
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    conf = harness.load_json(harness.BENCH, "configs", "qwen3-8b.json")
    conf.update(TINY_MODEL)
    mix = harness.load_json(harness.BENCH, "traffic", "coyo5.json")
    mix["seq_len"] = seq_len
    for s in mix["sources"]:
        s["n_samples"] = samples
        s["text_mu"] = min(s["text_mu"], 3.0)
    return {"name": "tiny.coyo5", "chips": 1, "config": conf,
            "traffic_name": "coyo5", "traffic": mix,
            "limits": dict(TINY_LIMITS),
            "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if "workloads" not in m]}
