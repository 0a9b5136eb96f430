"""The ssm (RWKV6) family's benchmark files at CPU-test sizes: its weights
are the program's parameter tree, the program's first steps agree with the
per-document reference, a program whose token shift crosses documents
(the fault ``rwkv6-3b`` carried before) fails the same comparison, its
FLOPs are the hand count, and ``wkv_chunk_share`` reads the step's
counts."""
import time

import jax
import pytest

from bench import flops, harness
from bench.reference import family

ssm = family("ssm")

# the rwkv6-3b configuration at CPU-test widths: two heads of the
# published size 64, chunks of 16 so that a 256-token row holds 16
TINY_SSM = {"num_layers": 2, "d_model": 128, "num_heads": 2,
            "num_kv_heads": 2, "d_ff": 256, "vocab_size": 256,
            "rwkv_chunk": 16, "rwkv_lora_dim": 16}
# set from CPU readings at these sizes over 8 seeds, the program (bfloat16
# compute) against the float32 reference: at most loss 4.1e-4, grad 0.060,
# update 4.3e-3; a token shift that crosses documents reads loss 6.3e-4 to
# 2.7e-3, grad 0.024 to 0.35, update 1.3e-2 to 2.3e-2.  The gradient is the
# loosest: at a document's first token a head's WKV output is one scalar
# r.(u*k) times v, GroupNorm's gradient through a scalar near 0 is
# 1/sqrt(eps), and bfloat16 rounding moves that scalar.
TINY_SSM_LIMITS = {"rows_wrong": 0, "tokens_gap": 0, "loss_gap": 1e-3,
                   "grad_gap": 0.1, "update_gap": 8e-3}


def tiny_ssm_cell(seq_len: int = 256, samples: int = 256) -> dict:
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    conf = harness.load_json(harness.BENCH, "configs", "rwkv6-3b.json")
    conf.update(TINY_SSM)
    mix = harness.load_json(harness.BENCH, "traffic", "coyo5.json")
    mix["seq_len"] = seq_len
    for s in mix["sources"]:
        s["n_samples"] = samples
        s["text_mu"] = min(s["text_mu"], 3.0)
    name = "rwkv6-3b.coyo5"
    return {"name": "tiny." + name, "chips": 1, "config": conf,
            "traffic_name": "coyo5", "traffic": mix,
            "limits": dict(TINY_SSM_LIMITS),
            "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]}


def window(steps):
    return harness.Window(
        conf={}, chips=1, device_kind="cpu", setup_s=1.0, window_s=1.0,
        steps=steps, batches={}, spans=[], rss_start_bytes=0,
        rss_peak_bytes=0, plane_bytes=0.0, trace=None)


@pytest.mark.parametrize("size", ["tiny", "cell"])
def test_weights_are_the_programs_tree(size):
    from repro.models.model_zoo import build_model
    conf = harness.cell("rwkv6-3b.coyo5")["config"]
    if size == "tiny":
        conf.update(TINY_SSM)
    want = jax.tree.map(
        lambda x: (x.shape, str(x.dtype)),
        build_model(harness.model_config(conf)).abstract_params(
            jax.numpy.float32))
    have = jax.tree.map(lambda x: (x.shape, str(x.dtype)), jax.eval_shape(
        lambda k: ssm.init_params(conf, k), jax.random.key(0)))
    assert want == have


def test_program_agrees_with_the_reference():
    out = harness.run_cell(tiny_ssm_cell(), 2**31 + 15, 1.0, False,
                           time.perf_counter(), jax.devices()[:1])
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_shift_across_documents_fails_the_check(monkeypatch):
    """The program with the token shift it had before segment-aware
    resets: each document's first token takes in the last token of the
    document before it, in the time mix and in the channel mix."""
    import jax.numpy as jnp
    from repro.models import rwkv

    def crossing(x, reset):
        return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))
    monkeypatch.setattr(rwkv, "_token_shift", crossing)
    out = harness.run_cell(tiny_ssm_cell(), 2**31 + 15, 1.0, False,
                           time.perf_counter(), jax.devices()[:1])
    checks = out["checks"]
    assert not out["correct"]
    assert checks["rows_wrong"]["value"] == 0
    assert checks["loss_gap"]["value"] > checks["loss_gap"]["limit"], checks


def test_doc_flops_by_hand():
    conf = harness.cell("rwkv6-3b.coyo5")["config"]
    d, F = 2560, 8960
    layer = 5 * d * d + 5 * 2 * d * 32 + 2 * d * 64 + 2 * d * F + d * d
    assert layer == 86_343_680
    weights = 4 * layer + d * 8192
    # per head and token: k^T v 4096, r S 8192, bonus 256, decay 8192
    recurrence = 40 * (4096 + 8192 + 256 + 8192)
    assert ssm.matmul_weights(conf) == weights
    assert ssm.recurrence_flops(conf) == recurrence
    assert ssm.doc_flops(conf, 41) == 6.0 * weights * 41 \
        + 3.0 * 4 * recurrence * 41
    import numpy as np
    seg = np.array([[1] * 41 + [2] * 7 + [0] * 16])
    assert flops.batch_flops(conf, seg) == ssm.doc_flops(conf, 41) \
        + ssm.doc_flops(conf, 7)


def test_wkv_chunk_share_sums_over_the_window():
    read = harness.reader("wkv_chunk_share")
    steps = [{"step": 3, "wkv_chunks_live": 30.0, "wkv_chunks_total": 128.0},
             {"step": 4, "wkv_chunks_live": 50.0, "wkv_chunks_total": 128.0}]
    assert read(window(steps)) == 100.0 * 80 / 256
    assert read(window([{"step": 3, "loss": 1.0}])) is None
    assert read(window([])) is None


def test_documents_are_grouped_alone():
    import numpy as np
    batch = {"tokens": np.arange(1, 17).reshape(2, 8),
             "segment_ids": np.array([[1, 1, 1, 2, 2, 0, 0, 0],
                                      [1, 1, 1, 1, 1, 1, 2, 0]]),
             "labels": np.arange(101, 117).reshape(2, 8)}
    docs = ssm.documents(batch)
    assert [d[0].tolist() for d in docs] == [[1, 2, 3], [4, 5], [9, 10, 11,
                                             12, 13, 14], [15]]
    (tok, lab), = ssm.groups(docs)
    assert tok.shape == (ssm.TOKENS // 256, 256)
    assert tok[1, :3].tolist() == [4, 5, 0] and lab[1, :3].tolist() == \
        [104, 105, -1]
    assert not tok[4:].any() and (lab[4:] == -1).all()
