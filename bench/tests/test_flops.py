"""bench/flops.py: a hand count, a bound by the compiled step's HLO FLOPs,
and the peaks table."""
import copy
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness
from bench.reference import family
from tiny import TINY_MODEL


@pytest.fixture(scope="module")
def conf():
    conf = copy.deepcopy(harness.load_json(harness.BENCH, "configs",
                                           "qwen3-8b.json"))
    conf.update(TINY_MODEL)
    return conf


def test_hand_count(conf):
    # d 64, 4 heads x 16, 2 kv heads, d_ff 128, vocab 256, 2 layers
    dense = family("dense")
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert dense.matmul_weights(conf) == 2 * per_layer + 64 * 256
    n = dense.matmul_weights(conf)
    # a 3-token document: 6 pairs, each 4 * heads * head_dim forward
    assert dense.doc_flops(conf, 3) == 6 * n * 3 + 3 * 2 * 6 * 4 * 4 * 16
    seg = np.array([[1, 1, 1, 2, 0], [1, 0, 0, 0, 0]])
    assert flops.batch_flops(conf, seg) == \
        dense.doc_flops(conf, 3) + 2 * dense.doc_flops(conf, 1)


def test_segment_lengths():
    seg = np.array([[1, 1, 2, 2, 2, 0], [1, 0, 0, 0, 0, 0],
                    [0, 0, 0, 0, 0, 0]])
    assert flops.segment_lengths(seg) == [2, 3, 1]


def test_never_above_compiled_step(conf):
    from repro.launch.hlo_cost import rollup
    from repro.models.model_zoo import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step
    model = build_model(harness.model_config(conf))
    state = jax.eval_shape(lambda k: init_train_state(model, k),
                           jax.random.key(0))
    rows, seq = 2, 256
    rng = np.random.default_rng(0)
    seg = np.zeros((rows, seq), np.int32)
    for r in range(rows):             # rows packed full of documents
        at, k = 0, 1
        while at < seq:
            n = min(int(rng.integers(8, 80)), seq - at)
            seg[r, at:at + n] = k
            at, k = at + n, k + 1
    batch = {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
             for k in ("tokens", "segment_ids", "positions", "labels")}
    text = jax.jit(make_train_step(model, AdamWConfig())).lower(
        state, batch).compile().as_text()
    assert flops.batch_flops(conf, seg) <= rollup(text).flops


def test_unknown_family_raises():
    with pytest.raises(KeyError, match="no model family"):
        flops.batch_flops({"family": "nope"}, np.ones((1, 4), np.int32))


def test_step_mfu_over_device_busy_time(conf):
    """step_mfu divides by the trace's busy time, not the host window, and
    reads nothing without a trace."""
    seg = np.zeros((2, 64), np.int32)
    seg[0, :40], seg[1, :10] = 1, 1
    w = types.SimpleNamespace(
        conf=conf, chips=2, device_kind="TPU v5 lite", window_s=100.0,
        steps=[{"step": 3}], batches={3: {"segment_ids": seg}},
        trace={"busy_s": 1e-6})
    read = harness.reader("step_mfu")
    want = 100 * flops.batch_flops(conf, seg) / (1e-6 * 2 * 197e12)
    assert read(w) == pytest.approx(want)
    w.trace = None
    assert read(w) is None


def test_peaks_table():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no peak"):
        flops.peak_flops("cpu")
