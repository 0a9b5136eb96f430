"""Per-arch smoke tests (reduced configs): forward + one train step on CPU,
shape/NaN assertions, and decode-consistency checks."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import all_reduced_configs, make_lm_batch
from repro.models.model_zoo import build_model
from repro.train.train_step import init_train_state, make_train_step

CONFIGS = all_reduced_configs()


@pytest.mark.parametrize("cfg", CONFIGS, ids=[c.name for c in CONFIGS])
def test_forward_shapes_and_finite(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.key(0), jnp.float32)
    b, s = 2, 64
    batch = make_lm_batch(cfg, b, s)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("cfg", CONFIGS, ids=[c.name for c in CONFIGS])
def test_one_train_step_no_nans(cfg):
    model = build_model(cfg)
    state = init_train_state(model, jax.random.key(1))
    step = jax.jit(make_train_step(model))
    batch = make_lm_batch(cfg, 2, 64)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(new_state.opt.step) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state.params, new_state.params)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("cfg", CONFIGS, ids=[c.name for c in CONFIGS])
def test_prefill_decode_consistency(cfg):
    """decode_step at position s (on a prefix cache) must reproduce the
    training forward's logits for the last token (single segment)."""
    model = build_model(cfg)
    params = model.init(jax.random.key(2), jnp.float32)
    b, s = 2, 32
    batch = make_lm_batch(cfg, b, s, n_segments=1, trailing_pad=0)
    logits_f, _ = jax.jit(model.forward)(params, batch)
    logits_p, _cache = jax.jit(model.prefill)(params, batch)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32),
        np.asarray(logits_f[:, -1], np.float32), atol=2e-3, rtol=2e-3)


def test_transformer_decode_matches_forward():
    """Token-by-token decode equals the packed forward (qwen3 reduced)."""
    from repro.configs.qwen3_8b import reduced
    cfg = reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(3), jnp.float32)
    b, s = 1, 16
    batch = make_lm_batch(cfg, b, s, n_segments=1, trailing_pad=0)
    logits_f, _ = jax.jit(model.forward)(params, batch)
    cache = model.init_cache(b, s, jnp.float32)
    decode = jax.jit(model.decode_step)
    for t in range(s):
        logits_d, cache = decode(params, cache,
                                 batch["tokens"][:, t:t + 1], jnp.int32(t))
    np.testing.assert_allclose(np.asarray(logits_d[:, 0], np.float32),
                               np.asarray(logits_f[:, -1], np.float32),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["yi_9b", "rwkv6_3b"])
def test_packed_segments_are_independent(arch):
    """Packing isolation, both ways: a segment's logits do not depend on
    what is packed after it, and a segment packed behind another has the
    logits it has alone (attention masks by segment; RWKV6 resets its
    WKV state and its token shift at a segment start)."""
    import importlib
    cfg = importlib.import_module(f"repro.configs.{arch}").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(4), jnp.float32)
    rng = np.random.default_rng(0)
    s = 64
    a = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    bpart = rng.integers(1, cfg.vocab_size, 30).astype(np.int32)
    c = rng.integers(1, cfg.vocab_size, 30).astype(np.int32)

    def packed(*docs):
        tokens = np.zeros((1, s), np.int32)
        seg = np.zeros((1, s), np.int32)
        pos = np.zeros((1, s), np.int32)
        at = 0
        for k, d in enumerate(docs, 1):
            tokens[0, at:at + len(d)] = d
            seg[0, at:at + len(d)] = k
            pos[0, at:at + len(d)] = np.arange(len(d))
            at += len(d)
        return dict(tokens=tokens, segment_ids=seg, positions=pos)

    f = jax.jit(model.forward)
    l1, _ = f(params, packed(a, bpart))
    l2, _ = f(params, packed(a, c))
    alone, _ = f(params, packed(bpart))
    # float32 end to end: the same document's logits differ only by the
    # rounding of sums taken in another order (the WKV chunk a token lands
    # in moves with its offset), ~1e-6 at logit scale ~4
    np.testing.assert_allclose(np.asarray(l1[0, :24], np.float32),
                               np.asarray(l2[0, :24], np.float32),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(l1[0, 24:54], np.float32),
                               np.asarray(alone[0, :30], np.float32),
                               atol=1e-4, rtol=1e-4)


def test_rwkv6_matches_plain_reference():
    """The program's rwkv6 (chunked WKV over packed rows) against the plain
    per-document reference (tests/rwkv6_reference.py): the loss, the first
    gradient's leaf norms and one AdamW update, on seeded random weights
    (the LoRAs' second matrices drawn too, so the data-dependent shift and
    decay act) and two rows of several packed documents with padding."""
    import rwkv6_reference as ref
    from repro.configs.rwkv6_3b import reduced
    from repro.train.optimizer import AdamWConfig, adamw_update, init_adamw
    from repro.train.train_step import cross_entropy
    cfg = reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(7), jnp.float32)
    tm = params["layers"]["tm"]
    for i, name in enumerate(sorted(k for k in tm if "B_" in k)):
        tm[name] = 0.3 * jax.random.normal(jax.random.key(100 + i),
                                           tm[name].shape)
    r = np.random.default_rng(3)
    s = 64
    batch = {k: np.zeros((2, s), np.int32)
             for k in ("tokens", "segment_ids", "positions")}
    batch["labels"] = np.full((2, s), -1, np.int32)
    for row, lengths in enumerate([(9, 23, 17, 5), (40, 1, 20)]):
        at = 0
        for k, n in enumerate(lengths, 1):
            toks = r.integers(1, cfg.vocab_size, n).astype(np.int32)
            batch["tokens"][row, at:at + n] = toks
            batch["segment_ids"][row, at:at + n] = k
            batch["positions"][row, at:at + n] = np.arange(n)
            batch["labels"][row, at:at + n - 1] = toks[1:]
            at += n

    def program_loss(p):
        logits, _ = model.forward(p, batch)
        mask = ((batch["labels"] >= 0) & (batch["segment_ids"] > 0))
        return cross_entropy(logits, jnp.asarray(batch["labels"]),
                             jnp.asarray(mask, jnp.float32))[0]

    opt = AdamWConfig(peak_lr=3e-4, warmup_steps=10, total_steps=100)
    loss_p, grads_p = jax.jit(jax.value_and_grad(program_loss))(params)
    new_p = adamw_update(opt, grads_p, init_adamw(params), params)[0]
    loss_r, grads_r = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg, p, batch)))(params)
    new_r = ref.adamw_first_step(opt, params, grads_r)

    def norms(tree):
        return np.array([float(jnp.linalg.norm(x.ravel()))
                         for x in jax.tree.leaves(tree)])
    # float32 both: the chunked scan and the token-by-token recurrence sum
    # in another order; read under 1e-7 relative in the loss and 7.6e-6 in
    # the gradients' leaf norms, ten times under these.  A token shift
    # that crosses documents reads 5e-3 and 15.
    np.testing.assert_allclose(float(loss_p), float(loss_r), rtol=1e-6)
    gp, gr = norms(grads_p), norms(grads_r)
    np.testing.assert_allclose(gp, gr, rtol=1e-4, atol=1e-4 * np.median(gr))
    # the first AdamW step is lr * (g / (|g| + eps) + wd p): an entry whose
    # gradient is near eps (1e-8) moves by a share of lr that rounding
    # decides, so the change is compared per leaf norm; read 1.1e-4 (the
    # crossing shift 1.6e-2)
    up = norms(jax.tree.map(jnp.subtract, new_p, params))
    ur = norms(jax.tree.map(jnp.subtract, new_r, params))
    np.testing.assert_allclose(up, ur, rtol=1e-3)


def test_zamba2_shared_block_is_shared():
    from repro.configs.zamba2_7b import reduced
    cfg = reduced()
    model = build_model(cfg)
    n_blocks = cfg.num_layers // cfg.attn_every
    # exactly ONE copy of shared-attn params regardless of applications
    sp = model.defs["shared_attn"]["attn"]["wq"]
    assert sp.shape[0] == cfg.d_model
    assert n_blocks > 1


def test_vlm_image_fusion_changes_logits():
    from repro.configs.pixtral_12b import reduced
    cfg = reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(5), jnp.float32)
    batch = make_lm_batch(cfg, 1, 32, n_segments=1, trailing_pad=0)
    l1, _ = jax.jit(model.forward)(params, batch)
    batch2 = dict(batch)
    batch2["image_embeds"] = batch["image_embeds"] + 1.0
    l2, _ = jax.jit(model.forward)(params, batch2)
    assert float(jnp.max(jnp.abs(l1 - l2))) > 1e-4
