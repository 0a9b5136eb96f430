"""A plain RWKV6 "Finch" language model for the CPU tests (arXiv:2404.05892),
written from the paper's equations in float32 at ``highest`` matmul
precision.  It imports nothing of the program under test and shares none
of its mechanics: every document runs alone, one token at a time, from an
empty state and a zero token shift.

Per layer, with x the LayerNorm'd residual stream and x' the previous
token's x (0 before a document's first token):

    time mix     base = x + (x' - x) mu_base
                 x_t  = x + (x' - x)(mu_t + tanh(base A_t) B_t), t in r,k,v,w,g
                 r, k, v = x_r W_r, x_k W_k, x_v W_v;  g = silu(x_g W_g)
                 w = exp(-exp(w0 + tanh(x_w A_w) B_w))          (per channel)
                 o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)        (per head)
                 S_t = diag(w_t) S_{t-1} + k_t^T v_t
                 out = (GroupNorm_heads(o) * g) W_o
    channel mix  k = relu(x_k W_k)^2;  out = sigmoid(x_r W_r) * (k W_v)

GroupNorm's epsilon is 1e-5 x head_size_divisor^2 = 6.4e-4, as the Finch
code sets it; the LayerNorms take the configuration's ``norm_eps``.

The parameters are read by name from the program's tree (``embed``,
``ln0``, ``layers/tm``, ``layers/cm``, ``final_norm``, ``unembed``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MIX = ("r", "k", "v", "w", "g")
GROUP_NORM_EPS = 1e-5 * 8 ** 2


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _prev(x):
    """The previous token's stream of one document (T, d): 0 first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _time_mix(p, x, hd):
    T, d = x.shape
    H = d // hd
    dx = _prev(x) - x
    base = x + dx * p["mu_base"]
    xs = {t: x + dx * (p["mu_" + t] + jnp.tanh(base @ p["mixA_" + t])
                       @ p["mixB_" + t]) for t in MIX}
    r = (xs["r"] @ p["w_r"]).reshape(T, H, hd)
    k = (xs["k"] @ p["w_k"]).reshape(T, H, hd)
    v = (xs["v"] @ p["w_v"]).reshape(T, H, hd)
    g = jax.nn.silu(xs["g"] @ p["w_g"])
    w = jnp.exp(-jnp.exp(p["w0"] + jnp.tanh(xs["w"] @ p["loraA_w"])
                         @ p["loraB_w"])).reshape(T, H, hd)
    u = p["u"]

    def token(S, rkvw):
        r_t, k_t, v_t, w_t = rkvw                     # (H, hd) each
        kv = k_t[:, :, None] * v_t[:, None, :]        # (H, hd, hd)
        o = jnp.einsum("hi,hij->hj", r_t, S + u[:, :, None] * kv)
        return w_t[:, :, None] * S + kv, o

    _, o = jax.lax.scan(token, jnp.zeros((H, hd, hd), jnp.float32),
                        (r, k, v, w))
    mu = jnp.mean(o, -1, keepdims=True)
    var = jnp.mean(jnp.square(o - mu), -1, keepdims=True)
    o = ((o - mu) / jnp.sqrt(var + GROUP_NORM_EPS)).reshape(T, d)
    o = o * p["out_ln"]["scale"] + p["out_ln"]["bias"]
    return (o * g) @ p["w_o"]


def _channel_mix(p, x):
    dx = _prev(x) - x
    k = jnp.square(jax.nn.relu((x + dx * p["mu_k"]) @ p["w_k"]))
    return jax.nn.sigmoid((x + dx * p["mu_r"]) @ p["w_r"]) * (k @ p["w_v"])


def doc_logits(cfg, params, tokens):
    """Logits (T, vocab) of one document ``tokens`` (T,) run alone."""
    eps, hd = cfg.norm_eps, cfg.rwkv_head_dim
    with jax.default_matmul_precision("highest"):
        h = _ln(params["embed"]["table"][tokens], params["ln0"], eps)
        n = params["layers"]["tm"]["w_r"].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            h = h + _time_mix(lp["tm"], _ln(h, lp["tm"]["ln"], eps), hd)
            h = h + _channel_mix(lp["cm"], _ln(h, lp["cm"]["ln"], eps))
        return _ln(h, params["final_norm"], eps) @ params["unembed"]


def documents(batch):
    """[(row, start, end)] of every document packed in the batch's rows."""
    out = []
    for r, seg in enumerate(np.asarray(batch["segment_ids"])):
        for k in np.unique(seg[seg > 0]):
            at = np.flatnonzero(seg == k)
            out.append((r, int(at[0]), int(at[-1]) + 1))
    return out


def loss(cfg, params, batch):
    """Mean next-token NLL over the batch's labels, each document alone
    (documents padded at their end to the longest, which cannot reach
    them: every position sees only the ones before it)."""
    docs = documents(batch)
    T = max(b - a for _, a, b in docs)
    tok = np.zeros((len(docs), T), np.int32)
    lab = np.full((len(docs), T), -1, np.int32)
    for i, (r, a, b) in enumerate(docs):
        tok[i, :b - a] = batch["tokens"][r, a:b]
        lab[i, :b - a] = batch["labels"][r, a:b]
    logits = jax.vmap(lambda t: doc_logits(cfg, params, t))(jnp.asarray(tok))
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.asarray(np.maximum(lab, 0))[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(jnp.asarray(lab >= 0), nll, 0.0)) \
        / int((lab >= 0).sum())


def adamw_first_step(opt, params, grads):
    """One AdamW step from zero moments after global-norm clipping: the
    bias-corrected moments are g and g^2, so the step is lr * (g / (|g| +
    eps) + weight_decay * p) with lr the warm-up's first value."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))
    lr = opt.peak_lr / max(opt.warmup_steps, 1)
    return jax.tree.map(
        lambda p, g: p - lr * (g * scale / (jnp.abs(g * scale) + opt.eps)
                               + opt.weight_decay * p), params, grads)
