"""The dense decoder family (Qwen3 and its kin): the benchmark's weights,
its plain training reference and its model FLOPs.  Nothing here imports
the program under test.

* weights: ``param_spec`` names every tensor, its shape and its initial
  distribution; ``init_params`` draws them from a key in one jitted call.
  The harness hands the program the same draw.
* ``train``: the LM (RMSNorm, per-head q/k RMSNorm, rotate-half RoPE on
  within-document positions, grouped-query causal attention that never
  crosses a document, SwiGLU, untied head) with AdamW and global norm
  clipping, in float32 at ``highest`` matmul precision.  It takes one row,
  one block of queries and one block of positions at a time, so that it
  fits one chip at the timed sizes once the program is freed.
  ``precision="int8"`` is the control: every matmul (forward and backward)
  on operands rounded to int8 with one scale per tensor.
* ``doc_flops``: training FLOPs of one document (see ``bench/flops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # queries per attention block in the reference
POS_BLOCK = 1024       # positions per MLP and output-head block


# --------------------------------------------------------------- weights
def param_spec(cfg: dict) -> dict:
    """{path: (shape, init)}; init is ("normal", std) or ("ones",)."""
    d, L, V = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    H, KH, hd, F = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                    cfg["d_ff"])
    spec = {
        "embed/table": ((V, d), ("normal", 1.0)),
        "final_norm/scale": ((d,), ("ones",)),
        "unembed": ((d, V), ("normal", d ** -0.5)),
        "layers/attn_norm/scale": ((L, d), ("ones",)),
        "layers/mlp_norm/scale": ((L, d), ("ones",)),
        "layers/attn/wq": ((L, d, H, hd), ("normal", d ** -0.5)),
        "layers/attn/wk": ((L, d, KH, hd), ("normal", d ** -0.5)),
        "layers/attn/wv": ((L, d, KH, hd), ("normal", d ** -0.5)),
        "layers/attn/wo": ((L, H, hd, d), ("normal", (H * hd) ** -0.5)),
        "layers/mlp/w_gate": ((L, d, F), ("normal", d ** -0.5)),
        "layers/mlp/w_up": ((L, d, F), ("normal", d ** -0.5)),
        "layers/mlp/w_down": ((L, F, d), ("normal", F ** -0.5)),
    }
    if cfg["qk_norm"]:
        spec["layers/attn/q_norm/scale"] = ((L, hd), ("ones",))
        spec["layers/attn/k_norm/scale"] = ((L, hd), ("ones",))
    return spec


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def init_params(cfg: dict, key) -> dict:
    """The benchmark's float32 weights, drawn from ``key``."""
    flat = {}
    for i, (path, (shape, init)) in enumerate(sorted(param_spec(cfg).items())):
        if init[0] == "ones":
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            flat[path] = init[1] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return _nest(flat)


# ----------------------------------------------------------- arithmetic
def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _int8(x):
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_ein(spec, a, b):
    return _ein(spec, _int8(a), _int8(b))


def _int8_fwd(spec, a, b):
    qa, qb = _int8(a), _int8(b)
    return _ein(spec, qa, qb), (qa, qb)


def _int8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: _ein(spec, x, y), *res)
    return vjp(_int8(g))


_int8_ein.defvjp(_int8_fwd, _int8_bwd)

MATMULS = {"f32": _ein, "int8": _int8_ein}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(mm, q, k, v, seg):
    """q (S, H, hd); k, v (S, KH, hd); causal inside each document."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    idx = jnp.arange(S)
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(args):
        qblk, sq, iq = args
        s = mm("qhd,khd->hqk", qblk, k) * hd ** -0.5
        mask = (sq[:, None] == seg[None, :]) & (seg[None, :] > 0) \
            & (iq[:, None] >= idx[None, :])
        s = jnp.where(mask[None], s, -jnp.inf)
        m = jnp.max(s, -1, keepdims=True)
        p = jnp.where(mask[None], jnp.exp(s - jnp.where(
            jnp.isfinite(m), m, 0.0)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        o = mm("hqk,khd->qhd", p, v)
        return jnp.where((sq > 0)[:, None, None], o, 0.0)

    out = jax.lax.map(block, (q.reshape(S // qb, qb, H, hd),
                              seg.reshape(S // qb, qb),
                              idx.reshape(S // qb, qb)))
    return out.reshape(S, H, hd)


def _by_blocks(fn, *xs):
    """``fn`` over blocks of POS_BLOCK positions (axis 0), rematerialised
    in the backward pass, so that one block's intermediates live at once."""
    n = xs[0].shape[0]
    b = min(POS_BLOCK, n)
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)),
                      tuple(x.reshape((n // b, b) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape((n,) + y.shape[2:]), out)


def _row_nll(cfg, mm, params, row):
    """Summed next-token NLL of one packed row, and its label count."""
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    tok, seg, pos, lab = (row["tokens"], row["segment_ids"],
                          row["positions"], row["labels"])
    h = params["embed"]["table"][tok]

    @jax.checkpoint
    def layer(h, lp):
        a = lp["attn"]
        x = _rms(h, lp["attn_norm"]["scale"], eps)
        q = mm("sd,dhk->shk", x, a["wq"])
        k = mm("sd,dhk->shk", x, a["wk"])
        v = mm("sd,dhk->shk", x, a["wv"])
        if cfg["qk_norm"]:
            q = _rms(q, a["q_norm"]["scale"], eps)
            k = _rms(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        h = h + mm("shk,hkd->sd", _attention(mm, q, k, v, seg), a["wo"])
        m = lp["mlp"]

        def mlp(h):
            x = _rms(h, lp["mlp_norm"]["scale"], eps)
            g = jax.nn.silu(mm("sd,df->sf", x, m["w_gate"])) \
                * mm("sd,df->sf", x, m["w_up"])
            return mm("sf,fd->sd", g, m["w_down"])
        return h + _by_blocks(mlp, h), None

    h, _ = jax.lax.scan(layer, h, params["layers"])

    def nll(h, lab):
        h = _rms(h, params["final_norm"]["scale"], eps)
        logits = mm("sd,dv->sv", h, params["unembed"])
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[:, None], -1)[:, 0]
    mask = (lab >= 0) & (seg > 0)
    return jnp.sum(jnp.where(mask, _by_blocks(nll, h, lab), 0.0)), \
        jnp.sum(mask)


def _lr(opt: dict, step):
    step = step.astype(jnp.float32)
    warm = step / max(opt["warmup_steps"], 1)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 \
        * (1 + jnp.cos(jnp.pi * frac))
    return opt["peak_lr"] * jnp.where(step < opt["warmup_steps"], warm, cos)


def _row_grads(cfg, precision, params, row):
    """One row's summed NLL and its gradient."""
    mm = MATMULS[precision]
    return jax.value_and_grad(
        lambda p: _row_nll(cfg, mm, p, row)[0])(params)


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _adamw(opt, params, m, v, grads, n_labels, step):
    """The mean loss's gradient (``grads`` sums rows' NLL gradients),
    global-norm clipping, then AdamW; returns the new state and the
    clipped gradient's leaf norms."""
    grads = jax.tree.map(lambda g: g / n_labels, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1
    lr = _lr(opt, t)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1 / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p), params, m, v)
    return params, m, v, leaf_norms(grads)


def train(cfg: dict, opt: dict, key, batches: list[dict],
          precision: str = "f32", device=None) -> dict:
    """Train ``len(batches)`` steps from the benchmark's weights; returns
    each step's loss, the first clipped gradient's leaf norms and the
    leaf norms of the weights' change over all the steps."""
    with jax.default_device(device or jax.devices()[0]):
        return _train(cfg, opt, key, batches, precision)


def _train(cfg, opt, key, batches, precision):
    init = jax.jit(functools.partial(init_params, cfg))
    row_grads = jax.jit(functools.partial(_row_grads, cfg, precision))
    add = jax.jit(_add, donate_argnums=(0,))
    adamw = jax.jit(functools.partial(_adamw, opt),
                    donate_argnums=(0, 1, 2))
    params = init(key)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        n_labels = float(np.sum((b["labels"] >= 0) & (b["segment_ids"] > 0)))
        total, grads = 0.0, None
        for r in range(len(b["tokens"])):
            row = {k: jnp.asarray(b[k][r]) for k in (
                "tokens", "segment_ids", "positions", "labels")}
            nll, g = row_grads(params, row)
            total += float(nll)
            grads = g if grads is None else add(grads, g)
            del g
        params, m, v, gn = adamw(params, m, v, grads, jnp.float32(n_labels),
                                 jnp.int32(i))
        losses.append(total / n_labels)
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in gn.items()}
    del m, v
    p0 = init(key)
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {k: float(x) for k, x in change.items()}}


# ----------------------------------------------------------------- FLOPs
def matmul_weights(cfg: dict) -> int:
    """Weights that multiply each token: every layer's and the head's."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    attn = 2 * d * cfg["num_heads"] * hd + 2 * d * cfg["num_kv_heads"] * hd
    return cfg["num_layers"] * (attn + 3 * d * cfg["d_ff"]) \
        + d * cfg["vocab_size"]


def doc_flops(cfg: dict, length: int) -> float:
    """Training FLOPs of one document of ``length`` tokens: 6 per weight
    and token, and per layer and causal (query, key) pair 2 * heads *
    head_dim for the scores and as many for the values, forward, 3 times
    that in training."""
    pairs = length * (length + 1) / 2
    return 6.0 * matmul_weights(cfg) * length \
        + 3.0 * cfg["num_layers"] * pairs * 4 * cfg["num_heads"] \
        * cfg["head_dim"]
