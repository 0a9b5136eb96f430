"""The RWKV6 "Finch" family (arXiv:2404.05892, hf:RWKV/v6-Finch-3B-HF): the
benchmark's weights, its plain training reference and its model FLOPs.
Nothing here imports the program under test.

* weights: ``param_spec`` names every tensor of the program's parameter
  tree, its shape and its initial distribution; ``init_params`` draws them
  from a key in one jitted call.  The harness hands the program the same
  draw.
* ``train``: the LM with AdamW and global norm clipping, in float32 at
  ``highest`` matmul precision.  After the embedding a LayerNorm; per layer,
  on the LayerNorm'd stream x with x' the previous token's x:

      time mix     base = x + (x' - x) mu_base
                   x_t  = x + (x' - x)(mu_t + tanh(base A_t) B_t),  t in r,k,v,w,g
                   r, k, v = x_r W_r, x_k W_k, x_v W_v;  g = silu(x_g W_g)
                   w = exp(-exp(w0 + tanh(x_w A_w) B_w))         per channel
                   o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)       per head
                   S_t = diag(w_t) S_{t-1} + k_t^T v_t
                   out = (GroupNorm_heads(o) * g) W_o
      channel mix  k = relu(x_k W_k)^2;  out = sigmoid(x_r W_r) * (k W_v)

  then a LayerNorm and the untied head.  Each document runs alone, from an
  empty state and a zero token shift, through a token-by-token recurrence:
  the reference shares none of the program's packing and reset mechanics.
  Documents of a step are batched by length (``BUCKETS``), each padded at
  its end to its bucket; padding comes after a document and so cannot
  reach it.  ``precision="int8"`` is the control: every matmul of the
  projections and the head (forward and backward) on operands rounded to
  int8 with one scale per tensor.  The LayerNorms take the configuration's
  ``norm_eps``; GroupNorm takes the Finch code's 1e-5 x head_size_divisor^2
  (``GROUP_NORM_EPS``).
* ``doc_flops``: training FLOPs of one document (see ``bench/flops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import family, leaf_norms

# AdamW, its learning-rate schedule, the float32 and int8 matmuls and the
# tree helpers are the dense family's: the same optimizer and control.
dense = family("dense")

MIX_LORA = 32          # width of the five token-shift LoRAs, as published
MIX = ("r", "k", "v", "w", "g")
TOKENS = 4096          # token slots a reference call computes
# lengths documents are padded to, TOKENS // T documents a call: few
# shapes, so few compiles (coyo texts are ~41 tokens, a few over 256)
BUCKETS = (256, 4096)
TIME_BLOCK = 8         # tokens per rematerialised block of the recurrence
GROUP_NORM_EPS = 1e-5 * 8 ** 2    # 1e-5 x head_size_divisor^2, as published


# --------------------------------------------------------------- weights
def param_spec(cfg: dict) -> dict:
    """{path: (shape, init)}; init is ("normal", std), ("uniform", lo, hi),
    ("ones",) or ("zeros",).  Matrices are N(0, 1/fan_in), the time mix's
    key and gate a tenth of that, as the published initialisation scales
    them.  Decays start in [-6, -1] (w = exp(-exp(w0)) from 0.69 to 0.9975
    a token), the token-shift mixes in [0, 1] and the bonus in [0, 1], the
    ranges the published initialisation spans.  Departures from it (the
    configuration's ``assumed`` lists them): matrices are Gaussian, not
    orthogonal; decays, mixes and bonus are drawn uniformly, not laid out
    as ramps over the channels; the embedding is N(0, 1) and the head
    N(0, 1/d); and, so that the first step's gradient reaches every
    weight, the time mix's output, the channel mix's value and receptance
    and the LoRAs' second matrices are drawn where the published ones start
    at zero."""
    d, L, V, F = (cfg["d_model"], cfg["num_layers"], cfg["vocab_size"],
                  cfg["d_ff"])
    hd, lo = cfg["rwkv_head_dim"], cfg["rwkv_lora_dim"]
    H = d // hd
    unit, ones, zeros = ("uniform", 0.0, 1.0), ("ones",), ("zeros",)
    spec = {
        "embed/table": ((V, d), ("normal", 1.0)),
        "ln0/scale": ((d,), ones), "ln0/bias": ((d,), zeros),
        "final_norm/scale": ((d,), ones), "final_norm/bias": ((d,), zeros),
        "unembed": ((d, V), ("normal", d ** -0.5)),
    }
    tm = {"ln/scale": ((d,), ones), "ln/bias": ((d,), zeros),
          "mu_base": ((d,), unit),
          "w0": ((d,), ("uniform", -6.0, -1.0)),
          "loraA_w": ((d, lo), ("normal", d ** -0.5)),
          "loraB_w": ((lo, d), ("normal", 0.05)),
          "u": ((H, hd), unit),
          "out_ln/scale": ((d,), ones), "out_ln/bias": ((d,), zeros)}
    for t in MIX:
        tm[f"mu_{t}"] = ((d,), unit)
        tm[f"mixA_{t}"] = ((d, MIX_LORA), ("normal", d ** -0.5))
        tm[f"mixB_{t}"] = ((MIX_LORA, d), ("normal", 0.05))
    for t in ("r", "k", "v", "g", "o"):
        tm[f"w_{t}"] = ((d, d), ("normal", (0.1 if t in "kg" else 1.0)
                                 * d ** -0.5))
    cm = {"ln/scale": ((d,), ones), "ln/bias": ((d,), zeros),
          "mu_k": ((d,), unit), "mu_r": ((d,), unit),
          "w_k": ((d, F), ("normal", d ** -0.5)),
          "w_v": ((F, d), ("normal", F ** -0.5)),
          "w_r": ((d, d), ("normal", d ** -0.5))}
    for block, part in (("tm", tm), ("cm", cm)):
        for path, (shape, init) in part.items():
            spec[f"layers/{block}/{path}"] = ((L,) + shape, init)
    return spec


def init_params(cfg: dict, key) -> dict:
    """The benchmark's float32 weights, drawn from ``key``."""
    flat = {}
    for i, (path, (shape, init)) in enumerate(sorted(param_spec(cfg).items())):
        k = jax.random.fold_in(key, i)
        if init[0] == "ones":
            flat[path] = jnp.ones(shape, jnp.float32)
        elif init[0] == "zeros":
            flat[path] = jnp.zeros(shape, jnp.float32)
        elif init[0] == "uniform":
            flat[path] = jax.random.uniform(k, shape, jnp.float32,
                                            init[1], init[2])
        else:
            flat[path] = init[1] * jax.random.normal(k, shape, jnp.float32)
    return dense._nest(flat)


# ----------------------------------------------------------------- model
def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _prev(x):
    """The previous token's stream; every row of x (G, T, d) is one
    document from its first token, whose previous token is 0."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))


def _wkv(r, k, v, w, u):
    """o_t = r_t (S_{t-1} + diag(u) k_t^T v_t), S_t = diag(w_t) S_{t-1} +
    k_t^T v_t, one token at a time from S_0 = 0.  r, k, v, w: (G, T, H, hd);
    u: (H, hd).  Blocks of TIME_BLOCK tokens are rematerialised in the
    backward pass, so that only a block's states live at once."""
    G, T, H, hd = r.shape

    def token(S, x):
        r_t, k_t, v_t, w_t = x                           # (G, H, hd)
        kv = k_t[..., :, None] * v_t[..., None, :]       # (G, H, hd, hd)
        o = jnp.einsum("ghi,ghij->ghj", r_t, S + u[:, :, None] * kv)
        return w_t[..., :, None] * S + kv, o

    @jax.checkpoint
    def block(S, x):
        return jax.lax.scan(token, S, x)

    def blocks(a):   # (G, T, H, hd) -> (T/B, B, G, H, hd)
        return a.transpose(1, 0, 2, 3).reshape(T // TIME_BLOCK, TIME_BLOCK,
                                               G, H, hd)

    _, o = jax.lax.scan(block, jnp.zeros((G, H, hd, hd), jnp.float32),
                        tuple(map(blocks, (r, k, v, w))))
    return o.reshape(T, G, H, hd).transpose(1, 0, 2, 3)


def _layer(cfg, mm, h, lp):
    eps, hd = cfg["norm_eps"], cfg["rwkv_head_dim"]
    G, T, d = h.shape
    H = d // hd
    p = lp["tm"]
    x = _ln(h, p["ln"], eps)
    dx = _prev(x) - x
    base = x + dx * p["mu_base"]
    xs = {t: x + dx * (p["mu_" + t] + mm(
        "gtl,ld->gtd", jnp.tanh(mm("gtd,dl->gtl", base, p["mixA_" + t])),
        p["mixB_" + t])) for t in MIX}

    def proj(t):
        return mm("gtd,de->gte", xs[t], p["w_" + t]).reshape(G, T, H, hd)
    w = jnp.exp(-jnp.exp(p["w0"] + mm(
        "gtl,ld->gtd", jnp.tanh(mm("gtd,dl->gtl", xs["w"], p["loraA_w"])),
        p["loraB_w"]))).reshape(G, T, H, hd)
    o = _wkv(proj("r"), proj("k"), proj("v"), w, p["u"])
    mu = jnp.mean(o, -1, keepdims=True)
    var = jnp.mean(jnp.square(o - mu), -1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + GROUP_NORM_EPS)).reshape(G, T, d) \
        * p["out_ln"]["scale"] + p["out_ln"]["bias"]
    g = jax.nn.silu(mm("gtd,de->gte", xs["g"], p["w_g"]))
    h = h + mm("gtd,de->gte", o * g, p["w_o"])
    p = lp["cm"]
    x = _ln(h, p["ln"], eps)
    dx = _prev(x) - x
    k = jnp.square(jax.nn.relu(mm("gtd,df->gtf", x + dx * p["mu_k"],
                                  p["w_k"])))
    r = jax.nn.sigmoid(mm("gtd,de->gte", x + dx * p["mu_r"], p["w_r"]))
    return h + r * mm("gtf,fd->gtd", k, p["w_v"])


def _docs_nll(cfg, mm, params, tok, lab):
    """Summed next-token NLL of G documents (G, T), each from position 0
    and padded at its end (label -1)."""
    eps = cfg["norm_eps"]
    h = _ln(params["embed"]["table"][tok], params["ln0"], eps)
    h, _ = jax.lax.scan(
        jax.checkpoint(lambda h, lp: (_layer(cfg, mm, h, lp), None)), h,
        params["layers"])
    logits = mm("gtd,dv->gtv", _ln(h, params["final_norm"], eps),
                params["unembed"])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(lab >= 0, nll, 0.0))


def documents(batch: dict) -> list[tuple[np.ndarray, np.ndarray]]:
    """(tokens, labels) of every document packed in a batch's rows."""
    out = []
    for tok, seg, lab in zip(batch["tokens"], batch["segment_ids"],
                             batch["labels"]):
        for k in np.unique(seg[seg > 0]):
            at = np.flatnonzero(seg == k)
            out.append((tok[at[0]:at[-1] + 1], lab[at[0]:at[-1] + 1]))
    return out


def groups(docs: list) -> list[tuple[np.ndarray, np.ndarray]]:
    """The documents in (G, T) arrays: each in the least bucket T that
    holds it, G = TOKENS // T documents an array, padded at the end (token
    0, label -1) and with empty documents."""
    by_bucket: dict[int, list] = {}
    for tok, lab in docs:
        T = next(b for b in BUCKETS if b >= len(tok))
        by_bucket.setdefault(T, []).append((tok, lab))
    out = []
    for T, ds in sorted(by_bucket.items()):
        G = TOKENS // T
        for a in range(0, len(ds), G):
            tok = np.zeros((G, T), np.int32)
            lab = np.full((G, T), -1, np.int32)
            for i, (t, l) in enumerate(ds[a:a + G]):
                tok[i, :len(t)], lab[i, :len(l)] = t, l
            out.append((tok, lab))
    return out


def _group_grads(cfg, precision, params, tok, lab):
    mm = dense.MATMULS[precision]
    return jax.value_and_grad(
        lambda p: _docs_nll(cfg, mm, p, tok, lab))(params)


def train(cfg: dict, opt: dict, key, batches: list[dict],
          precision: str = "f32", device=None) -> dict:
    """Train ``len(batches)`` steps from the benchmark's weights; returns
    each step's loss, the first clipped gradient's leaf norms and the
    leaf norms of the weights' change over all the steps."""
    with jax.default_device(device or jax.devices()[0]), \
            jax.default_matmul_precision("highest"):
        return _train(cfg, opt, key, batches, precision)


def _train(cfg, opt, key, batches, precision):
    init = jax.jit(functools.partial(init_params, cfg))
    group_grads = jax.jit(functools.partial(_group_grads, cfg, precision))
    add = jax.jit(dense._add, donate_argnums=(0,))
    adamw = jax.jit(functools.partial(dense._adamw, opt),
                    donate_argnums=(0, 1, 2))
    params = init(key)
    m = v = None              # allocated after the first gradient, not beside it
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        n_labels = float(np.sum((b["labels"] >= 0) & (b["segment_ids"] > 0)))
        total, grads = 0.0, None
        for tok, lab in groups(documents(b)):
            nll, g = group_grads(params, jnp.asarray(tok), jnp.asarray(lab))
            total += float(nll)
            grads = g if grads is None else add(grads, g)
            del g
        if m is None:
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
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
    d, F, lo = cfg["d_model"], cfg["d_ff"], cfg["rwkv_lora_dim"]
    time_mix = 5 * d * d                # r, k, v, g, output
    time_mix += 5 * 2 * d * MIX_LORA    # the token-shift LoRAs, A and B
    time_mix += 2 * d * lo              # the decay LoRA, A and B
    channel_mix = 2 * d * F + d * d     # key, value; receptance
    return cfg["num_layers"] * (time_mix + channel_mix) \
        + d * cfg["vocab_size"]


def recurrence_flops(cfg: dict) -> int:
    """Forward FLOPs of the WKV recurrence for one token of one layer, all
    heads, on a hd x hd state per head."""
    hd = cfg["rwkv_head_dim"]
    kv = hd * hd             # k_t^T v_t: a multiply per state entry
    read = 2 * hd * hd       # r_t S_{t-1}: a multiply and an add per entry
    bonus = 4 * hd           # (r_t . (u * k_t)) v_t: 3 hd, then hd
    decay = 2 * hd * hd      # diag(w_t) S_{t-1} + k_t^T v_t
    return cfg["d_model"] // hd * (kv + read + bonus + decay)


def doc_flops(cfg: dict, length: int) -> float:
    """Training FLOPs of one document of ``length`` tokens: 6 per weight
    and token, and 3 times the recurrence's forward FLOPs per token and
    layer.  Padding, recomputation and the chunked form's extra work
    inside a chunk do not count."""
    return 6.0 * matmul_weights(cfg) * length \
        + 3.0 * cfg["num_layers"] * recurrence_flops(cfg) * length
