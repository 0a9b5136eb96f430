"""Witness for why rwkv6-3b has no cell yet: the program's RWKV6 lets a
packed document see the one before it.

    python bench/tests/rwkv6_packing_witness.py [--chip]

For three seeds it runs the program's rwkv6 forward (float32 weights and
compute) on one row holding document A then document B, and on rows
holding each alone, and prints the largest logit difference of each
document between the two.  Documents are independent in a packed row
(attention masks by segment, the WKV state resets at a segment start), so
both differences should be 0.  A matches; B does not, because the token
shift (``rwkv._token_shift``) carries A's last token into B's first in
both the time mix and the channel mix.  Default: reduced() widths on the
CPU.  ``--chip``: published widths, 4 layers, on a TPU.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src")]


def main(chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.configs.rwkv6_3b import reduced
    from repro.models.model_zoo import build_model
    if chip:
        cfg = get_config("rwkv6-3b").replace(num_layers=4, vocab_size=8192)
        la, lb, seq = 300, 500, 1024
    else:
        cfg = reduced()
        la, lb, seq = 20, 30, 64
    model = build_model(cfg.replace(remat="none"))
    fwd = jax.jit(model.forward)

    def row(docs):
        t = np.zeros((1, seq), np.int32)
        s, p = np.zeros_like(t), np.zeros_like(t)
        at = 0
        for k, d in enumerate(docs, 1):
            t[0, at:at + len(d)], s[0, at:at + len(d)] = d, k
            p[0, at:at + len(d)] = np.arange(len(d))
            at += len(d)
        return {"tokens": t, "segment_ids": s, "positions": p}

    print("device", jax.devices()[0].device_kind)
    for seed in (0, 1, 2):
        params = model.init(jax.random.key(seed), jnp.float32)
        rng = np.random.default_rng(seed)
        a = rng.integers(1, cfg.vocab_size, la)
        b = rng.integers(1, cfg.vocab_size, lb)
        both = fwd(params, row([a, b]))[0][0]
        alone_a = fwd(params, row([a]))[0][0]
        alone_b = fwd(params, row([b]))[0][0]
        da = float(jnp.max(jnp.abs(both[:la] - alone_a[:la])))
        db = float(jnp.max(jnp.abs(both[la:la + lb] - alone_b[:lb])))
        scale = float(jnp.max(jnp.abs(alone_b[:lb])))
        print(f"seed {seed}: doc A max|diff| {da:.6g}, doc B max|diff| "
              f"{db:.6g}, logit scale {scale:.6g}")


if __name__ == "__main__":
    main("--chip" in sys.argv[1:])
