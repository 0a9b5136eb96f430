"""Live (query-block, key-block) attention tiles over all tiles of the
window's steps, %: the share of its packed rows' attention the train step
computes.  Read from ``Trainer.history`` (``attn_tiles_live`` and
``attn_tiles_total``, counted by the train step from the attention
kernel's live ranges; the jnp path counts every tile live).  None where
the history carries no tile counts."""


def read(w):
    steps = [r for r in w.steps if "attn_tiles_total" in r]
    total = sum(r["attn_tiles_total"] for r in steps)
    if not total:
        return None
    return 100.0 * sum(r["attn_tiles_live"] for r in steps) / total
