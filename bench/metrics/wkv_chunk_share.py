"""(row, chunk) pairs of the WKV scan the train step computed over all its
(row, chunk) pairs, over the window's steps, %.  Read from
``Trainer.history`` (``wkv_chunks_live`` and ``wkv_chunks_total``, counted
by the ssm family's train step; the jnp scan computes every chunk, so it
reads 100).  None where the history carries no chunk counts."""


def read(w):
    steps = [r for r in w.steps if "wkv_chunks_total" in r]
    total = sum(r["wkv_chunks_total"] for r in steps)
    if not total:
        return None
    return 100.0 * sum(r["wkv_chunks_live"] for r in steps) / total
