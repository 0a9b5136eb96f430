"""Trained label tokens (labels >= 0 and segment id > 0, the trainer's
``host_tokens``) of every step done in the window, over the window's
seconds.  All chips' rows count: the global batch is one step."""


def read(w):
    return sum(r["host_tokens"] for r in w.steps) / w.window_s
