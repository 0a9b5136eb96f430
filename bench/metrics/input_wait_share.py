"""Share of the window the loop spent in ``Trainer.fetch`` (the sum of
its ``fetch_s``), %."""


def read(w):
    return 100.0 * sum(r["fetch_s"] for r in w.steps) / w.window_s
