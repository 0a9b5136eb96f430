"""Process start to the first timed step: imports, sources, Overlord
start, weights, compilation and the warm-up steps."""


def read(w):
    return w.setup_s
