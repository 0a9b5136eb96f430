"""Data constructor packing time (``constructor.assemble`` spans) per
window step, in ms."""
from bench.metrics._spans import ms_per_step


def read(w):
    return ms_per_step(w, "constructor.assemble")
