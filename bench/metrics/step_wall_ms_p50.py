"""Median ``step_s`` of the window's steps (host-to-device copy and the
step, ending in ``block_until_ready``), in ms."""
import statistics


def read(w):
    return statistics.median(r["step_s"] for r in w.steps) * 1e3
