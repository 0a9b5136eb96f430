"""Label tokens over slots (rows x seq_len) of the window's batches, %."""
import numpy as np


def read(w):
    labels = slots = 0
    for r in w.steps:
        b = w.batches[r["step"]]
        labels += int(np.sum((b["labels"] >= 0) & (b["segment_ids"] > 0)))
        slots += b["labels"].size
    return 100.0 * labels / slots if slots else None
