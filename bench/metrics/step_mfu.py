"""Model FLOPs of the documents trained in the traced window
(bench/flops.py) over the device's busy time in that window (the trace's
union of op intervals, averaged over the chips) x chips x the chip's bf16
peak (bench/peaks.json), %.  Padding and recomputation do not count, and
neither do the host's gaps between steps, which ``device_idle_share``
reads.  None without a device trace."""
from bench.flops import batch_flops, peak_flops


def read(w):
    if w.trace is None:
        return None
    done = sum(batch_flops(w.conf, w.batches[r["step"]]["segment_ids"])
               for r in w.steps)
    return 100.0 * done / (w.trace["busy_s"] * w.chips
                           * peak_flops(w.device_kind))
