"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run, named by its HLO text (``%fusion.3 = ...``).
The harness's host annotations (``bench.*``) are events on the host
plane's threads.  Both are on the trace's clock, in nanoseconds.

* busy: the union of a device's op intervals inside the window;
* idle share: 1 - busy / window, averaged over the devices;
* top ops: device seconds per op name, summed over devices / devices;
* idle gaps: each gap between ops on the first device, named by the host
  annotation that overlaps it most (``host`` when none does).
"""
from __future__ import annotations

import glob
import os
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> dict:
    """{"devices": {plane: [(start_ns, end_ns, op)]},
        "annotations": [(start_ns, end_ns, name)]}"""
    from jax.profiler import ProfileData
    devices: dict[str, list] = {}
    notes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        notes.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return {"devices": devices, "annotations": sorted(notes)}


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """Device metrics over the window the host annotations span; None
    when the trace holds no device op or no annotation."""
    notes = trace["annotations"]
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not notes or not devices:
        return None
    lo = min(a for a, _, _ in notes)
    hi = max(b for _, b, _ in notes)
    window = hi - lo
    if window <= 0:
        return None
    busy, per_op = [], {}
    first = None
    for name in sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):])):
        ops = devices[name]
        merged = union(clip([(a, b) for a, b, _ in ops], lo, hi))
        busy.append(total(merged))
        if first is None:
            first = merged
        for a, b, n in ops:
            dur = min(b, hi) - max(a, lo)
            if dur > 0:
                per_op[n] = per_op.get(n, 0.0) + dur
    n_dev = len(devices)
    gaps = []
    for (_, a), (b, _) in zip(first, first[1:]):
        gaps.append((a, b))
    if first:
        gaps = [(lo, first[0][0])] + gaps + [(first[-1][1], hi)]
    named = []
    for a, b in gaps:
        if b <= a:
            continue
        best, best_overlap = "host", 0.0
        for c, d, n in notes:
            ov = min(b, d) - max(a, c)
            if ov > best_overlap:
                best, best_overlap = n, ov
        named.append((best, (b - a) * 1e-9))
    named.sort(key=lambda x: -x[1])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "devices": n_dev,
        "window_s": window * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "idle_share": 1.0 - sum(busy) / n_dev / window,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in ops_sorted[:top]],
        "idle_gaps": [[n, s] for n, s in named[:top]],
    }
