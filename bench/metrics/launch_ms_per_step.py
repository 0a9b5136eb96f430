"""The batch's host-to-device copy per window step, in ms: the
``trainer.put`` children of the window's ``trainer.step`` spans.  The
step's dispatch is left out: the device starts the step about a
millisecond into it, so the rest of the call overlaps the device's work.
None when the window holds no ``trainer.step`` span."""
from bench.metrics.loop_host_ms_per_step import loop_steps


def read(w):
    steps = loop_steps(w)
    if not steps or not w.steps:
        return None
    return sum(c.duration for _, kids in steps
               for c in kids.get("trainer.put", ())) * 1e3 / len(w.steps)
