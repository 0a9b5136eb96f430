"""The training loop's host time per window step that its thread spent off
the CPU, in ms: for each window ``trainer.step`` span, its duration less
its ``trainer.dispatch`` and ``trainer.wait`` children's, less the same
difference of their thread CPU times (``Span.cpu``).  Time the loop
waited, while the device had no step of it to run, for the interpreter
lock, the runtime or I/O.  None when the window holds no
``trainer.step`` span."""
from bench.metrics.loop_host_ms_per_step import host_part, loop_steps


def read(w):
    steps = loop_steps(w)
    if not steps or not w.steps:
        return None
    return sum(host_part(s, k, "duration") - host_part(s, k, "cpu")
               for s, k in steps) * 1e3 / len(w.steps)
