"""The data plane's thread CPU time per window step, in ms: ``Span.cpu`` of
the same spans the wall-time readers sum (``loader.refill``,
``loader.prepare``, ``planner.plan_step``, ``constructor.assemble``), so
wall less CPU is the plane's time off the CPU.  None when none of those
spans carries a CPU time (a program whose spans record none)."""

NAMES = ("loader.refill", "loader.prepare", "planner.plan_step",
         "constructor.assemble")


def read(w):
    spans = [s for s in w.spans if s.name in NAMES]
    if not w.steps or not spans or \
            any(getattr(s, "cpu", None) is None for s in spans):
        return None
    return sum(s.cpu for s in spans) * 1e3 / len(w.steps)
