"""Shared by the span readers: milliseconds per window step that spans of
the given names took, from the telemetry spans that started inside the
window.  None when the tracer kept none of them."""


def ms_per_step(w, *names):
    spans = [s for s in w.spans if s.name in names]
    if not spans or not w.steps:
        return None
    return sum(s.duration for s in spans) * 1e3 / len(w.steps)
