"""The training loop's host time per window step, in ms: each window
``trainer.step`` span's duration less those of its ``trainer.dispatch``
and ``trainer.wait`` children.  The device runs the loop's step from
about a millisecond into the dispatch to the end of the wait; what is
left is fetch, host-to-device copy, readback and ``step_done``, the
loop's work in which the device has no step of this loop to run.  None
when the window holds no ``trainer.step`` span."""

DEVICE_BUSY = ("trainer.dispatch", "trainer.wait")


def loop_steps(w):
    """[(trainer.step span, {child name: [child spans]})] of the window;
    children whose ``trainer.step`` started before the window are left
    out with it."""
    steps = {s.span_id: (s, {}) for s in w.spans if s.name == "trainer.step"}
    for s in w.spans:
        if s.parent_id in steps:
            steps[s.parent_id][1].setdefault(s.name, []).append(s)
    return list(steps.values())


def host_part(step, kids, field):
    """The step span's ``field`` (duration or cpu) less its dispatch's and
    wait's."""
    return getattr(step, field) - sum(getattr(k, field)
                                      for name in DEVICE_BUSY
                                      for k in kids.get(name, ()))


def read(w):
    steps = loop_steps(w)
    if not steps or not w.steps:
        return None
    return sum(host_part(s, k, "duration") for s, k in steps) * 1e3 \
        / len(w.steps)
