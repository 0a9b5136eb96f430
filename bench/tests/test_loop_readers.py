"""The training-loop readers (``loop_host_ms_per_step``,
``loop_offcpu_ms_per_step``, ``launch_ms_per_step``,
``plane_cpu_ms_per_step``) against a hand-built window of known spans."""
import pytest

from bench import harness
from repro.telemetry.tracing import Span

_ids = iter(range(1, 10_000))


def span(name, start, end, cpu, parent=None, thread="MainThread", **attrs):
    s = Span(next(_ids), parent.span_id if parent else None, name, attrs,
             thread, start)
    s.end, s.cpu = end, cpu
    return s


def loop_step(step, t):
    """One loop iteration of 0.5 s starting at ``t``: fetch 0.01, put 0.02,
    dispatch 0.03, wait 0.4, readback 0.01, step_done 0.03; the thread ran
    0.05 s of it, of which 0.01 in the wait."""
    st = span("trainer.step", t, t + 0.5, 0.05, step=step)
    kids, at = [], t
    for name, dur, cpu in (("trainer.fetch", 0.01, 0.005),
                           ("trainer.put", 0.02, 0.01),
                           ("trainer.dispatch", 0.03, 0.01),
                           ("trainer.wait", 0.4, 0.01),
                           ("trainer.readback", 0.01, 0.005),
                           ("overlord.step_done", 0.03, 0.01)):
        kids.append(span(name, at, at + dur, cpu, parent=st))
        at += dur
    return [st] + kids


def window(spans, n_steps):
    return harness.Window(
        conf={}, chips=1, device_kind="cpu", setup_s=1.0,
        window_s=0.5 * n_steps, steps=[{"step": i} for i in range(n_steps)],
        batches={}, spans=spans, rss_start_bytes=0, rss_peak_bytes=0,
        plane_bytes=0.0, trace=None)


def plane_spans(t):
    """Plane spans of one step: wall 0.2 s on other threads, 0.08 s CPU."""
    return [span("loader.refill", t, t + 0.05, 0.02, thread="loader-a"),
            span("loader.prepare", t, t + 0.05, 0.03, thread="loader-a"),
            span("planner.plan_step", t, t + 0.09, 0.02, thread="planner"),
            span("constructor.assemble", t, t + 0.01, 0.01,
                 thread="constructor"),
            span("actor.exec", t, t + 0.3, 0.2, thread="planner")]


@pytest.fixture
def w():
    # the step before the window: its trainer.step started earlier, so
    # only children that started inside the window are in the list
    before = loop_step(9, -0.005)
    spans = [s for s in before[1:] if s.start >= 0]
    for i in range(2):
        spans += loop_step(10 + i, 0.05 + 0.5 * i)
        spans += plane_spans(0.05 + 0.5 * i)
    return window(spans, 2)


def test_loop_host_ms_per_step(w):
    # 0.5 s iteration less the 0.03 s dispatch and 0.4 s wait, on each of
    # the 2 steps
    assert harness.reader("loop_host_ms_per_step")(w) == pytest.approx(70.0)


def test_loop_offcpu_ms_per_step(w):
    # host part 0.07 s, of which the thread ran 0.05 - 0.01 - 0.01 = 0.03 s
    assert harness.reader("loop_offcpu_ms_per_step")(w) == \
        pytest.approx(40.0)


def test_launch_ms_per_step(w):
    # the 0.02 s put alone
    assert harness.reader("launch_ms_per_step")(w) == pytest.approx(20.0)


def test_plane_cpu_ms_per_step(w):
    # the four named spans' CPU, not actor.exec's
    assert harness.reader("plane_cpu_ms_per_step")(w) == pytest.approx(80.0)


@pytest.mark.parametrize("metric", [
    "loop_host_ms_per_step", "loop_offcpu_ms_per_step",
    "launch_ms_per_step", "plane_cpu_ms_per_step"])
def test_silent_without_loop_spans(metric):
    # a program that writes no trainer.* span: its plane spans carry no
    # CPU time to read
    spans = [s for s in plane_spans(0.0)]
    for s in spans:
        del s.cpu
    assert harness.reader(metric)(window(spans, 2)) is None
    assert harness.reader(metric)(window([], 2)) is None
