"""The training loop's spans: thread CPU time on every span, the profiler
mirror, the ``trainer.*`` tree of a reduced run on the CPU, the spans'
close on an exception, their events in a profiler trace, and the named
scopes in the lowered train step."""
import glob
import re
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch import train
from repro.models.model_zoo import build_model
from repro.telemetry import Tracer
from repro.train.train_step import abstract_train_state, make_train_step
from repro.train.trainer import Trainer

LOOP_CHILDREN = ["trainer.fetch", "trainer.put", "trainer.dispatch",
                 "trainer.wait", "trainer.readback", "overlord.step_done"]


def test_span_cpu_tells_sleep_from_work():
    tr = Tracer()
    with tr.span("sleep") as slept:
        time.sleep(0.2)
    with tr.span("busy") as busy:
        t_end = time.perf_counter() + 0.2
        x = 0
        while time.perf_counter() < t_end:
            x += 1
    assert slept.cpu < 0.1 * slept.duration
    assert busy.cpu > 0.5 * busy.duration
    assert slept.as_dict()["cpu"] == slept.cpu


def test_mirror_enters_and_exits_once_per_span_nested():
    events = []

    class Mirror:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            events.append(("enter", self.name, self.attrs))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    tr = Tracer()
    tr.mirror = Mirror
    with tr.span("outer", step=3):
        with tr.span("inner", source="a"):
            pass
        with pytest.raises(KeyError):
            with tr.span("failing"):
                raise KeyError
    assert events == [("enter", "outer", {"step": 3}),
                      ("enter", "inner", {"source": "a"}),
                      ("exit", "inner"),
                      ("enter", "failing", {}), ("exit", "failing"),
                      ("exit", "outer")]
    assert tr.find("failing")[0].attrs["error"] == "KeyError"


def _args(steps):
    return train.build_parser().parse_args(
        ["--reduced", "--steps", str(steps), "--seq-len", "64", "--dp", "2",
         "--rows", "1"])


def _trained(steps, wrap_step_done=None):
    """A reduced Trainer run on the CPU, as ``repro.launch.train.run`` runs
    one; returns (finished spans, history, the exception train() raised)."""
    args = _args(steps)
    cfg = train.model_config(args)
    raised = None
    with train.overlord_for(args, cfg) as ov:
        trainer = Trainer(build_model(cfg), ov, train.trainer_config(args))
        if wrap_step_done is not None:
            ov.step_done = wrap_step_done(ov.step_done)
        try:
            trainer.train()
        except Exception as exc:    # noqa: BLE001 - returned to the test
            raised = exc
        spans = ov.telemetry.tracer.finished()
        history = list(trainer.history)
    return spans, history, raised


def test_trainer_spans_tree_per_step():
    spans, history, raised = _trained(2)
    assert raised is None and len(history) == 2
    by_id = {s.span_id: s for s in spans}
    steps = [s for s in spans if s.name == "trainer.step"]
    assert [s.attrs["step"] for s in steps] == [0, 1]
    for st in steps:
        kids = sorted((s for s in spans if s.parent_id == st.span_id),
                      key=lambda s: s.start)
        names = [k.name for k in kids if k.name != "trainer.compile"]
        assert names == LOOP_CHILDREN
        for k in kids:
            assert st.start <= k.start <= k.end <= st.end
            assert k.thread == st.thread
            assert 0 <= k.cpu <= k.duration + 1e-3
        fetch = next(k for k in kids if k.name == "trainer.fetch")
        gets = [s for s in spans if s.name == "overlord.get_batch"
                and s.attrs["step"] == st.attrs["step"]]
        assert gets and all(by_id[g.parent_id] is fetch for g in gets)
    compiles = [s for s in spans if s.name == "trainer.compile"]
    assert len(compiles) == 1
    assert by_id[compiles[0].parent_id] is steps[0]
    assert [h["step"] for h in history] == [0, 1]
    for h in history:
        assert h["tokens"] == h["host_tokens"] > 0


class StopLoop(Exception):
    pass


def test_exception_from_step_done_closes_every_loop_span():
    def wrap(step_done):
        def raising(step, metrics=None):
            step_done(step, metrics)
            if step == 1:
                raise StopLoop
        return raising

    spans, history, raised = _trained(4, wrap_step_done=wrap)
    assert isinstance(raised, StopLoop)
    assert [h["step"] for h in history] == [0, 1]
    loop = [s for s in spans if s.name.startswith("trainer.")]
    assert all(s.end is not None for s in loop)
    steps = [s for s in spans if s.name == "trainer.step"]
    assert [s.attrs["step"] for s in steps] == [0, 1]
    assert "error" not in steps[0].attrs
    assert steps[1].attrs["error"] == "StopLoop"


def test_profiler_trace_holds_loop_and_plane_spans(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans, history, raised = _trained(3)
    finally:
        jax.profiler.stop_trace()
    assert raised is None
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path
    names = set()
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"trainer.step", "trainer.wait", "planner.plan_step"} <= names


def test_lowered_step_names_its_blocks():
    cfg = train.model_config(_args(1))
    model = build_model(cfg)
    b, s = 2, 64
    batch = {k: jax.ShapeDtypeStruct((b, s), np.int32)
             for k in ("tokens", "segment_ids", "positions", "labels")}
    text = jax.jit(make_train_step(model)).lower(
        abstract_train_state(model), batch).as_text(debug_info=True)
    # a scope is one component of an op's name path, such as
    # "jit(train_step)/jvp(loss)/ge" or ".../rematted_computation/ffn/mul"
    for scope in ("attention", "ffn", "loss", "optimizer"):
        assert re.search(rf'loc\("[^"]*[/(]{scope}[/)]', text), scope


def test_lowered_rwkv6_step_names_its_blocks():
    from repro.configs.rwkv6_3b import reduced
    model = build_model(reduced())
    b, s = 2, 64
    batch = {k: jax.ShapeDtypeStruct((b, s), np.int32)
             for k in ("tokens", "segment_ids", "positions", "labels")}
    text = jax.jit(make_train_step(model)).lower(
        abstract_train_state(model), batch).as_text(debug_info=True)
    for scope in ("timemix", "wkv", "channelmix", "loss", "optimizer"):
        assert re.search(rf'loc\("[^"]*[/(]{scope}[/)]', text), scope
