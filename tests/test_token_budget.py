"""Token-budget step sizing (``samples_per_step=0``).

The planner draws packed tokens up to ``fill_factor`` of a step's
capacity and defers what the packer's first-fit would not place, so no
sample is dropped.  Each test runs the data plane synchronously (one
thread, ``_SyncHandle``) over coyo-like lengths and a heavy-tailed text
mix, on one and two DP buckets.
"""
import dataclasses
from concurrent.futures import Future

import pytest

from repro.chaos.ledger import DeliveryLedger
from repro.configs import get_config
from repro.core.constructor import DataConstructor
from repro.core.mixing import StaticSchedule
from repro.core.placetree import ClientPlaceTree
from repro.core.planner import Planner
from repro.core.source_loader import SourceLoader
from repro.core.strategies import STRATEGIES
from repro.data.cost_models import backbone_cost
from repro.data.packing import first_fit, packed_length
from repro.data.sources import SourceSpec, coyo_like_specs, materialize_group
from repro.telemetry import Telemetry

# fill 0.9 of short rows: every layout below overflows some bins
SEQ_LEN, ROWS, BINS, FILL = 256, 2, 2, 0.9
STEPS = 110


class _SyncHandle:
    alive = True

    def __init__(self, actor):
        self._actor = actor
        self.name = getattr(actor, "name", type(actor).__name__)

    def call(self, method, *args, timeout=None, retry=None, **kwargs):
        return getattr(self._actor, method)(*args, **kwargs)

    def call_async(self, method, *args, **kwargs):
        fut = Future()
        fut.set_result(getattr(self._actor, method)(*args, **kwargs))
        return fut

    def cast(self, method, *args, **kwargs):
        getattr(self._actor, method)(*args, **kwargs)


def coyo_specs():
    # bench/traffic/coyo5.json's length parameters, text up to 8192
    return [dataclasses.replace(s, n_samples=4096)
            for s in coyo_like_specs(5, seed=0)]


def heavy_specs():
    # lognormal sigma 2.2: most texts are a few tokens, a few pass seq_len
    return [SourceSpec(f"heavy_{i}", "text", n_samples=4096,
                       text_mu=2.5 + 0.3 * i, text_sigma=2.2, seed=40 + i)
            for i in range(3)]


MIXES = {"coyo5": coyo_specs, "heavy": heavy_specs}


class Plane:
    """Loaders -> Planner -> Constructors on one thread, with a ledger."""

    def __init__(self, root, mix: str, dp: int, samples_per_step: int = 0):
        paths = materialize_group(MIXES[mix](), str(root))
        self.tree = ClientPlaceTree([("PP", 1), ("DP", dp), ("CP", 1),
                                     ("TP", 1)])
        self.ledger = DeliveryLedger()
        self.tel = Telemetry(enabled=True, seed=0)
        self.loaders = {}
        for src, path in sorted(paths.items()):
            loader = SourceLoader(src, path, (0, 1), workers=1,
                                  buffer_target=64, seed=3,
                                  telemetry=self.tel)
            loader.name = f"loader:{src}:0of1"
            loader.on_start()
            self.loaders[loader.name] = _SyncHandle(loader)
        self.constructors = {
            b: _SyncHandle(DataConstructor(b, self.tree, SEQ_LEN, ROWS,
                                           BINS, ledger=self.ledger,
                                           telemetry=self.tel))
            for b in range(dp)}
        self.budget = int(FILL * dp * BINS * ROWS * SEQ_LEN)
        self.planner = Planner(
            self.tree, StaticSchedule({s: 1.0 for s in paths}),
            STRATEGIES["backbone_balance"],
            dict(costfn=backbone_cost(get_config("qwen3-8b")),
                 broadcast=(), n_bins=BINS),
            loaders=self.loaders, constructors=self.constructors,
            samples_per_step=samples_per_step, budget_tokens=self.budget,
            seq_len=SEQ_LEN, rows=ROWS, seed=5, ledger=self.ledger,
            telemetry=self.tel)
        self.longest: list[int] = []    # per planned step, in plan order
        self.deferred: dict[int, set] = {}
        self.predicted: dict[int, dict] = {}   # step -> bucket -> rows
        collect, defer = self.planner._collect_buffers, \
            self.planner._defer_overflow

        def collect_spy():
            meta, owner, degraded = collect()
            self.longest.append(max(packed_length(m["text_tokens"], SEQ_LEN)
                                    for m in meta))
            return meta, owner, degraded

        def defer_spy(plan, order, meta):
            before = {e.sample_id for e in plan.entries}
            n = defer(plan, order, meta)
            self.deferred[plan.step] = before - {e.sample_id
                                                 for e in plan.entries}
            assert len(self.deferred[plan.step]) == n
            length = {m["sample_id"]: packed_length(m["text_tokens"],
                                                    SEQ_LEN) for m in meta}
            per = self.predicted[plan.step] = {}
            for b, pairs in order.items():
                per[b] = []
                for k in range(plan.bins):
                    ids = [e.sample_id for _, e in pairs if e.bin == k]
                    rows = [[] for _ in range(ROWS)]
                    for i, r in zip(ids, first_fit(
                            [length[i] for i in ids], SEQ_LEN, ROWS)):
                        rows[r].append(i)
                    per[b].append(rows)
            return n

        self.planner._collect_buffers = collect_spy
        self.planner._defer_overflow = defer_spy

    def deliver(self, step: int):
        """Every rank takes its view of ``step``, as a client would."""
        self.planner.ensure_planned(step)
        for rank in range(self.tree.world):
            bucket = self.tree.coords(rank)["DP"]
            view = self.constructors[bucket].call("get_view", step, rank)
            assert view is not None and view["role"] == "data"
            if step in self.predicted:     # the packer placed as planned
                assert [b.doc_ids for b in view["bins"]] == \
                    self.predicted[step].get(bucket, [[[]] * ROWS] * BINS)
            self.ledger.record_delivered(
                step, rank, bucket,
                [i for b in view["bins"] for row in b.doc_ids for i in row])
        for c in self.constructors.values():
            c.call("pop_step", step)

    def plan_spans(self) -> list:
        return [s for s in self.tel.tracer.finished()
                if s.name == "planner.plan_step"]

    def dropped(self) -> float:
        reg = self.tel.registry
        return sum(reg.counter_value("constructor_dropped_total", bucket=b,
                                     reason=r)
                   for b in self.constructors
                   for r in ("packing_overflow", "queue_evicted"))

    def close(self):
        for h in self.loaders.values():
            h.call("on_stop")


LAYOUTS = pytest.mark.parametrize("mix,dp", [
    ("coyo5", 1), ("coyo5", 2), ("heavy", 1), ("heavy", 2)])


@LAYOUTS
def test_budget_bounds_each_step_draw(tmp_path, mix, dp):
    plane = Plane(tmp_path, mix, dp)
    try:
        for step in range(STEPS):
            plane.deliver(step)
    finally:
        plane.close()
    spans = sorted(plane.plan_spans(), key=lambda s: s.attrs["step"])
    assert [s.attrs["step"] for s in spans] == list(range(STEPS))
    for span, longest in zip(spans, plane.longest):
        drawn = span.attrs["drawn_tokens"]
        assert span.attrs["budget_tokens"] == plane.budget
        assert plane.budget - longest <= drawn <= plane.budget, span.attrs


@LAYOUTS
def test_deferral_drops_nothing_and_delivers_once(tmp_path, mix, dp):
    plane = Plane(tmp_path, mix, dp)
    try:
        for step in range(STEPS):
            plane.deliver(step)
    finally:
        plane.close()
    assert plane.dropped() == 0.0
    summary = plane.ledger.verify(strict=True)   # no loss, no duplicate
    assert summary["dropped"] == 0
    n = sum(len(ids) for ids in plane.deferred.values())
    assert plane.tel.registry.counter_value("planner_deferred_total") == n
    assert sum(s.attrs["deferred"] for s in plane.plan_spans()) == n
    assert n > 0
    delivered = plane.ledger.snapshot()["delivered"]
    for step, ids in plane.deferred.items():
        for sid in ids:
            if step < STEPS - 10:
                assert sid in delivered, (step, sid)
            if sid in delivered:
                assert len(delivered[sid]) == 1
                assert delivered[sid][0] > step


@LAYOUTS
def test_replan_draws_the_same_sample_ids(tmp_path, mix, dp):
    plane = Plane(tmp_path, mix, dp)
    try:
        for step in range(20):
            plane.deliver(step)
        loaders = {n: h.call("checkpoint_state")
                   for n, h in plane.loaders.items()}
        planner = plane.planner.checkpoint_state()
        plane.planner.ensure_planned(21)
        first = {s: plane.planner.history_window()[s] for s in (20, 21)}
        for n, h in plane.loaders.items():
            h.call("restore_state", loaders[n])
        plane.planner.restore_state(planner)
        plane.planner.ensure_planned(21)
        again = {s: plane.planner.history_window()[s] for s in (20, 21)}
    finally:
        plane.close()
    assert first == again
    assert all(ids for per in first.values() for ids in per.values())


@LAYOUTS
def test_explicit_count_draws_exactly_that_count(tmp_path, mix, dp):
    plane = Plane(tmp_path, mix, dp, samples_per_step=12)
    try:
        for step in range(30):
            plane.planner.ensure_planned(step)
    finally:
        plane.close()
    hist = plane.planner.history_window()
    assert sorted(hist) == list(range(30))
    for per_loader in hist.values():
        assert sum(len(ids) for ids in per_loader.values()) == 12
    assert plane.deferred == {}             # count mode defers nothing
    for span in plane.plan_spans():
        assert set(span.attrs) == {"step"}
