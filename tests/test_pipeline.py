"""Pipelined planning path (docs/PERFORMANCE.md): plan-ahead prefetch,
fan-out collect over merged ``snapshot`` RPCs, batched ``ingest``, and
the recovery semantics that keep all of it exactly-once.

Live tests run the real Overlord (threads and all); determinism-critical
behaviors (degraded re-mix, restore invalidation) drive the same plane
synchronously through a handle stand-in, like the golden-trace test.
"""
import time
from concurrent.futures import Future

import pytest

from repro.configs import get_config
from repro.core import (
    ClientPlaceTree, Overlord, OverlordConfig, StaticSchedule,
)
from repro.core.constructor import DataConstructor
from repro.core.planner import Planner
from repro.core.resilience import CircuitBreaker
from repro.core.source_loader import SourceLoader
from repro.core.strategies import STRATEGIES
from repro.data.cost_models import backbone_cost
from repro.data.sources import coyo_like_specs, materialize_group
from repro.telemetry import Telemetry


@pytest.fixture(scope="module")
def source_paths(tmp_path_factory):
    root = tmp_path_factory.mktemp("pipeline_sources")
    return materialize_group(coyo_like_specs(3), str(root))


def mk(source_paths, **kw):
    tree = ClientPlaceTree([("PP", 1), ("DP", 2), ("CP", 1), ("TP", 1)])
    cfg = get_config("qwen3-8b")
    sched = StaticSchedule({f"coyo_{i:03d}": 1.0 for i in range(3)})
    defaults = dict(
        seq_len=256, rows_per_microbatch=2, n_bins=1,
        strategy="backbone_balance",
        strategy_params=dict(costfn=backbone_cost(cfg), broadcast=()))
    defaults.update(kw)
    return Overlord(source_paths, tree, sched,
                    OverlordConfig(**defaults)).start()


def run_steps(ov, lo, hi, compute_s=0.005, timeout=30.0):
    for step in range(lo, hi):
        for r in range(ov.tree.world):
            v = ov.get_batch(step, r, timeout=timeout)
            assert v["role"] in ("data", "metadata", "none")
        ov.step_done(step)
        time.sleep(compute_s)   # the window the planner runs ahead in


# --------------------------------------------------------- live pipeline
def test_plan_ahead_runs_ahead_of_consumer(source_paths):
    """With plan_ahead=2 the planner frontier stays beyond the newest
    consumed step, the prefetch-depth gauge is exported, and steps are
    fully planned BEFORE the trainer asks for them."""
    ov = mk(source_paths, plan_ahead=2, shadows=False)
    try:
        last = 7
        run_steps(ov, 0, last + 1)
        time.sleep(0.5)   # drain the final advance_to cast
        planned = ov.planner.call("planned_through", timeout=10)
        assert planned > last, \
            f"frontier {planned} not ahead of consumer {last}"
        depth = ov.telemetry.registry.gauge_value("planner_prefetch_depth")
        assert depth == depth, "planner_prefetch_depth gauge missing"
        spans = ov.telemetry.tracer.finished()
        names = {s.name for s in spans}
        assert "planner.pipeline" in names

        # at least one prefetched step finished planning strictly before
        # any rank fetched it — planning left the critical path
        plan_end = {}
        for s in spans:
            if s.name == "planner.plan_step" and s.end is not None:
                plan_end[s.attrs.get("step")] = s.end
        fetch_start = {}
        for s in spans:
            if s.name == "overlord.get_batch":
                st = s.attrs.get("step")
                fetch_start[st] = min(fetch_start.get(st, s.start), s.start)
        ahead = [t for t in fetch_start
                 if t in plan_end and plan_end[t] <= fetch_start[t]]
        assert ahead, "no step was planned before its first fetch"
    finally:
        ov.shutdown()


def test_demand_driven_path_delivers(source_paths):
    """plan_ahead=0 plans each step only when a client asks for it.  That
    is the path every step takes on a cold start and after a replan, so
    it must keep delivering on its own."""
    ov = mk(source_paths, plan_ahead=0, shadows=False)
    try:
        run_steps(ov, 0, 3, compute_s=0.0)
        assert ov.planner.call("planned_through", timeout=10) >= 2
    finally:
        ov.shutdown()


def test_ingest_first_plan_wins(source_paths):
    """A replanning planner must not overwrite an assembled step: the
    batched ingest RPC returns False for a step a client may have
    consumed, exactly like expect() did."""
    ov = mk(source_paths, plan_ahead=2, shadows=False)
    try:
        run_steps(ov, 0, 2)
        for b, h in ov.constructors.items():
            assert h.call("ingest", 0, {}, 1, timeout=10) is False
    finally:
        ov.shutdown()


def test_replay_after_recovery_keeps_ledger_clean(source_paths):
    """Planner crash mid-pipeline: prefetched-but-lost plans are replanned,
    already-assembled steps win, and the delivery ledger still proves
    no-loss / no-duplication across the recovery."""
    ov = mk(source_paths, plan_ahead=2, shadows=False,
            ledger=True, planner_ckpt_every=1)
    try:
        run_steps(ov, 0, 4)
        ov.inject_planner_failure()
        time.sleep(0.5)
        run_steps(ov, 4, 8)
        assert any(r["actor"] == "planner" for r in ov.recovery_log)
        report = ov.ledger.verify(strict=True)   # raises on loss/dup
        assert report["delivered"] > 0
    finally:
        ov.shutdown()


# ------------------------------------------------- synchronous unit path
class _SyncHandle:
    """Dispatch on the caller's thread (same stand-in as the golden-trace
    test) so re-mix and restore behavior is deterministic."""

    alive = True

    def __init__(self, actor):
        self._actor = actor
        self.name = getattr(actor, "name", type(actor).__name__)

    def call(self, method, *args, timeout=None, retry=None, **kwargs):
        return getattr(self._actor, method)(*args, **kwargs)

    def call_async(self, method, *args, **kwargs):
        fut = Future()
        try:
            fut.set_result(getattr(self._actor, method)(*args, **kwargs))
        except Exception as e:
            fut.set_exception(e)
        return fut

    def cast(self, method, *args, **kwargs):
        getattr(self._actor, method)(*args, **kwargs)


def build_sync_plane(tmpdir, n_sources=3, plan_ahead=2):
    tel = Telemetry(enabled=True, seed=0)
    paths = materialize_group(coyo_like_specs(n_sources, seed=11),
                              str(tmpdir))
    tree = ClientPlaceTree([("PP", 1), ("DP", 2), ("CP", 1), ("TP", 1)])
    sched = StaticSchedule({s: 1.0 for s in paths})
    loaders, raw = {}, {}
    for src, path in sorted(paths.items()):
        loader = SourceLoader(src, path, (0, 1), workers=1,
                              buffer_target=64, seed=3, telemetry=tel)
        loader.name = f"loader:{src}:0of1"
        loader.on_start()
        loaders[loader.name] = _SyncHandle(loader)
        raw[src] = loader
    constructors = {
        b: _SyncHandle(DataConstructor(b, tree, seq_len=128,
                                       rows_per_microbatch=2, n_bins=1,
                                       telemetry=tel))
        for b in range(tree.buckets("DP"))}
    planner = Planner(
        tree, sched, STRATEGIES["backbone_balance"],
        dict(costfn=backbone_cost(get_config("qwen3-8b")), broadcast=(),
             n_bins=1),
        loaders=loaders, constructors=constructors,
        samples_per_step=8, seed=5, plan_ahead=plan_ahead, telemetry=tel)
    return planner, raw, tel


def test_snapshot_routes_weight_away_from_open_breaker(tmp_path):
    """The merged snapshot RPC still carries the degradation signal: a
    source whose circuit breaker is open gets zero weight in the re-mixed
    schedule and contributes no samples to the plan."""
    planner, raw, tel = build_sync_plane(tmp_path)
    broken = sorted(raw)[0]
    raw[broken].breaker = CircuitBreaker(1, 999.0)
    raw[broken].breaker.record_failure()
    assert raw[broken].breaker.state == "open"

    meta, owner, degraded = planner._collect_buffers()
    assert degraded == {broken}
    assert any(m["source"] == broken for m in meta)   # buffered, not used

    planner.ensure_planned(2)
    log = planner.degraded_log()
    assert log and all(d["degraded"] == [broken] for d in log)
    assert tel.registry.counter_value(
        "planner_samples_planned_total", source=broken) == 0.0
    healthy = [s for s in raw if s != broken]
    assert sum(tel.registry.counter_value(
        "planner_samples_planned_total", source=s) for s in healthy) > 0
    hist = planner.history_window()
    broken_loader = f"loader:{broken}:0of1"
    for per_loader in hist.values():
        assert not per_loader.get(broken_loader)


def test_restore_invalidates_prefetched_history(tmp_path):
    """A recovered planner must not trust plans the dead incarnation
    prefetched past the restored checkpoint: restore_state drops history
    beyond planned_through and ensure_planned replans forward."""
    planner, raw, tel = build_sync_plane(tmp_path)
    planner.ensure_planned(1)
    planner.advance_to(5)          # prefetch frontier well ahead
    assert planner.planned_through() == 5
    state = planner.checkpoint_state()
    # simulate the crash window: the checkpoint is authoritative only
    # through step 2, but its history pickle carries prefetched steps
    state["planned_through"] = 2
    planner.restore_state(state)
    assert planner.planned_through() == 2
    assert max(planner.history_window()) <= 2
    # the new incarnation replans forward from the restored frontier
    planner.ensure_planned(4)
    assert planner.planned_through() == 4
    assert 4 in planner.history_window()


def test_prefetch_depth_gauge_tracks_frontier(tmp_path):
    """planner_prefetch_depth = planned_through - last_requested: positive
    while the pipeline runs ahead, 0 when planning is demand-driven."""
    planner, raw, tel = build_sync_plane(tmp_path)
    planner.ensure_planned(0)
    assert tel.registry.gauge_value("planner_prefetch_depth") == 0.0
    planner.advance_to(3)
    assert tel.registry.gauge_value("planner_prefetch_depth") == 3.0
