"""repro.analysis: static data-plane linter + actor-concurrency analyzer.

Each rule family gets at least one true-positive (seeded-bad input is
caught) and one true-negative (shipped/valid input is clean).
"""
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    AnalysisError, Report, Severity, lint_actor_source, lint_dgraph,
    lint_model_config, lint_observability_source, lint_overlord_config,
    lint_perf_source, lint_shipped_model_configs, lint_strategies,
    lint_strategy, validate_launch,
)
from repro.analysis.lint import main as lint_main
from repro.configs import get_config
from repro.core import (
    ClientPlaceTree, Overlord, OverlordConfig, StaticSchedule,
)
from repro.core.dgraph import BINNED, DGraph, SELECTED
from repro.core.primitives import LoadingPlan
from repro.core.strategies import STRATEGIES
from repro.data.cost_models import backbone_cost


def _meta(n):
    return [{"sample_id": f"s{i}", "source": f"src{i % 2}",
             "text_tokens": 8 + i, "image_tokens": 0} for i in range(n)]


def tree4():
    return ClientPlaceTree([("PP", 1), ("DP", 4), ("CP", 1), ("TP", 1)])


def rules(report):
    return {f.rule for f in report.findings}


# =====================================================================
# pipeline family: DGraph lifecycle (DG1xx)
# =====================================================================

def full_lifecycle_graph(n=8, buckets=2, bins=2):
    g = DGraph.from_buffer(_meta(n))
    g.mark(g.nodes, SELECTED, "mix")
    g.with_cost(lambda m: float(m["text_tokens"]))
    g.assign_buckets([i % buckets for i in range(n)])
    for _, nodes in g.by_bucket().items():
        g.assign_bins(nodes, [i % bins for i in range(len(nodes))])
    return g


def test_dgraph_clean_lifecycle_is_clean():          # true negative
    g = full_lifecycle_graph()
    rep = lint_dgraph(g, n_buckets=2, n_bins=2)
    assert rep.ok and len(rep) == 0


def test_dgraph_detects_state_regression():          # DG102
    g = full_lifecycle_graph()
    g.mark(g.nodes[:1], SELECTED, "mix")             # BINNED -> SELECTED
    rep = lint_dgraph(g)
    assert "DG102" in rules(rep) and not rep.ok


def test_dgraph_detects_unknown_state():             # DG101
    g = DGraph.from_buffer(_meta(2))
    g.nodes[0].state = "teleported"
    rep = lint_dgraph(g)
    assert "DG101" in rules(rep)


def test_dgraph_detects_orphan_membership():         # DG103
    g = DGraph.from_buffer(_meta(4))
    g.mark(g.nodes, SELECTED, "mix")
    g.nodes[0].bin = 1                               # bin without bucket
    rep = lint_dgraph(g)
    assert "DG103" in rules(rep)
    g2 = full_lifecycle_graph()
    g2.nodes[0].bucket = None                        # BINNED but no bucket
    assert "DG103" in rules(lint_dgraph(g2))


def test_dgraph_detects_out_of_range_bucket():       # DG104
    g = full_lifecycle_graph(buckets=4)
    rep = lint_dgraph(g, n_buckets=2)
    assert "DG104" in rules(rep)
    assert lint_dgraph(g, n_buckets=4, n_bins=2).ok


def test_dgraph_detects_parent_cycle_and_dangling():  # DG105 / DG106
    g = DGraph.from_buffer(_meta(3))
    a, b, c = g.nodes
    a.parents = [b.nid]
    b.parents = [a.nid]
    rep = lint_dgraph(g)
    assert "DG105" in rules(rep)
    g2 = DGraph.from_buffer(_meta(2))
    g2.nodes[0].parents = [999]
    assert "DG106" in rules(lint_dgraph(g2))


def test_dgraph_detects_duplicate_sample_ids():      # DG107
    meta = _meta(3)
    meta[2]["sample_id"] = meta[0]["sample_id"]
    rep = lint_dgraph(DGraph.from_buffer(meta))
    assert "DG107" in rules(rep)


def test_dgraph_flags_stragglers():                  # DG108
    g = full_lifecycle_graph(n=6)
    late = DGraph.from_buffer([{"sample_id": "late", "source": "src0",
                                "text_tokens": 9, "image_tokens": 0}])
    late.nodes[0].nid = 99
    late.mark(late.nodes, SELECTED, "mix")
    g.nodes.append(late.nodes[0])                    # stuck at SELECTED
    rep = lint_dgraph(g)
    assert "DG108" in {f.rule for f in rep.warnings}
    assert rep.ok                                    # warning, not error


# =====================================================================
# pipeline family: strategy contracts (ST2xx)
# =====================================================================

def test_shipped_strategies_are_clean():             # true negative
    rep = lint_strategies(STRATEGIES)
    assert rep.ok and len(rep) == 0


def test_strategy_bad_signature():                   # ST201
    def bad(ctx, schedule, total):                   # not keyword-only
        ctx.mix(schedule, total)
        return ctx.plan()
    rep = lint_strategy("bad", bad)
    assert "ST201" in rules(rep)

    def no_total(ctx, *, schedule) -> LoadingPlan:
        ctx.mix(schedule, 1)
        return ctx.plan()
    assert "ST201" in rules(lint_strategy("no_total", no_total))


def test_strategy_missing_mix():                     # ST204
    def skips_mix(ctx, *, schedule, total) -> LoadingPlan:
        ctx.dgraph("main")
        ctx.distribute("DP")
        return ctx.plan()
    rep = lint_strategy("skips_mix", skips_mix)
    assert "ST204" in rules(rep)


def test_strategy_balance_before_distribute():       # ST205
    def inverted(ctx, *, schedule, total, costfn) -> LoadingPlan:
        ctx.mix(schedule, total)
        g = ctx.dgraph("main")
        ctx.cost(costfn, g)
        ctx.balance("greedy_binpack", graph=g)
        ctx.distribute("DP")
        return ctx.plan(g)
    rep = lint_strategy("inverted", inverted)
    assert "ST205" in rules(rep)


def test_strategy_unknown_primitive_typo():          # ST206
    def typo(ctx, *, schedule, total) -> LoadingPlan:
        ctx.mix(schedule, total)
        ctx.distrbute("DP")                          # typo
        return ctx.plan()
    rep = lint_strategy("typo", typo)
    assert "ST206" in rules(rep)


def test_strategy_wrong_return_shape():              # ST203
    def returns_list(ctx, *, schedule, total) -> LoadingPlan:
        ctx.mix(schedule, total)
        return [1, 2, 3]
    rep = lint_strategy("returns_list", returns_list)
    assert "ST203" in rules(rep)


# =====================================================================
# config family (CFG3xx / MDL4xx)
# =====================================================================

def good_overlord_cfg(**kw):
    base = dict(strategy="backbone_balance",
                strategy_params=dict(
                    costfn=backbone_cost(get_config("qwen3-8b")),
                    broadcast=()))
    base.update(kw)
    return OverlordConfig(**base)


def test_shipped_style_overlord_config_is_clean():   # true negative
    rep = lint_overlord_config(good_overlord_cfg(), tree=tree4(),
                               n_sources=4)
    assert rep.ok and len(rep) == 0


def test_config_bad_fill_factor_and_dims():          # CFG301 / CFG302
    rep = lint_overlord_config(good_overlord_cfg(fill_factor=1.5,
                                                 seq_len=0))
    assert {"CFG301", "CFG302"} <= rules(rep)


def test_config_unknown_strategy():                  # CFG303
    rep = lint_overlord_config(OverlordConfig(strategy="nope"))
    assert "CFG303" in rules(rep)


def test_config_missing_and_unknown_strategy_params():   # CFG304
    rep = lint_overlord_config(OverlordConfig(
        strategy="backbone_balance", strategy_params={}))
    assert "CFG304" in rules(rep)
    rep2 = lint_overlord_config(good_overlord_cfg(
        strategy_params=dict(
            costfn=backbone_cost(get_config("qwen3-8b")),
            not_a_param=1)))
    assert "CFG304" in rules(rep2)


def test_config_unknown_axis_needs_tree():           # CFG305
    cfg = good_overlord_cfg()
    cfg.strategy_params["axis"] = "EP"
    assert lint_overlord_config(cfg).ok              # no tree: not checkable
    rep = lint_overlord_config(cfg, tree=tree4())
    assert "CFG305" in rules(rep)


def test_config_capacity_overflow_warns():           # CFG306
    # auto: the token budget is fill_factor of capacity, never over it
    auto = lint_overlord_config(
        good_overlord_cfg(samples_per_step=0, fill_factor=1.0, seq_len=128,
                          rows_per_microbatch=1, n_bins=1), tree=tree4())
    assert "CFG306" not in rules(auto)
    rep = lint_overlord_config(
        good_overlord_cfg(samples_per_step=10_000, seq_len=128,
                          rows_per_microbatch=1, n_bins=1),
        tree=tree4())
    assert "CFG306" in {f.rule for f in rep.warnings}
    rep2 = lint_overlord_config(good_overlord_cfg(samples_per_step=2,
                                                  n_bins=2), tree=tree4())
    assert "CFG306" in {f.rule for f in rep2.errors}  # can't fill bins
    # a budget of 0.6 x 4 x 2 x 1 x 4 = 19 tokens fills 8 bins; 0.2 x 32
    # = 6 tokens cannot
    ok = lint_overlord_config(good_overlord_cfg(
        samples_per_step=0, seq_len=4, rows_per_microbatch=1, n_bins=2),
        tree=tree4())
    assert "CFG306" not in rules(ok)
    rep3 = lint_overlord_config(good_overlord_cfg(
        samples_per_step=0, fill_factor=0.2, seq_len=4,
        rows_per_microbatch=1, n_bins=2), tree=tree4())
    assert "CFG306" in {f.rule for f in rep3.errors}


def test_config_buffer_starvation_warns():           # CFG307
    rep = lint_overlord_config(
        good_overlord_cfg(samples_per_step=512, buffer_target=16),
        tree=tree4(), n_sources=2)
    assert "CFG307" in {f.rule for f in rep.warnings}
    # auto: 0.6 x 4 x 2 x 4 x 512 = 9830 tokens, 4915 per source; 16
    # samples hold at most 16 x 512 = 8192 tokens, 8 at most 4096
    fits = lint_overlord_config(
        good_overlord_cfg(samples_per_step=0, buffer_target=16),
        tree=tree4(), n_sources=2)
    assert "CFG307" not in rules(fits)
    rep2 = lint_overlord_config(
        good_overlord_cfg(samples_per_step=0, buffer_target=8),
        tree=tree4(), n_sources=2)
    assert "CFG307" in {f.rule for f in rep2.warnings}


def test_config_inverted_ckpt_frequencies():         # CFG308
    rep = lint_overlord_config(good_overlord_cfg(
        planner_ckpt_every=8, loader_ckpt_every=1))
    assert "CFG308" in {f.rule for f in rep.warnings}
    rep2 = lint_overlord_config(good_overlord_cfg(planner_ckpt_every=0))
    assert "CFG308" in {f.rule for f in rep2.errors}


def test_config_resilience_knobs():                  # CFG309
    from repro.core import RetryPolicy
    assert lint_overlord_config(good_overlord_cfg()).ok  # true negative
    rep = lint_overlord_config(good_overlord_cfg(
        retry=RetryPolicy(max_attempts=0)))
    assert "CFG309" in {f.rule for f in rep.errors}
    rep2 = lint_overlord_config(good_overlord_cfg(
        retry=RetryPolicy(base_delay_s=2.0, max_delay_s=0.1)))
    assert "CFG309" in {f.rule for f in rep2.errors}
    rep3 = lint_overlord_config(good_overlord_cfg(
        breaker_failures=0, breaker_cooldown_s=-1.0, dlq_capacity=0))
    assert len([f for f in rep3.errors if f.rule == "CFG309"]) == 3


def test_config_pipelining_knobs():                  # CFG310
    assert lint_overlord_config(good_overlord_cfg()).ok  # true negative
    assert lint_overlord_config(
        good_overlord_cfg(plan_ahead=0)).ok  # pipelining off is valid
    rep = lint_overlord_config(good_overlord_cfg(plan_ahead=-1))
    assert "CFG310" in {f.rule for f in rep.errors}
    rep2 = lint_overlord_config(good_overlord_cfg(plan_ahead=2,
                                                  prefetch=0))
    assert "CFG310" in {f.rule for f in rep2.warnings}


def test_config_recovery_knobs():                    # CFG311
    assert lint_overlord_config(good_overlord_cfg()).ok  # true negative
    rep = lint_overlord_config(good_overlord_cfg(manifest_every=0))
    assert "CFG311" in {f.rule for f in rep.errors}
    rep2 = lint_overlord_config(good_overlord_cfg(keep_epochs=0))
    assert "CFG311" in {f.rule for f in rep2.errors}
    rep3 = lint_overlord_config(good_overlord_cfg(
        checkpoint_dir="/tmp/ck", manifest_every=3, loader_ckpt_every=8))
    assert "CFG311" in {f.rule for f in rep3.warnings}
    assert lint_overlord_config(good_overlord_cfg(
        checkpoint_dir="/tmp/ck", manifest_every=4,
        loader_ckpt_every=8)).ok  # aligned cadences


def test_all_shipped_model_configs_clean():          # true negative
    rep = lint_shipped_model_configs()
    assert rep.ok, rep.as_text()
    assert len(rep) == 0


def test_model_config_bad_geometry_and_moe():        # MDL401/402/403
    bad = get_config("qwen3-8b").replace(
        name="bad", head_dim=0, d_model=100, num_heads=3, num_kv_heads=2,
        num_experts=4, experts_per_token=8)
    rep = lint_model_config(bad)
    assert {"MDL401", "MDL402", "MDL403"} <= rules(rep)


def test_model_config_bad_enums():                   # MDL404 / MDL405
    bad = get_config("qwen3-8b").replace(
        name="bad2", family="quantum", dtype="float8", remat="everything")
    rep = lint_model_config(bad)
    assert {"MDL404", "MDL405"} <= rules(rep)


# =====================================================================
# actor-concurrency family (ACT5xx)
# =====================================================================

GOOD_ACTOR = """
from repro.core.actors import Actor

class Fine(Actor):
    def __init__(self):
        self.count = 0

    def bump(self, peer):
        self.count += 1
        return peer.call("ping", timeout=5)

    def checkpoint_state(self):
        return {"count": self.count}

    def restore_state(self, state):
        self.count = state["count"]
"""


def test_shipped_actor_sources_are_clean():          # true negative
    rep = lint_actor_source(GOOD_ACTOR, "good.py")
    assert rep.ok and len(rep) == 0
    for mod in ("source_loader", "planner", "constructor"):
        with open(f"src/repro/core/{mod}.py") as f:
            rep = lint_actor_source(f.read(), mod)
        assert rep.ok, rep.as_text()


def test_actor_thread_mutating_state():              # ACT501
    src = textwrap.dedent("""
        import threading
        from repro.core.actors import Actor

        class Racy(Actor):
            def on_start(self):
                self.items = []
                def pump():
                    self.items = ["x"]
                threading.Thread(target=pump, daemon=True).start()
    """)
    rep = lint_actor_source(src, "racy.py")
    assert "ACT501" in {f.rule for f in rep.errors}


def test_actor_thread_via_self_method_target():      # ACT501
    src = textwrap.dedent("""
        import threading
        from repro.core.actors import Actor

        class Racy2(Actor):
            def on_start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.tick = 1
    """)
    rep = lint_actor_source(src, "racy2.py")
    assert "ACT501" in {f.rule for f in rep.errors}


def test_actor_lock_smell():                         # ACT502
    src = textwrap.dedent("""
        import threading
        from repro.core.actors import Actor

        class Locked(Actor):
            def on_start(self):
                self.lock = threading.Lock()
    """)
    rep = lint_actor_source(src, "locked.py")
    assert "ACT502" in {f.rule for f in rep.warnings}


def test_actor_self_call_deadlock():                 # ACT503
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Deadlock(Actor):
            def __init__(self, runtime):
                self.runtime = runtime

            def poke(self):
                return self.runtime.get(self.name).call("poke")
    """)
    rep = lint_actor_source(src, "deadlock.py")
    assert "ACT503" in {f.rule for f in rep.errors}

    src2 = textwrap.dedent("""
        from repro.core.actors import Actor

        class Deadlock2(Actor):
            def poke(self):
                return self.self_handle.call("poke")
    """)
    assert "ACT503" in rules(lint_actor_source(src2, "deadlock2.py"))


def test_actor_unbounded_call():                     # ACT504
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Forever(Actor):
            def fetch(self, peer):
                return peer.call("slow", timeout=None)
    """)
    rep = lint_actor_source(src, "forever.py")
    assert "ACT504" in {f.rule for f in rep.errors}


def test_actor_half_checkpoint_pair():               # ACT505
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Half(Actor):
            def checkpoint_state(self):
                return {}
    """)
    rep = lint_actor_source(src, "half.py")
    assert "ACT505" in {f.rule for f in rep.errors}


def test_actor_checkpoint_key_never_restored():      # ACT507
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Lossy(Actor):
            def checkpoint_state(self):
                return {"cursor": self.cursor, "buffer": self.buffer}

            def restore_state(self, state):
                self.cursor = state["cursor"]
    """)
    rep = lint_actor_source(src, "lossy.py")
    errs = [f for f in rep.errors if f.rule == "ACT507"]
    assert errs and "'buffer'" in errs[0].message


def test_actor_roundtrip_checkpoint_clean():         # ACT507 true negative
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Full(Actor):
            def checkpoint_state(self):
                return {"cursor": self.cursor, "buffer": self.buffer}

            def restore_state(self, state):
                self.cursor = state["cursor"]
                self.buffer = list(state.get("buffer", []))
    """)
    assert lint_actor_source(src, "full.py").ok


def test_actor_wholesale_restore_not_flagged():      # ACT507 opt-out shape
    src = textwrap.dedent("""
        from repro.core.actors import Actor

        class Wholesale(Actor):
            def checkpoint_state(self):
                return {"cursor": self.cursor, "buffer": self.buffer}

            def restore_state(self, state):
                self.__dict__.update(state)
    """)
    # generic consumption reads every key; nothing provably unread
    assert lint_actor_source(src, "wholesale.py").ok


def test_shipped_actors_roundtrip_clean():           # ACT507 true negative
    import os
    from repro.analysis.actor_lint import lint_actor_paths
    core_dir = os.path.join(os.path.dirname(__file__), os.pardir,
                            "src", "repro", "core")
    rep = lint_actor_paths([os.path.abspath(core_dir)])
    assert "ACT507" not in rules(rep), rep.as_text()


BARE_CALL = textwrap.dedent("""
    class Supervisor:
        def recover(self, handle):
            return handle.call("restore_state", {})
""")


def test_bare_call_in_core_flagged():                # ACT506
    rep = lint_actor_source(BARE_CALL, "src/repro/core/supervisor.py")
    assert "ACT506" in {f.rule for f in rep.warnings}


def test_bare_call_outside_core_not_flagged():       # ACT506 scope
    assert lint_actor_source(BARE_CALL, "src/repro/chaos/driver.py").ok
    # the actor runtime itself is exempt: it implements the mechanism
    assert lint_actor_source(BARE_CALL, "src/repro/core/actors.py").ok


def test_guarded_calls_in_core_not_flagged():        # ACT506 true negative
    src = textwrap.dedent("""
        class Supervisor:
            def recover(self, handle, policy):
                handle.call("restore_state", {}, retry=policy)
                try:
                    handle.call("replay", [])
                except Exception:
                    pass

            def dynamic(self, handle, method):
                return handle.call(method)   # non-literal: out of scope
    """)
    assert lint_actor_source(src, "src/repro/core/supervisor.py").ok


def test_call_in_try_orelse_still_flagged():         # ACT506 orelse gap
    src = textwrap.dedent("""
        class Supervisor:
            def recover(self, handle):
                try:
                    x = 1
                except Exception:
                    pass
                else:
                    handle.call("replay", [])   # runs unguarded
    """)
    rep = lint_actor_source(src, "src/repro/core/supervisor.py")
    assert "ACT506" in {f.rule for f in rep.warnings}


def test_shipped_core_modules_have_no_bare_calls():  # ACT506 repo-wide
    import os
    from repro.analysis.actor_lint import lint_actor_file
    import repro.core as core_pkg
    core_dir = os.path.dirname(core_pkg.__file__)
    rep = Report()
    for fn in sorted(os.listdir(core_dir)):
        if fn.endswith(".py"):
            lint_actor_file(os.path.join(core_dir, fn), rep)
    assert "ACT506" not in rules(rep), rep.as_text()


# =====================================================================
# observability family (OBS6xx)
# =====================================================================

FOREIGN_COUNTER = textwrap.dedent("""
    class Loader:
        def quarantine(self, sid):
            self.dlq._total += 1          # another object's books
            self.dlq._counts[sid] += 1    # subscripted, same problem
""")


def test_foreign_counter_write_flagged():            # OBS601
    rep = lint_observability_source(
        FOREIGN_COUNTER, "src/repro/core/source_loader.py")
    assert len([f for f in rep.warnings if f.rule == "OBS601"]) == 2


def test_foreign_counter_outside_core_not_flagged():  # OBS601 scope
    assert lint_observability_source(
        FOREIGN_COUNTER, "src/repro/chaos/driver.py").ok


def test_own_counters_and_registry_clean():          # OBS601 true negative
    src = textwrap.dedent("""
        class Constructor:
            def drop(self, src):
                self._dropped += 1            # own books: fine
                self._over_count[src] += 1    # own, subscripted: fine
                self.telemetry.inc("constructor_dropped_total", 1.0)
                depth = self.dlq.stats()["held"]   # read via API: fine
                return depth
    """)
    assert lint_observability_source(
        src, "src/repro/core/constructor.py").ok


def test_shipped_core_modules_obs_clean():           # OBS601 repo-wide
    import os
    from repro.analysis import lint_observability_file
    import repro.core as core_pkg
    core_dir = os.path.dirname(core_pkg.__file__)
    rep = Report()
    for fn in sorted(os.listdir(core_dir)):
        if fn.endswith(".py"):
            lint_observability_file(os.path.join(core_dir, fn), rep)
    assert "OBS601" not in rules(rep), rep.as_text()


# =====================================================================
# performance family (PERF7xx)
# =====================================================================

SERIAL_LOOP = textwrap.dedent("""
    def collect(handles):
        out = {}
        for name, h in handles.items():
            out[name] = h.call("snapshot", timeout=10)
        return out
""")


def test_serial_handle_loop_flagged():               # PERF701
    rep = lint_perf_source(SERIAL_LOOP, "src/repro/core/planner.py")
    assert "PERF701" in {f.rule for f in rep.warnings}


def test_serial_loop_outside_core_not_flagged():     # PERF701 scope
    assert lint_perf_source(SERIAL_LOOP, "src/repro/chaos/driver.py").ok
    # the actor runtime is exempt: FanOut's gather loop lives there
    assert lint_perf_source(SERIAL_LOOP, "src/repro/core/actors.py").ok


def test_serial_loop_true_negatives():               # PERF701 true negative
    src = textwrap.dedent("""
        def pipelined(handles, planner):
            out = {}
            # annotated on the loop header: intentional baseline
            for name, h in handles.items():   # perf: serial ok
                out[name] = h.call("snapshot", timeout=10)
            # async fan-out: does not block per handle
            futs = {}
            for name, h in handles.items():
                futs[name] = h.call_async("snapshot")
            # fixed receiver inside a step loop: one RPC per step,
            # not per handle
            for step in range(4):
                planner.call("ensure_planned", step, timeout=30)
            return out, futs
    """)
    assert lint_perf_source(src, "src/repro/core/planner.py").ok


def test_serial_loop_annotation_above_call():        # PERF701 opt-out
    src = textwrap.dedent("""
        def report(handles):
            health = {}
            for name, h in handles.items():
                # perf: serial ok — operator introspection
                health[name] = h.call("health", timeout=10)
            return health
    """)
    assert lint_perf_source(src, "src/repro/core/orchestrator.py").ok


def test_shipped_core_modules_perf_clean():          # PERF701 repo-wide
    import os
    from repro.analysis import lint_perf_file
    import repro.core as core_pkg
    core_dir = os.path.dirname(core_pkg.__file__)
    rep = Report()
    for fn in sorted(os.listdir(core_dir)):
        if fn.endswith(".py"):
            lint_perf_file(os.path.join(core_dir, fn), rep)
    assert "PERF701" not in rules(rep), rep.as_text()


# =====================================================================
# findings / report plumbing
# =====================================================================

def test_report_suppression_and_render():
    rep = Report(disabled=["DG102"])
    assert rep.add("DG102", Severity.ERROR, "suppressed") is None
    rep.add("DG103", Severity.ERROR, "kept", where="x", hint="fix it")
    assert rules(rep) == {"DG103"} and not rep.ok
    assert "fix it" in rep.as_text()
    assert '"rule": "DG103"' in rep.as_json()


# =====================================================================
# launch-time validation + CLI
# =====================================================================

def test_overlord_validate_rejects_bad_config(tmp_path):
    tree = tree4()
    sched = StaticSchedule({"a": 1.0})
    with pytest.raises(AnalysisError) as ei:
        Overlord({}, tree, sched,
                 OverlordConfig(strategy="not_a_strategy"))
    assert any(f.rule == "CFG303" for f in ei.value.report.errors)
    # validate=False opts out (legacy escape hatch)
    ov = Overlord({}, tree, sched,
                  OverlordConfig(strategy="not_a_strategy"),
                  validate=False)
    assert ov.analysis is None
    ov.runtime.shutdown()


def test_overlord_validate_accepts_good_config():
    ov = Overlord({}, tree4(), StaticSchedule({"a": 1.0}),
                  good_overlord_cfg())
    assert ov.analysis is not None and ov.analysis.ok
    ov.runtime.shutdown()


def test_validate_launch_matches_cli_rules():
    rep = validate_launch(OverlordConfig(strategy="nope"), tree4(),
                          n_sources=2)
    assert "CFG303" in rules(rep)


BAD_FIXTURE = """
import threading
from repro.configs.base import ModelConfig
from repro.core.actors import Actor
from repro.core.orchestrator import OverlordConfig

BAD_MODEL = ModelConfig(
    name="bad-fixture", family="dense", num_layers=2, d_model=100,
    num_heads=3, num_kv_heads=2, d_ff=64, vocab_size=0)

BAD_OVERLORD = OverlordConfig(strategy="does_not_exist", fill_factor=0.0)


class BadActor(Actor):
    def checkpoint_state(self):
        return {}

    def wait(self, peer):
        return peer.call("x", timeout=None)
"""


def _run_cli(args):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *args],
        capture_output=True, text=True, env={"PYTHONPATH": "src",
                                             "PATH": "/usr/bin:/bin",
                                             "JAX_PLATFORMS": "cpu"})


def test_cli_nonzero_on_seeded_bad_fixture(tmp_path):
    bad = tmp_path / "bad_fixture.py"
    bad.write_text(BAD_FIXTURE)
    proc = _run_cli([str(bad), "--format", "json"])
    assert proc.returncode == 1, proc.stderr
    import json
    out = json.loads(proc.stdout)
    got = {f["rule"] for f in out["findings"]}
    assert {"MDL401", "CFG303", "ACT504", "ACT505"} <= got


def test_cli_zero_on_shipped_surface():
    proc = _run_cli([])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout
    proc2 = _run_cli(["src/repro/configs"])
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr


def test_cli_disable_suppresses_rule(tmp_path):
    bad = tmp_path / "only_ckpt.py"
    bad.write_text(textwrap.dedent("""
        from repro.core.actors import Actor

        class Half(Actor):
            def restore_state(self, s):
                pass
    """))
    assert lint_main([str(bad)]) == 1
    assert lint_main([str(bad), "--disable", "ACT505"]) == 0
