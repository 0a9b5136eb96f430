"""End-to-end behaviour tests for the OVERLORD data plane."""
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    ClientPlaceTree, Overlord, OverlordConfig, StaticSchedule,
)
from repro.core.colocated import ColocatedFleet
from repro.data.cost_models import active_params, backbone_cost, encoder_cost
from repro.data.sources import coyo_like_specs, materialize_group


@pytest.fixture(scope="module")
def source_paths(tmp_path_factory):
    root = tmp_path_factory.mktemp("sources")
    return materialize_group(coyo_like_specs(4), str(root))


def mk_overlord(source_paths, strategy="backbone_balance", tree=None,
                **cfg_kw):
    tree = tree or ClientPlaceTree([("PP", 1), ("DP", 4), ("CP", 1),
                                    ("TP", 2)])
    cfg = get_config("qwen3-8b")
    if strategy == "hybrid_balance":
        params = dict(backbone_costfn=backbone_cost(cfg),
                      encoder_costfn=encoder_cost(48, 1664),
                      broadcast=("TP",))
    elif strategy == "backbone_balance":
        params = dict(costfn=backbone_cost(cfg), broadcast=("TP",))
    else:
        params = dict(costfn=backbone_cost(cfg))
    sched = StaticSchedule({f"coyo_{i:03d}": 1.0 for i in range(4)})
    ov = Overlord(source_paths, tree, sched, OverlordConfig(
        seq_len=512, rows_per_microbatch=2, n_bins=2, strategy=strategy,
        strategy_params=params, **cfg_kw))
    return ov.start()


def test_delivery_and_balancing(source_paths):
    ov = mk_overlord(source_paths, "backbone_balance")
    try:
        for step in range(5):
            views = [ov.get_batch(step, r) for r in range(ov.tree.world)]
            data = [v for v in views if v["role"] == "data"]
            none = [v for v in views if v["role"] == "none"]
            assert len(data) == 4          # DP=4 x TP0 only (broadcast)
            assert len(none) == 4          # TP=1 suppressed
            for v in data:
                assert len(v["bins"]) == 2
                assert v["bins"][0].tokens.shape == (2, 512)
            ov.step_done(step)
        diags = ov.diagnostics()
        im = [d["balance:main"]["imbalance"] for d in diags]
        assert all(i < 1.6 for i in im)    # balanced buckets
    finally:
        ov.shutdown()


def test_balanced_beats_vanilla_imbalance(source_paths):
    res = {}
    for strat in ("vanilla", "backbone_balance"):
        ov = mk_overlord(source_paths, strat)
        try:
            for step in range(4):
                for r in range(ov.tree.world):
                    ov.get_batch(step, r)
                ov.step_done(step)
            ds = ov.diagnostics()
            res[strat] = np.mean(
                [d["balance:main"]["imbalance"] for d in ds])
        finally:
            ov.shutdown()
    assert res["backbone_balance"] < res["vanilla"]


def test_cp_slices_partition_sequence(source_paths):
    tree = ClientPlaceTree([("PP", 1), ("DP", 2), ("CP", 2), ("TP", 1)])
    ov = mk_overlord(source_paths, "backbone_balance", tree=tree)
    try:
        views = [ov.get_batch(0, r) for r in range(tree.world)]
        by_dp = {}
        for r, v in enumerate(views):
            c = tree.coords(r)
            if v["role"] == "data":
                by_dp.setdefault(c["DP"], {})[c["CP"]] = v
        for dp, cps in by_dp.items():
            assert set(cps) == {0, 1}
            full = 512
            got = cps[0]["bins"][0].tokens.shape[1] \
                + cps[1]["bins"][0].tokens.shape[1]
            assert got == full
            # zig-zag slices are disjoint in content ordering: token
            # multisets of the two slices partition the packed row
            t0 = cps[0]["bins"][0].tokens.flatten()
            t1 = cps[1]["bins"][0].tokens.flatten()
            assert len(t0) == len(t1) == full // 2 * 2  # rows=2
        ov.step_done(0)
    finally:
        ov.shutdown()


def test_memory_smaller_than_colocated(tmp_path):
    """The core §7.2 claim at test scale: per-source loaders beat
    per-rank x all-source colocated loaders on resident bytes.  The gap
    widens with sources x ranks x workers (Figs. 4/14); we use a scale
    where all three dimensions are non-trivial."""
    from repro.data.sources import navit_like_specs
    paths = materialize_group(
        [s.__class__(**{**s.__dict__, "n_samples": 256})
         for s in navit_like_specs(12)], str(tmp_path))
    dp, workers = 16, 8
    tree = ClientPlaceTree([("PP", 1), ("DP", dp), ("CP", 1), ("TP", 1)])
    cfg = get_config("qwen3-8b")
    sched = StaticSchedule({n: 1.0 for n in paths})
    from repro.core.autoscale import PartitionLimits
    ov = Overlord(paths, tree, sched, OverlordConfig(
        seq_len=512, rows_per_microbatch=2, n_bins=1,
        strategy="backbone_balance",
        strategy_params=dict(costfn=backbone_cost(cfg), broadcast=()),
        shadows=False, buffer_target=64,
        limits=PartitionLimits(total_workers=16, w_actor=2),
    )).start()
    try:
        for step in range(2):
            for r in range(ov.tree.world):
                ov.get_batch(step, r)
            ov.step_done(step)
        ov_mem = ov.memory_report()["total_ex_shadows"]
    finally:
        ov.shutdown()
    fleet = ColocatedFleet(paths, dp, workers, 512, 2, sched)
    co_mem = fleet.memory_bytes()
    fleet.close()
    assert ov_mem < co_mem, (ov_mem, co_mem)


def test_curriculum_mixture_shifts(source_paths):
    from repro.core import CurriculumSchedule
    tree = ClientPlaceTree([("PP", 1), ("DP", 2), ("CP", 1), ("TP", 1)])
    cfg = get_config("qwen3-8b")
    sched = CurriculumSchedule(
        easy={"coyo_000": 1.0}, hard={"coyo_003": 1.0}, ramp_steps=8)
    ov = Overlord(source_paths, tree, sched, OverlordConfig(
        seq_len=256, rows_per_microbatch=2, n_bins=1,
        strategy="backbone_balance",
        strategy_params=dict(costfn=backbone_cost(cfg), broadcast=()),
    )).start()
    try:
        for step in range(9):
            for r in range(tree.world):
                ov.get_batch(step, r)
            ov.step_done(step)
        diags = ov.planner.call("diagnostics")
        # weights recorded in planner history shift easy -> hard
        hist = ov.planner.call("history_window")
        first = min(hist)
    finally:
        ov.shutdown()
    w0 = sched.weights(0)
    w8 = sched.weights(8)
    assert w0["coyo_000"] > 0.9 and w8["coyo_003"] > 0.9


def test_rwkv6_cost_counts_its_own_block():
    """Published RWKV6-3B, per layer: the time mix's r, k, v, g and output
    matrices (5 d^2 = 32,768,000), its five token-shift LoRAs (5 x 2 x d x
    32 = 819,200) and its decay LoRA (2 x d x 64 = 327,680); the channel
    mix's key and value (2 d d_ff = 45,875,200) and receptance (d^2 =
    6,553,600).  The embedding and the head are not counted.  No attention,
    so the planner's cost is linear in tokens."""
    cfg = get_config("rwkv6-3b")
    per_layer = 86_343_680
    assert active_params(cfg) == 32 * per_layer
    cost = backbone_cost(cfg)
    assert cost.name == "linear"
    assert cost({"text_tokens": 100}) == 6.0 * 32 * per_layer * 100
