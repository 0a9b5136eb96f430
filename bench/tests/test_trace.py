"""bench/trace.py against a small trace recorded on four TPU v5e chips
(data/dp4_probe.xplane.pb, written by record_trace.py): three steps of a
data-parallel least-squares step, each after a 5 ms host sleep annotated
``bench.fetch``."""
import os

import pytest

from bench import trace as tr

PROBE = os.path.join(os.path.dirname(__file__), "data", "dp4_probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(PROBE))


def test_interval_arithmetic():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.total(merged) == 6
    assert tr.clip(merged, 2, 6) == [(2, 3), (5, 6)]


def test_op_name():
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.3"
    assert tr.op_name("barrier-cores") == "barrier-cores"


def test_probe_devices_and_window(reduced):
    assert reduced["devices"] == 4
    # three 5 ms sleeps and three steps lie between the first and last
    # annotation
    assert 0.015 < reduced["window_s"] < 0.1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])
    assert reduced["idle_share"] > 0.9


def test_probe_top_ops(reduced):
    # the probe's ops run one at a time: the longest is its all-reduce,
    # and all of them together take no longer than the busy time
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    assert ops[0][0] == "all-reduce"
    assert sum(s for _, s in ops) <= reduced["busy_s"] * (1 + 1e-9)


def test_probe_gaps_named_by_host(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) <= 10
    assert [n for n, _ in gaps[:3]] == ["bench.fetch"] * 3
    assert all(s >= 0.004 for _, s in gaps[:3])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_no_device_ops_reads_nothing():
    assert tr.reduce({"devices": {}, "annotations": [(0, 5, "bench.step")]}
                     ) is None
    assert tr.reduce({"devices": {"/device:TPU:0": [(0, 1, "x")]},
                      "annotations": []}) is None
