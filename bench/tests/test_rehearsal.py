"""The CPU rehearsal: a whole run of the harness at CPU-test sizes (the
data path, the window, the checks and the metric readers), the generator's
seed contract, and the command's refusal of anything but a TPU.  Nothing
here measures a device."""
import copy
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from bench import flops, harness, sources
from tiny import tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_end_to_end_run():
    c = tiny_cell()
    out = harness.run_cell(c, 2**31 + 5, 1.0, False, time.perf_counter(),
                           jax.devices()[:1])
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in c["end_to_end"]}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    for v in out["checks"].values():
        assert set(v) == {"value", "limit"}


def test_traced_run_reads_every_layer(tmp_path, monkeypatch):
    table = json.load(open(flops.PEAKS))
    table["devices"]["cpu"] = table["devices"]["TPU v5 lite"]
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps(table))
    monkeypatch.setattr(flops, "PEAKS", str(peaks))
    c = tiny_cell()
    out = harness.run_cell(c, 11, 1.0, True, time.perf_counter(),
                           jax.devices()[:1])
    assert out["correct"], out["checks"]
    # the CPU trace has no TPU device plane, so nothing device-side reads
    want = {m["name"] for m in c["per_layer"]} - {"device_idle_share",
                                                   "step_mfu"}
    assert set(out["metrics"]) == want
    assert 0 < out["metrics"]["pack_fill"]["value"] <= 100


def test_rss_sampler_sees_growth_inside_the_window():
    s = harness.RssSampler(every=0.01)
    s.start()
    block = np.ones(64 << 20, np.uint8)
    time.sleep(0.1)
    s.stop()
    assert s.peak_bytes - s.start_bytes >= 60e6
    assert s.start_bytes > 0
    del block


def test_every_seed_gets_the_same_work():
    mix = copy.deepcopy(harness.cell("qwen3-8b.coyo5")["traffic"])
    spec = sources.source_specs(mix)[1]
    a = sources.source_records(spec, 1, 3, mix["order_block"])
    b = sources.source_records(spec, 1, 2**40 + 3, mix["order_block"])
    assert a == sources.source_records(spec, 1, 3, mix["order_block"])
    assert [r["seed"] for r in a] != [r["seed"] for r in b]
    block = mix["order_block"]
    for i in range(0, len(a), block):
        key = lambda r: (r["text_tokens"], r["image_tokens"],
                         r["transform_cost"])
        assert sorted(map(key, a[i:i + block])) == \
            sorted(map(key, b[i:i + block]))


def test_record_tokens_match_the_program_transform():
    from repro.data.transforms import transform_record
    mix = harness.cell("qwen3-8b.coyo5")["traffic"]
    spec = sources.source_specs(mix)[0]
    recs = sources.source_records(spec, 0, 9, mix["order_block"])[:20]
    ref = sources.record_tokens(mix, 9, 18992)
    for r in recs:
        got = transform_record(r, spec["name"], vocab_size=18992).tokens
        np.testing.assert_array_equal(got, ref[r["sample_id"]])


def test_command_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-8b.coyo5", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_command_needs_the_benchmark_files(tmp_path):
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "nope", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
