"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip training cell can have
(bench/tests/plant.py), at CPU-test sizes, with the harness's look for a
chip skipped."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "plant.py"),
                        fault], capture_output=True, text=True, env=env,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,number", [
    ("none", None),
    ("unchanged", "update_gap"),
    ("half_batch", "tokens_gap"),
    ("token", "rows_wrong"),
])
def test_one_chip_fault(fault, number):
    out = run(fault)
    checks = out["checks"]
    assert list(out)[-1] == "checks"
    if number is None:
        assert out["correct"], checks
    else:
        assert not out["correct"]
        assert checks[number]["value"] > checks[number]["limit"], checks
