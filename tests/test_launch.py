"""The training entry point, the compile-cache helper and chip_smoke.py,
on the CPU at reduced() sizes."""
import json
import math
import os
import pathlib
import subprocess
import sys

import pytest
import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import cache, train

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_launch_train_two_steps_reduced(cache_config):
    args = train.build_parser().parse_args(
        ["--reduced", "--steps", "2", "--seq-len", "64", "--dp", "2",
         "--rows", "1"])
    out = train.run(args)
    assert out["config"].name == "qwen3-8b-reduced"
    assert out["compile_s"] > 0
    hist = out["history"]
    assert [h["step"] for h in hist] == [0, 1]
    for h in hist:
        assert math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])
        assert h["tokens"] == h["host_tokens"] > 0
        assert h["fetch_s"] >= 0 and h["step_s"] > 0


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def _python(code_or_script, *, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices}").strip()
    return subprocess.run([sys.executable, *code_or_script], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_refuses_cpu():
    proc = _python(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_data_parallel_step_agrees_with_one_device():
    """4 virtual CPU devices, one DP rank's row each, against the first
    device alone on the same global batches."""
    argv = ["--reduced", "--dp", "4", "--rows", "1", "--seq-len", "64"]
    proc = _python(["-c", "import json, chip_smoke; print(json.dumps("
                    f"chip_smoke.dp_agreement({argv!r})))"], devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["rows"] == 4
    assert res["ok"], res
