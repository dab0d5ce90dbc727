"""The run refuses anything but a TPU, and a checkout with no program;
it keeps JAX's compilation cache where the environment or the checkout
says."""
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import run as bench
from chipbench.harness.cell import BENCH, ROOT

ARGS = ["--workload", "gpt2l-golden18.chat", "--seed", str(2 ** 31 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_device():
    p = _run(ROOT)
    assert p.returncode == bench.EXIT_NO_DEVICE, p.stderr
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def _jax(platform, n):
    dev = SimpleNamespace(platform=platform, device_kind="TPU v5 lite")
    return SimpleNamespace(devices=lambda: [dev] * n)


def test_check_device():
    dev, count = bench.check_device(_jax("tpu", 1), 1)
    assert count == 1 and dev.platform == "tpu"
    with pytest.raises(bench.NoDevice):
        bench.check_device(_jax("tpu", 1), 4)
    with pytest.raises(bench.NoDevice):
        bench.check_device(_jax("cpu", 8), 1)


@pytest.mark.parametrize("env", [None, "/cache/from/the/environment"])
def test_compilation_cache_dir(monkeypatch, env):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    seen = {}
    bench.configure_jax(SimpleNamespace(
        config=SimpleNamespace(update=seen.__setitem__)))
    assert seen.get("jax_compilation_cache_dir") == \
        (None if env else str(bench.CACHE_DIR))
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0
