"""Runs that must end without a result line."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ne50.sweep",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_naming_the_platform():
    proc = _run(ROOT)
    assert proc.returncode == 2
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
