"""Shared helpers: a temporary copy of the benchmark at CPU sizes, and a
way to drive one run of it without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _edit(path: Path, **changes):
    data = json.loads(path.read_text())
    for key, value in changes.items():
        data[key] = value
    path.write_text(json.dumps(data))


def make_copy(dest: Path) -> Path:
    """``dest`` holds the benchmark and ``BENCHMARK.json``, with ``src``
    linked in, and its configurations and traffic cut to CPU sizes."""
    from bench import shapes

    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    os.symlink(ROOT / "src", dest / "src")
    cfg = dest / "bench" / "configs" / "resnet18-cifar.json"
    model = json.loads(cfg.read_text())["model"]
    model["stage_widths"] = [8, 16, 32, 64]
    model["param_count"] = shapes.resnet18_params(model)
    _edit(cfg, model=model, max_rounds=3)
    traffic = dest / "bench" / "traffic"
    _edit(traffic / "tableII.json", check_calls=1, check_within=1)
    _edit(traffic / "sweep512.json", batch=8, check_batches=1,
          check_within=1)
    return dest


def run_cell(copy: Path, workload: str, *, seconds: float = 2.0,
             trace: int = 0, seed: int = 2_147_483_659,
             patch: str = "") -> tuple[int, dict | None, str]:
    """One run of ``workload`` in ``copy`` on the CPU, with ``patch``
    (Python source) executed first; returns (exit code, last line, stderr).
    """
    code = "\n".join([
        "import sys",
        f"sys.path.insert(0, {str(copy)!r})",
        f"sys.path.insert(1, {str(copy / 'src')!r})",
        patch,
        "from bench import run",
        f"sys.exit(run.run(['--workload', {workload!r}, '--seed', "
        f"'{seed}', '--seconds', '{seconds}', '--trace', '{trace}'], "
        "require_chip=False))",
    ])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                          capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines else None
    return proc.returncode, last, proc.stderr


@pytest.fixture(scope="session")
def copy(tmp_path_factory) -> Path:
    return make_copy(tmp_path_factory.mktemp("bench-copy"))
