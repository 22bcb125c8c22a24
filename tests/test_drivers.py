"""Launch-driver smoke tests: train.py and serve.py run end to end on
reduced configs in a subprocess (clean jax device state)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
ENV.pop("XLA_FLAGS", None)


def _run(args, timeout=420):
    return subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                          text=True, env=ENV, timeout=timeout, cwd=REPO)


@pytest.mark.slow
def test_train_driver_gemma_reduced(tmp_path):
    out = _run(["repro.launch.train", "--arch", "gemma-2b", "--reduced",
                "--steps", "4", "--batch", "2", "--seq", "32",
                "--n-clients", "2", "--ckpt-dir", str(tmp_path)])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "participation p=" in out.stdout
    assert "loss" in out.stdout
    assert any(f.startswith("ckpt_") for f in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_driver_rwkv_reduced():
    out = _run(["repro.launch.serve", "--arch", "rwkv6-3b", "--reduced",
                "--batch", "2", "--prompt-len", "4", "--gen", "8"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "generated 8 toks" in out.stdout


@pytest.mark.slow
def test_dryrun_driver_single_combo(tmp_path):
    out = _run(["repro.launch.dryrun", "--arch", "whisper-tiny",
                "--shape", "decode_32k", "--out", str(tmp_path)],
               timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[OK ]" in out.stdout
    files = os.listdir(tmp_path)
    assert len(files) == 1 and files[0].endswith(".json")


@pytest.mark.slow
def test_serve_sweeps_driver_demo(tmp_path):
    """The sweep-service driver serves a synthetic mixed demo workload:
    JSONL responses out, cache/latency summary on stderr, events on disk."""
    import json

    resp_path = tmp_path / "responses.jsonl"
    events_path = tmp_path / "events.jsonl"
    out = _run(["repro.launch.serve_sweeps", "--demo", "6",
                "--max-batch", "4", "--output", str(resp_path),
                "--events", str(events_path)])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 6 responses" in out.stderr
    assert "cache:" in out.stderr
    lines = resp_path.read_text().splitlines()
    assert len(lines) == 6
    resps = [json.loads(line) for line in lines]
    assert all(r["schema"] == "repro.serve/v1" for r in resps)
    assert all(r["ok"] for r in resps)  # seed-0 demo mix is all well-formed
    assert {r["kind"] for r in resps} == {"ne_solve", "calibrate"}
    events = [json.loads(line)
              for line in events_path.read_text().splitlines()]
    assert sum(e["event"] == "serve.request" for e in events) == 6
    assert sum(e["event"] == "serve.complete" for e in events) == 6


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """``enable_compile_cache`` keeps JAX's persistent cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (and sets no other path), else in
    the checkout's fixed ``.jax_cache/``."""
    env = dict(ENV, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            + ("jax.jit(lambda x: x + 1)(1.0).block_until_ready()\n"
               if from_env else ""))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert out.stdout.split() == [want, want]
    if from_env:
        assert os.listdir(tmp_path), "the compile was not cached there"
