"""Without a TPU, or without the program beside it, the benchmark exits
non-zero and prints no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fb48_k4.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        return "correct" not in json.loads(last)
    except ValueError:
        return True


def test_refuses_without_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
