"""``run.py`` itself: no TPU, no result; no program, no result."""

import os
import shutil
import subprocess
import sys

import run

ARGS = ["--workload", "sift1m-ivf_flat.batch10k", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_fails_without_a_tpu():
    p = _run(run.ROOT, os.path.join("benchmark", "run.py"))
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    p = _run(str(tmp_path), os.path.join("benchmark", "run.py"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
