"""``bench/run.py`` measures on TPU chips or not at all."""

import json
import os
import shutil
import subprocess
import sys

import benchtest_util as U


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    with open(os.path.join(U.REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_fails_without_a_tpu():
    p = _run_py(U.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_fails_in_a_directory_that_holds_only_the_benchmark(tmp_path):
    """Without the program beside it the benchmark prints no result."""
    shutil.copytree(U.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(U.REPO, "BENCHMARK.json"), tmp_path)
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
