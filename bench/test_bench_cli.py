"""The command refuses to run without a TPU, and without the program."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench.conftest import ROOT

ARGS = ["--workload", "protein-2e18.square-sync", "--seed", "5",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "-m", "bench.run", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
