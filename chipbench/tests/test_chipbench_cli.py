"""Without a TPU, and in a directory that holds only the benchmark, the
harness exits non-zero and prints no result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "wan5_10m.ycsb_b.sweep_1m"


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_no_result_without_a_chip(where, tmp_path):
    cwd = ROOT
    if where == "benchmark_alone":
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(ROOT, "chipbench"),
                        tmp_path / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = str(tmp_path)
    proc = _run(cwd)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
